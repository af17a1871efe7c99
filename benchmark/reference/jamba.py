"""Plain reference of the `jamba` block (AI21-Jamba2-3B): layers of a Mamba-1
mixer (the selective scan: a recurrent state a CHANNEL, fed through a short
causal convolution with a bias, whose step is a data-dependent low-rank
projection and whose decay differs over channel and state index) beside
layers of grouped-query softmax attention WITHOUT any positional encoding on
ONE KV head, and a dense SwiGLU MLP in every layer, under a pre-norm serial
block. The recurrence is written TOKEN BY TOKEN (`lax.scan`) with the state
a channel (D, N), the published orientation; the program keeps it (N, D) and
advances it a chunk a kernel call.

    h  = x + mixer(rms(x; g_att))
    x' = h + W2 (silu(W1 u) * (W3 u)),   u = rms(h; g_ffn)
    logit = Wcls rms(x_L; g_final)

    Mamba-1 mixer (Gu and Dao, arXiv:2312.00752, as Jamba has it,
    arXiv:2403.19887; `transformers`' JambaMambaMixer), m = rms(x):
      [x ; z] = W_in m                          2560 -> 2 x 5120, no bias
      x <- silu(conv4(x) + b_conv)              causal, depthwise, over x
                                                ALONE, zeros before token 0
      [r ; B ; C] = W_x x                       5120 -> 160 + 16 + 16, from
                                                the CONVOLVED x, no bias
      r <- rms(r; w_dt), B <- rms(B; w_b), C <- rms(C; w_c)      eps 1e-6
      dt = softplus(W_dt r + b_dt)              160 -> 5120, > 0
      A = -exp(A_log)                           5120 x 16, < 0
      h_t[d, n] = exp(dt_t[d] A[d, n]) h_{t-1}[d, n] + dt_t[d] B_t[n] x_t[d]
      y_t[d] = sum_n C_t[n] h_t[d, n] + D[d] x_t[d]         h_0 = 0, float32
      mix = W_out (y_t * silu(z_t))             NO norm before W_out
    ATTENTION mixer (layer l where l % attn_layer_period == attn_layer_offset):
      mix = Wo softmax_causal(q_h . k * hs^-1/2) v     20 query heads on one
                                                k and one v, no rotation

Departures from the published description are the configuration's
`assumed`: W_in is stored as its halves (wz the gate's rows, wx the x rows);
A_log is stored TRANSPOSED, (16, 5120), the state index outermost (the
program's scan keeps the channels in the lanes; the draw is iid, so the
orientation changes no value's distribution), and read back as (5120, 16)
here; the tied embedding is two tensors of the file; the header holds
rope_theta 0 and the layer kinds as data (key 1000 + l).

Attention runs over blocks of queries and the head over blocks of the
vocabulary; one tensor's weights resident at a time.
"""

from __future__ import annotations

import struct

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import F32, Q40, ModelFile, highest
from .olmo_hybrid import Q_BLOCK, causal_conv, head, rms

SSM = 3                 # LayerKind of the header's per-layer keys
MIXER_KEY0 = 1000       # key MIXER_KEY0 + l holds layer l's kind


class JambaFile(ModelFile):
    """The `.m` header keys and tensor order of JAMBA (README.md at the root
    lists them); rms_eps holds the bits of a float32."""

    KEYS = {**ModelFile.KEYS, 24: "rms_eps", 36: "ssm_heads",
            37: "ssm_head_dim", 38: "ssm_d_state", 39: "ssm_groups",
            40: "ssm_conv_width", 41: "ssm_conv_bias", 47: "ssm_dt_rank"}

    def __init__(self, path: str):
        super().__init__(path)
        self.h["rms_eps"] = struct.unpack(
            "<f", struct.pack("<i", self.h["rms_eps"]))[0]

    def kind(self, l: int) -> int:
        return self.h[MIXER_KEY0 + l]

    def _plan(self):
        h, d, hid = self.h, self.h["dim"], self.h["hidden_dim"]
        inner = h["ssm_heads"] * h["ssm_head_dim"]
        n, r = h["ssm_d_state"], h["ssm_dt_rank"]
        yield "tok_emb", (h["vocab_size"], d), F32
        for l in range(h["n_layers"]):
            p = f"layers.{l}."
            if h[MIXER_KEY0 + l] == SSM:
                yield p + "wz", (inner, d), Q40
                yield p + "wx", (inner, d), Q40
                yield p + "wxp", (r + 2 * n, inner), Q40
                yield p + "wdt", (inner, r), Q40
                yield p + "wo", (d, inner), Q40
                yield p + "conv_w", (h["ssm_conv_width"], inner), F32
                if h["ssm_conv_bias"]:
                    yield p + "conv_b", (inner,), F32
                yield p + "a_log", (n, inner), F32
                yield p + "dt_bias", (inner,), F32
                yield p + "ssm_d", (inner,), F32
                yield p + "rms_dt", (r,), F32
                yield p + "rms_b", (n,), F32
                yield p + "rms_c", (n,), F32
            else:
                yield p + "wq", (d, d), Q40
                yield p + "wk", (self.kv_dim, d), Q40
                yield p + "wv", (self.kv_dim, d), Q40
                yield p + "wo", (d, d), Q40
            yield p + "w1", (hid, d), Q40
            yield p + "w2", (d, hid), Q40
            yield p + "w3", (hid, d), Q40
            yield p + "rms_att", (d,), F32
            yield p + "rms_ffn", (d,), F32
        yield "rms_final", (d,), F32
        yield "wcls", (h["vocab_size"], d), Q40


@jax.jit
def recurrence(x, dt, a, bm, cm):
    """x, dt (T, D), a (D, N), bm and cm (T, N): the scan one token after
    another, h (D, N) from zeros; returns y (T, D) without the skip term."""
    def step(h, xs):
        xt, dtt, bt, ct = xs
        h = (jnp.exp(dtt[:, None] * a) * h
             + (dtt * xt)[:, None] * bt[None, :])
        return h, h @ ct

    h0 = jnp.zeros((x.shape[1], bm.shape[1]), jnp.float32)
    return jax.lax.scan(step, h0, (x, dt, bm, cm))[1]


def mamba_mixer(mf: JambaFile, l: int, m):
    p, h = f"layers.{l}.", mf.h
    n, r, eps = h["ssm_d_state"], h["ssm_dt_rank"], h["rms_eps"]
    z = m @ mf.tensor(p + "wz").T
    x = causal_conv(m @ mf.tensor(p + "wx").T, mf.tensor(p + "conv_w"))
    if h["ssm_conv_bias"]:
        x = x + mf.tensor(p + "conv_b")
    x = jax.nn.silu(x)
    rbc = x @ mf.tensor(p + "wxp").T
    low = rms(rbc[:, :r], mf.tensor(p + "rms_dt"), eps)
    bm = rms(rbc[:, r:r + n], mf.tensor(p + "rms_b"), eps)
    cm = rms(rbc[:, r + n:], mf.tensor(p + "rms_c"), eps)
    dt = jax.nn.softplus(low @ mf.tensor(p + "wdt").T
                         + mf.tensor(p + "dt_bias"))
    a = -jnp.exp(mf.tensor(p + "a_log")).T           # (D, N), as published
    y = recurrence(x, dt, a, bm, cm) + mf.tensor(p + "ssm_d") * x
    return (y * jax.nn.silu(z)) @ mf.tensor(p + "wo").T


def attention_mixer(mf: JambaFile, l: int, m):
    p, h = f"layers.{l}.", mf.h
    heads, kvh = h["n_heads"], h["n_kv_heads"]
    hs, t = h["dim"] // heads, m.shape[0]
    q = (m @ mf.tensor(p + "wq").T).reshape(t, heads, hs)
    k = jnp.repeat((m @ mf.tensor(p + "wk").T).reshape(t, kvh, hs),
                   heads // kvh, axis=1)
    v = jnp.repeat((m @ mf.tensor(p + "wv").T).reshape(t, kvh, hs),
                   heads // kvh, axis=1)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        scores = jnp.einsum("thd,shd->hts", qb, k) * hs ** -0.5
        seen = (jnp.arange(t)[None, :]
                <= (lo + jnp.arange(qb.shape[0]))[:, None])
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs).reshape(t, heads * hs) @ mf.tensor(p + "wo").T


def mlp(mf: JambaFile, l: int, u):
    p = f"layers.{l}."
    gate = jax.nn.silu(u @ mf.tensor(p + "w1").T)
    return (gate * (u @ mf.tensor(p + "w3").T)) @ mf.tensor(p + "w2").T


@highest
def forward(model_path: str, tokens: np.ndarray) -> np.ndarray:
    """Logits (T, vocab) of every position of one sequence, float32."""
    mf = JambaFile(model_path)
    eps = mf.h["rms_eps"]
    x = mf.rows("tok_emb", tokens)
    for l in range(mf.h["n_layers"]):
        p = f"layers.{l}."
        mixer = mamba_mixer if mf.kind(l) == SSM else attention_mixer
        x = x + mixer(mf, l, rms(x, mf.tensor(p + "rms_att"), eps))
        x = x + mlp(mf, l, rms(x, mf.tensor(p + "rms_ffn"), eps))
    return head(mf, x)
