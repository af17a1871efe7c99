"""Plain reference of the granitemoehybrid block (Granite 4.0-H): layers of
a Mamba-2 state-space mixer (a recurrent state a head, fed through a short
causal convolution with a bias) beside layers of grouped-query softmax
attention without any positional encoding, and in EVERY layer a sparse
mixture of SwiGLU experts plus a shared expert, under a pre-norm serial block
with four published multipliers. The recurrence is written TOKEN BY TOKEN
(`lax.scan`), a different formulation from the program's chunked one on
purpose.

    x_0 = m_e E[tokens]
    h  = x + m_r mixer(rms(x; g_att))
    x' = h + m_r (experts(u) + shared(u)),   u = rms(h; g_ffn)
    logit = m_l Wcls rms(x_L; g_final)

    SSM mixer, u = rms(x):
      z = Wz u,  xBC = [Wx u ; Wbc u],  dt = softplus(Wdt u + dt_bias)
      [x ; B ; C] = silu(conv4(xBC) + b_conv)    causal, depthwise, zeros
                                                 before the first token
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  S_0 = 0,  A = -exp(A_log)
            (S a head (P, N) float32; B_t, C_t shared by a group's heads)
      y_t = S_t C_t + D x_t
      mix = Wo rms(y * silu(z); g_o)             the gate BEFORE the norm,
                                                 one norm over d_inner
    ATTENTION mixer:
      mix = Wo softmax_causal(q_h . k_h * m_a) v_h      no rotation
    experts: l = Wr u (all routed experts); S = top-k of l; w = softmax of
      l over S; sum over the experts of S HELD here (router index
      expert_offset + i is file expert i) of w_e Down_e (silu(Gate_e u) *
      Up_e u). What the absent experts would add is left out, as in the
      program: this file describes one chip's share.
    shared: W2 (silu(W1 u) * (W3 u)), width n_shared_experts x hidden_dim.

Departures from the published description are the configuration's
`assumed`: W_in is stored as its four row blocks (z, x, B | C, dt); the tied
embedding is two tensors of the file; the shared MLP of width 1536 is kept
as n_shared_experts = 2 of an expert's width (a SwiGLU's hidden units do not
interact, so the two are one function).

`forward(..., routing=[])` also appends, a layer, `top_i` (T, k) and
`margin` (T,), the gap between the k-th and the (k+1)-th router logit, as
reference/mixtral.py does. Attention runs over blocks of queries and the
head over blocks of the vocabulary; one tensor's weights resident at a time.
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
FLOAT_KEYS = ("rms_eps", "embedding_scale", "residual_scale", "attn_scale",
              "logit_scale")


class GraniteFile(ModelFile):
    """The `.m` header keys and tensor order of GRANITE_HYBRID (README.md at
    the root lists them); FLOAT_KEYS hold the bits of a float32."""

    KEYS = {**ModelFile.KEYS, 20: "n_shared_experts", 21: "n_routed_experts",
            22: "expert_offset", 24: "rms_eps", 36: "ssm_heads",
            37: "ssm_head_dim", 38: "ssm_d_state", 39: "ssm_groups",
            40: "ssm_conv_width", 41: "ssm_conv_bias", 42: "embedding_scale",
            43: "residual_scale", 44: "attn_scale", 45: "logit_scale"}

    def __init__(self, path: str):
        super().__init__(path)
        for k in FLOAT_KEYS:
            self.h[k] = struct.unpack("<f", struct.pack("<i", self.h[k]))[0]

    def kind(self, l: int) -> int:
        return self.h[MIXER_KEY0 + l]

    def _plan(self):
        h, d, hid = self.h, self.h["dim"], self.h["hidden_dim"]
        heads, n, g = h["ssm_heads"], h["ssm_d_state"], h["ssm_groups"]
        inner = heads * h["ssm_head_dim"]
        yield "tok_emb", (h["vocab_size"], d), F32
        for l in range(h["n_layers"]):
            p = f"layers.{l}."
            if h[MIXER_KEY0 + l] == SSM:
                yield p + "wz", (inner, d), Q40
                yield p + "wx", (inner, d), Q40
                yield p + "wbc", (2 * g * n, d), Q40
                yield p + "wdt", (heads, d), Q40
                yield p + "wo", (d, inner), Q40
                yield p + "conv_w", (h["ssm_conv_width"],
                                     inner + 2 * g * n), F32
                if h["ssm_conv_bias"]:
                    yield p + "conv_b", (inner + 2 * g * n,), F32
                yield p + "a_log", (heads,), F32
                yield p + "dt_bias", (heads,), F32
                yield p + "ssm_d", (heads,), F32
                yield p + "rms_o", (inner,), F32
            else:
                yield p + "wq", (d, d), Q40
                yield p + "wk", (self.kv_dim, d), Q40
                yield p + "wv", (self.kv_dim, d), Q40
                yield p + "wo", (d, d), Q40
            yield p + "moe_router", (h["n_routed_experts"], d), Q40
            for e in range(h["n_experts"]):
                yield p + f"experts.{e}.up", (hid, d), Q40
                yield p + f"experts.{e}.gate", (hid, d), Q40
                yield p + f"experts.{e}.down", (d, hid), Q40
            if h["n_shared_experts"]:
                sh = h["n_shared_experts"] * hid
                yield p + "sh_w1", (sh, d), Q40
                yield p + "sh_w2", (d, sh), Q40
                yield p + "sh_w3", (sh, d), Q40
            yield p + "rms_att", (d,), F32
            yield p + "rms_ffn", (d,), F32
        yield "rms_final", (d,), F32
        yield "wcls", (h["vocab_size"], d), Q40


@jax.jit
def recurrence(x, dt, a, bm, cm):
    """x (T, H, P), dt (T, H), a (H,), bm and cm (T, H, N) (a group's B and
    C repeated over its heads): the recurrence one token after another, S
    (H, P, N) from zeros; returns y (T, H, P) without the skip term."""
    def step(s, xs):
        xt, dtt, bt, ct = xs
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, ct)

    s0 = jnp.zeros((x.shape[1], x.shape[2], bm.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (x, dt, bm, cm))[1]


def ssm_mixer(mf: GraniteFile, l: int, u):
    p, h = f"layers.{l}.", mf.h
    heads, hd, n, g = (h["ssm_heads"], h["ssm_head_dim"], h["ssm_d_state"],
                       h["ssm_groups"])
    inner, t = heads * hd, u.shape[0]
    z = u @ mf.tensor(p + "wz").T
    xbc = jnp.concatenate([u @ mf.tensor(p + w).T for w in ("wx", "wbc")],
                          axis=-1)
    y = causal_conv(xbc, mf.tensor(p + "conv_w"))
    if h["ssm_conv_bias"]:
        y = y + mf.tensor(p + "conv_b")
    y = jax.nn.silu(y)
    x = y[:, :inner].reshape(t, heads, hd)
    bm = jnp.repeat(y[:, inner:inner + g * n].reshape(t, g, n), heads // g, 1)
    cm = jnp.repeat(y[:, inner + g * n:].reshape(t, g, n), heads // g, 1)
    dt = jax.nn.softplus(u @ mf.tensor(p + "wdt").T
                         + mf.tensor(p + "dt_bias"))
    o = recurrence(x, dt, -jnp.exp(mf.tensor(p + "a_log")), bm, cm)
    o = o + mf.tensor(p + "ssm_d")[:, None] * x
    o = o.reshape(t, inner) * jax.nn.silu(z)
    return rms(o, mf.tensor(p + "rms_o"), h["rms_eps"]) @ mf.tensor(p + "wo").T


def attention_mixer(mf: GraniteFile, l: int, u):
    p, h = f"layers.{l}.", mf.h
    heads, kvh = h["n_heads"], h["n_kv_heads"]
    hs, t = h["dim"] // heads, u.shape[0]
    q = (u @ mf.tensor(p + "wq").T).reshape(t, heads, hs)
    k = jnp.repeat((u @ mf.tensor(p + "wk").T).reshape(t, kvh, hs),
                   heads // kvh, axis=1)
    v = jnp.repeat((u @ mf.tensor(p + "wv").T).reshape(t, kvh, hs),
                   heads // kvh, axis=1)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        scores = jnp.einsum("thd,shd->hts", qb, k) * h["attn_scale"]
        seen = (jnp.arange(t)[None, :]
                <= (lo + jnp.arange(qb.shape[0]))[:, None])
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(outs).reshape(t, heads * hs) @ mf.tensor(p + "wo").T


def experts(mf: GraniteFile, l: int, u, routing: list | None = None,
            shared: bool = True):
    """The held experts' part of the routed sum, plus the shared expert."""
    p, h = f"layers.{l}.", mf.h
    k, off = h["n_active_experts"], h["expert_offset"]
    logits = u @ mf.tensor(p + "moe_router").T
    top_l, top_i = jax.lax.top_k(logits, k)
    if routing is not None:
        best = jax.lax.top_k(logits, k + 1)[0]
        routing.append({"top_i": np.asarray(top_i),
                        "margin": np.asarray(best[:, k - 1] - best[:, k])})
    gates = jax.nn.softmax(top_l, -1)
    out = jnp.zeros_like(u)
    for e in range(h["n_experts"]):
        w_e = jnp.where(top_i == off + e, gates, 0.0).sum(-1, keepdims=True)
        pe = p + f"experts.{e}."
        gate = jax.nn.silu(u @ mf.tensor(pe + "gate").T)
        out = out + w_e * ((gate * (u @ mf.tensor(pe + "up").T))
                           @ mf.tensor(pe + "down").T)
    if shared and h["n_shared_experts"]:
        gate = jax.nn.silu(u @ mf.tensor(p + "sh_w1").T)
        out = out + (gate * (u @ mf.tensor(p + "sh_w3").T)) @ mf.tensor(
            p + "sh_w2").T
    return out


@highest
def forward(model_path: str, tokens: np.ndarray,
            routing: list | None = None) -> np.ndarray:
    """Logits (T, vocab) of every position of one sequence, float32."""
    mf = GraniteFile(model_path)
    h = mf.h
    eps, m_r = h["rms_eps"], h["residual_scale"]
    x = h["embedding_scale"] * mf.rows("tok_emb", tokens)
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        mixer = ssm_mixer if mf.kind(l) == SSM else attention_mixer
        x = x + m_r * mixer(mf, l, rms(x, mf.tensor(p + "rms_att"), eps))
        x = x + m_r * experts(mf, l, rms(x, mf.tensor(p + "rms_ffn"), eps),
                              routing)
    return h["logit_scale"] * head(mf, x)
