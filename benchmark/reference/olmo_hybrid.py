"""Plain reference of the olmo_hybrid block: layers of the gated delta rule
(a recurrent state a head, fed through a short causal convolution) beside
layers of full softmax attention without rotation, both with the norm on the
sublayer's OUTPUT. The recurrence is written TOKEN BY TOKEN (`lax.scan`), a
different formulation from the program's chunked one on purpose.

    x_0 = E[tokens]
    DELTA layer, u = x (no norm on the input):
      [q ; k ; v] = silu(conv4([Wq u ; Wk u ; Wv u]))   causal, depthwise,
                    over the token axis, zeros before the first token
      q_h <- q_h / |q_h| * d_k^-1/2,  k_h <- k_h / |k_h|        per head
      beta = scale * sigmoid(Wb u),  alpha = exp(-exp(A_log) * softplus(Wa u + dt_bias))
      S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T,  S_0 = 0
      o_t = S_t q_t
      mix = Wo (rms(o_t; g_o) * silu(Wg u))                     rms per head
    ATTENTION layer:
      q = rms(Wq u; g_q), k = rms(Wk u; g_k) over the whole width, v = Wv u
      mix = Wo softmax_causal(q_h . k_h * hs^-1/2) v_h          no rotation
    h  = x + rms(mix; g_att)
    x' = h + rms(W2 (silu(W1 h) * (W3 h)); g_ffn)
    logit = Wcls rms(x_L; g_final)

Departures from the published description are the configuration's `assumed`:
the DELTA layer is flash-linear-attention's GatedDeltaNet (separate
projections, no convolution bias, |.| = sqrt(sum of squares + 1e-6)); the
two norms sit on the sublayers' outputs and q, k are normed at full width as
in OLMo 2 and 3; `rope_theta` null is read as no rotation. Attention runs over
blocks of queries and the head over blocks of the vocabulary, so that 2104
tokens of a 100352-word model fit beside the engine; one tensor's weights
resident at a time.
"""

from __future__ import annotations

import struct

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import F32, Q40, ModelFile, decode_q40, highest

Q_BLOCK = 512
VOCAB_BLOCK = 8192
DELTA = 2               # LayerKind of the header's per-layer keys
MIXER_KEY0 = 1000       # key MIXER_KEY0 + l holds layer l's kind


class HybridFile(ModelFile):
    """The `.m` header keys and tensor order of OLMO_HYBRID (README.md at
    the root lists them); rms_eps holds the bits of a float32."""

    KEYS = {**ModelFile.KEYS, 24: "rms_eps", 31: "lin_heads",
            32: "lin_k_head_dim", 33: "lin_v_head_dim", 34: "lin_conv_width",
            35: "lin_beta_scale"}

    def __init__(self, path: str):
        super().__init__(path)
        self.h["rms_eps"] = struct.unpack(
            "<f", struct.pack("<i", self.h["rms_eps"]))[0]

    def kind(self, l: int) -> int:
        return self.h[MIXER_KEY0 + l]

    def _plan(self):
        h, d, hid = self.h, self.h["dim"], self.h["hidden_dim"]
        n, dk, dv = h["lin_heads"], h["lin_k_head_dim"], h["lin_v_head_dim"]
        yield "tok_emb", (h["vocab_size"], d), F32
        for l in range(h["n_layers"]):
            p = f"layers.{l}."
            if h[MIXER_KEY0 + l] == DELTA:
                yield p + "wq", (n * dk, d), Q40
                yield p + "wk", (n * dk, d), Q40
                yield p + "wv", (n * dv, d), Q40
                yield p + "wg", (n * dv, d), Q40
                yield p + "wa", (n, d), Q40
                yield p + "wb", (n, d), Q40
                yield p + "wo", (d, n * dv), Q40
                yield p + "conv_w", (h["lin_conv_width"],
                                     n * (2 * dk + dv)), F32
                yield p + "a_log", (n,), F32
                yield p + "dt_bias", (n,), F32
                yield p + "rms_o", (dv,), F32
            else:
                yield p + "wq", (d, d), Q40
                yield p + "wk", (self.kv_dim, d), Q40
                yield p + "wv", (self.kv_dim, d), Q40
                yield p + "wo", (d, d), Q40
                yield p + "rms_q", (d,), F32
                yield p + "rms_k", (self.kv_dim,), F32
            yield p + "w1", (hid, d), Q40
            yield p + "w2", (d, hid), Q40
            yield p + "w3", (hid, d), Q40
            yield p + "rms_att", (d,), F32
            yield p + "rms_ffn", (d,), F32
        yield "rms_final", (d,), F32
        yield "wcls", (h["vocab_size"], d), Q40


def rms(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def causal_conv(x, w):
    """x (T, C), w (taps, C): y_t = sum_j w[j] x[t - (taps - 1) + j], rows
    before the first token zeros."""
    taps, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return sum(w[j] * padded[j:j + t] for j in range(taps))


@jax.jit
def recurrence(q, k, v, alpha, beta):
    """q, k (T, H, d_k), v (T, H, d_v), alpha and beta (T, H): the rule one
    token after another, S (H, d_v, d_k) from zeros; returns o (T, H, d_v)."""
    def step(s, xs):
        qt, kt, vt, at, bt = xs
        s = at[:, None, None] * s
        u = bt[:, None] * (vt - jnp.einsum("hvk,hk->hv", s, kt))
        s = s + u[:, :, None] * kt[:, None, :]
        return s, jnp.einsum("hvk,hk->hv", s, qt)

    s0 = jnp.zeros((q.shape[1], v.shape[2], q.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, alpha, beta))[1]


def delta_mixer(mf: HybridFile, l: int, x):
    p, h = f"layers.{l}.", mf.h
    n, dk, dv = h["lin_heads"], h["lin_k_head_dim"], h["lin_v_head_dim"]
    t = x.shape[0]
    qkv = jnp.concatenate([x @ mf.tensor(p + w).T for w in ("wq", "wk", "wv")],
                          axis=-1)
    y = jax.nn.silu(causal_conv(qkv, mf.tensor(p + "conv_w")))
    q = unit(y[:, :n * dk].reshape(t, n, dk)) * dk ** -0.5
    k = unit(y[:, n * dk:2 * n * dk].reshape(t, n, dk))
    v = y[:, 2 * n * dk:].reshape(t, n, dv)
    alpha = jnp.exp(-jnp.exp(mf.tensor(p + "a_log")) * jax.nn.softplus(
        x @ mf.tensor(p + "wa").T + mf.tensor(p + "dt_bias")))
    beta = h["lin_beta_scale"] * jax.nn.sigmoid(x @ mf.tensor(p + "wb").T)
    o = recurrence(q, k, v, alpha, beta)
    z = (x @ mf.tensor(p + "wg").T).reshape(t, n, dv)
    o = rms(o, mf.tensor(p + "rms_o"), h["rms_eps"]) * jax.nn.silu(z)
    return o.reshape(t, n * dv) @ mf.tensor(p + "wo").T


def attention_mixer(mf: HybridFile, l: int, x):
    p, h = f"layers.{l}.", mf.h
    heads, kvh, eps = h["n_heads"], h["n_kv_heads"], h["rms_eps"]
    hs, t = h["dim"] // heads, x.shape[0]
    q = rms(x @ mf.tensor(p + "wq").T, mf.tensor(p + "rms_q"), eps)
    k = rms(x @ mf.tensor(p + "wk").T, mf.tensor(p + "rms_k"), eps)
    v = x @ mf.tensor(p + "wv").T
    q = q.reshape(t, heads, hs)
    k = jnp.repeat(k.reshape(t, kvh, hs), heads // kvh, axis=1)
    v = jnp.repeat(v.reshape(t, kvh, hs), heads // kvh, axis=1)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        scores = jnp.einsum("thd,shd->hts", qb, k) * hs ** -0.5
        seen = (jnp.arange(t)[None, :]
                <= (lo + jnp.arange(qb.shape[0]))[:, None])
        scores = jnp.where(seen[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v))
    att = jnp.concatenate(outs).reshape(t, heads * hs)
    return att @ mf.tensor(p + "wo").T


def head(mf: HybridFile, x) -> np.ndarray:
    """Logits over blocks of the vocabulary (the whole decoded head would
    be 1.5 GB beside the engine)."""
    xn = rms(x, mf.tensor("rms_final"), mf.h["rms_eps"])
    raw, (vocab, d), _ = mf.raw("wcls")
    row_bytes = d // 32 * 18
    out = np.empty((x.shape[0], vocab), np.float32)
    for lo in range(0, vocab, VOCAB_BLOCK):
        hi = min(lo + VOCAB_BLOCK, vocab)
        w = decode_q40(jnp.asarray(raw[lo * row_bytes:hi * row_bytes]),
                       (hi - lo, d))
        out[:, lo:hi] = np.asarray(xn @ w.T)
    return out


@highest
def forward(model_path: str, tokens: np.ndarray) -> np.ndarray:
    """Logits (T, vocab) of every position of one sequence, float32."""
    mf = HybridFile(model_path)
    eps = mf.h["rms_eps"]
    x = mf.rows("tok_emb", tokens)
    for l in range(mf.h["n_layers"]):
        p = f"layers.{l}."
        mixer = delta_mixer if mf.kind(l) == DELTA else attention_mixer
        x = x + rms(mixer(mf, l, x), mf.tensor(p + "rms_att"), eps)
        gate = jax.nn.silu(x @ mf.tensor(p + "w1").T)
        up = x @ mf.tensor(p + "w3").T
        x = x + rms((gate * up) @ mf.tensor(p + "w2").T,
                    mf.tensor(p + "rms_ffn"), eps)
    return head(mf, x)
