"""Plain reference of the kimi_linear block (Kimi-Linear-48B-A3B): layers of
Kimi Delta Attention (KDA: the gated delta rule with a decay that is a
VECTOR over a head's key channels) beside layers of latent attention WITHOUT
positions, a leading dense SwiGLU layer, then in every layer sigmoid-scored,
bias-chosen experts with a shared expert, of which this chip holds a share.
The recurrence is written TOKEN BY TOKEN (`lax.scan`) and the attention in
its EXPANDED form (per-head keys and values built from the latent; no cache,
nothing absorbed): other formulations than the program's, on purpose.

    x_0 = E[tokens];  m = rms(x; g_att)                    eps from the header
    KDA layer, per head h of H, d_k = d_v (Kimi Linear report,
    arXiv:2510.26692; flash-linear-attention's KimiDeltaAttention):
      q = W_q m, k = W_k m, v = W_v m                      no bias
      q, k, v <- silu(conv4(.))   causal, depthwise, each channel its own
                  taps, zeros before the first token
      q_h <- q_h / sqrt(sum q_h^2 + 1e-6) x d_k^-1/2,  k_h <- k_h / sqrt(...)
      g_t = -exp(A_log[h]) x softplus(W_f2 (W_f1 m) + dt_bias)   in R^d_k a head
      b_t = sigmoid(W_b m)                                 in (0, 1): no factor 2
      S'  = Diag(exp g_t) S_{t-1}          S (d_k, d_v), rows the key channels
      S_t = S' + b_t k_t (v_t - S'^T k_t)^T,   S_0 = 0
      o_t = S_t^T q_t
      mix = W_o (rms(o_t; g_o) * sigmoid(W_g2 (W_g1 m)))   rms per head
    LATENT layer:
      q = W_q m, per head [q_n (d_n) ; q_p (d_r)];  [c ; k_p] = W_kva m
      c~ = rms(c; g_kv);  [k_n,h ; v_h] = W_kvb,h c~;  k_h = [k_n,h ; k_p]
      mix = W_o concat_h softmax_causal(q_h . k_h x (d_n + d_r)^-1/2) v_h
            NO rotation of q_p, k_p (mla_use_nope)
    h  = x + mix;  m2 = rms(h; g_ffn)
    layer < n_dense_layers:  x' = h + W2 (silu(W1 m2) * (W3 m2))
    else:  s = sigmoid(W_r m2); S = the top-k of s + bias; w_i = scale x s_i /
           sum_{j in S} s_j;  x' = h + sum_{i in S, held here} w_i E_i(m2)
           + E_shared(m2)
    logit = Wcls rms(x_L; g_final)

Departures from the published description are the configuration's `assumed`:
the three published short convolutions are one (taps, 3 H d_k) table, their
channels side by side; one expert group (the config's num_expert_group 1),
so no group step; experts routed to that this chip does not hold add
nothing (the program leaves them out too) and the weights are normalised
over all the chosen. Attention runs over blocks of queries and the head over
blocks of the vocabulary, so that a few thousand tokens fit beside the
engine; one tensor's weights resident at a time.
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

_FLOAT_KEYS = ("routed_scaling", "rms_eps")


class KimiFile(ModelFile):
    """The `.m` header keys and tensor order of KIMI_LINEAR (README.md at
    the root lists them). A float key holds the bits of a float32."""

    KEYS = {**ModelFile.KEYS, 14: "kv_lora_rank", 15: "qk_nope_head_dim",
            16: "qk_rope_head_dim", 17: "v_head_dim", 18: "n_dense_layers",
            19: "dense_hidden_dim", 20: "n_shared_experts",
            21: "n_routed_experts", 22: "expert_offset",
            23: "routed_scaling", 24: "rms_eps", 31: "lin_heads",
            32: "lin_k_head_dim", 33: "lin_v_head_dim", 34: "lin_conv_width",
            35: "lin_beta_scale", 46: "lin_decay_dim"}

    def __init__(self, path: str):
        super().__init__(path)
        for k in _FLOAT_KEYS:
            self.h[k] = struct.unpack("<f", struct.pack("<i", self.h[k]))[0]
        assert self.h["lin_decay_dim"] == self.h["lin_k_head_dim"]
        assert self.h["rope_theta"] == 0 and self.h["lin_beta_scale"] == 1

    def kind(self, l: int) -> int:
        return self.h[MIXER_KEY0 + l]

    def _plan(self):
        h, d = self.h, self.h["dim"]
        n, dk, dv = h["lin_heads"], h["lin_k_head_dim"], h["lin_v_head_dim"]
        heads, r = h["n_heads"], h["kv_lora_rank"]
        d_n, d_r, d_v = (h["qk_nope_head_dim"], h["qk_rope_head_dim"],
                         h["v_head_dim"])
        hid, dense = h["hidden_dim"], h["dense_hidden_dim"]
        yield "tok_emb", (h["vocab_size"], d), F32
        for l in range(h["n_layers"]):
            p = f"layers.{l}."
            delta = h[MIXER_KEY0 + l] == DELTA
            if delta:
                yield p + "wq", (n * dk, d), Q40
                yield p + "wk", (n * dk, d), Q40
                yield p + "wv", (n * dv, d), Q40
                yield p + "wf_a", (dk, d), Q40
                yield p + "wf_b", (n * dk, dk), Q40
                yield p + "wbeta", (n, d), Q40
                yield p + "wg_a", (dv, d), Q40
                yield p + "wg_b", (n * dv, dv), Q40
                yield p + "wo", (d, n * dv), Q40
                yield p + "conv_w", (h["lin_conv_width"],
                                     n * (2 * dk + dv)), F32
                yield p + "a_log", (n,), F32
                yield p + "dt_bias", (n * dk,), F32
                yield p + "rms_o", (dv,), F32
            else:
                yield p + "wq", (heads * (d_n + d_r), d), Q40
                yield p + "wkva", (r + d_r, d), Q40
                yield p + "wkvb", (heads * (d_n + d_v), r), Q40
                yield p + "wo", (d, heads * d_v), Q40
            if l < h["n_dense_layers"]:
                yield p + "w1", (dense, d), Q40
                yield p + "w2", (d, dense), Q40
                yield p + "w3", (dense, d), Q40
            else:
                yield p + "moe_router", (h["n_routed_experts"], d), Q40
                yield p + "moe_bias", (h["n_routed_experts"],), F32
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
            if not delta:
                yield p + "rms_kv", (r,), F32
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
def recurrence(q, k, v, g, beta):
    """q, k and the log decay g (T, H, d_k), v (T, H, d_v), beta (T, H):
    the rule one token after another, S (H, d_k, d_v) from zeros; returns
    o (T, H, d_v)."""
    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = jnp.exp(gt)[:, :, None] * s
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, g, beta))[1]


def kda_mixer(mf: KimiFile, l: int, m):
    p, h = f"layers.{l}.", mf.h
    n, dk, dv = h["lin_heads"], h["lin_k_head_dim"], h["lin_v_head_dim"]
    t = m.shape[0]
    qkv = jnp.concatenate([m @ mf.tensor(p + w).T for w in ("wq", "wk", "wv")],
                          axis=-1)
    y = jax.nn.silu(causal_conv(qkv, mf.tensor(p + "conv_w")))
    q = unit(y[:, :n * dk].reshape(t, n, dk)) * dk ** -0.5
    k = unit(y[:, n * dk:2 * n * dk].reshape(t, n, dk))
    v = y[:, 2 * n * dk:].reshape(t, n, dv)
    f = (m @ mf.tensor(p + "wf_a").T) @ mf.tensor(p + "wf_b").T
    g = (-jnp.exp(mf.tensor(p + "a_log"))[:, None]
         * jax.nn.softplus(f + mf.tensor(p + "dt_bias")).reshape(t, n, dk))
    beta = jax.nn.sigmoid(m @ mf.tensor(p + "wbeta").T)
    o = recurrence(q, k, v, g, beta)
    z = ((m @ mf.tensor(p + "wg_a").T)
         @ mf.tensor(p + "wg_b").T).reshape(t, n, dv)
    o = rms(o, mf.tensor(p + "rms_o"), h["rms_eps"]) * jax.nn.sigmoid(z)
    return o.reshape(t, n * dv) @ mf.tensor(p + "wo").T


def latent_mixer(mf: KimiFile, l: int, m):
    """Wo . causal attention, expanded, no rotation anywhere."""
    h, p = mf.h, f"layers.{l}."
    t = m.shape[0]
    heads, r = h["n_heads"], h["kv_lora_rank"]
    d_n, d_r, d_v = h["qk_nope_head_dim"], h["qk_rope_head_dim"], \
        h["v_head_dim"]
    q = (m @ mf.tensor(p + "wq").T).reshape(t, heads, d_n + d_r)
    kva = m @ mf.tensor(p + "wkva").T
    c = rms(kva[:, :r], mf.tensor(p + "rms_kv"), h["rms_eps"])
    k_p = kva[:, r:]                                           # (T, d_r)
    kv = (c @ mf.tensor(p + "wkvb").T).reshape(t, heads, d_n + d_v)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    scale = (d_n + d_r) ** -0.5
    outs = []
    for a in range(0, t, Q_BLOCK):
        b = min(a + Q_BLOCK, t)
        s = (jnp.einsum("thd,shd->hts", q[a:b, :, :d_n], k_n[:b])
             + jnp.einsum("thd,sd->hts", q[a:b, :, d_n:], k_p[:b]))
        mask = (jnp.arange(b)[None, :] <= jnp.arange(a, b)[:, None])
        s = jnp.where(mask[None], s * scale, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v[:b]))
    o = jnp.concatenate(outs, 0).reshape(t, heads * d_v)
    return o @ mf.tensor(p + "wo").T


def swiglu(mf: KimiFile, m, gate: str, down: str, up: str):
    return (jax.nn.silu(m @ mf.tensor(gate).T) * (m @ mf.tensor(up).T)) \
        @ mf.tensor(down).T


def route(h: dict, scores, bias):
    """(chosen indices (T, k), their weights (T, k)) from sigmoid scores."""
    _, top_i = jax.lax.top_k(scores + bias, h["n_active_experts"])
    top_s = jnp.take_along_axis(scores, top_i, -1)
    return top_i, h["routed_scaling"] * top_s / top_s.sum(-1, keepdims=True)


def moe(mf: KimiFile, l: int, m, routing: list | None = None):
    """This chip's part of the expert layer: its held experts for the
    tokens routed to them, plus the shared expert. `routing`, a list,
    receives the layer's chosen experts (T, k)."""
    h, p = mf.h, f"layers.{l}."
    scores = jax.nn.sigmoid(m @ mf.tensor(p + "moe_router").T)
    top_i, top_w = route(h, scores, mf.tensor(p + "moe_bias"))
    if routing is not None:
        routing.append(np.asarray(top_i))
    out = jnp.zeros_like(m)
    for e in range(h["n_experts"]):
        w_e = jnp.where(top_i == h["expert_offset"] + e, top_w, 0.0) \
            .sum(-1, keepdims=True)
        pe = p + f"experts.{e}."
        out = out + w_e * swiglu(mf, m, pe + "gate", pe + "down", pe + "up")
    if h["n_shared_experts"]:
        out = out + swiglu(mf, m, p + "sh_w1", p + "sh_w2", p + "sh_w3")
    return out


def head(mf: KimiFile, x) -> np.ndarray:
    """Logits over blocks of the vocabulary."""
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
def forward(model_path: str, tokens: np.ndarray,
            routing: list | None = None) -> np.ndarray:
    """Logits (T, vocab) of every position of one sequence, float32.
    `routing`, a list, receives every expert layer's chosen experts (T, k),
    in layer order."""
    mf = KimiFile(model_path)
    h = mf.h
    x = mf.rows("tok_emb", tokens)
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        m = rms(x, mf.tensor(p + "rms_att"), h["rms_eps"])
        mixer = kda_mixer if mf.kind(l) == DELTA else latent_mixer
        x = x + mixer(mf, l, m)
        m = rms(x, mf.tensor(p + "rms_ffn"), h["rms_eps"])
        if l < h["n_dense_layers"]:
            x = x + swiglu(mf, m, p + "w1", p + "w2", p + "w3")
        else:
            x = x + moe(mf, l, m, routing)
    return head(mf, x)
