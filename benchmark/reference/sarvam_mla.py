"""Plain reference of the sarvam_mla block: latent attention in its EXPANDED
form (per-head keys and values built from the latent; no cache, nothing
absorbed), a leading dense SwiGLU layer, then layers of sigmoid-scored,
bias-chosen experts with a shared expert, of which this chip holds a share.

    u      = rms(x; g_att)                               eps from the header
    q      = Wq u              per head [q_n (d_n) ; q_r (d_r)]
    [c;k_r]= Wkva u            c~ = rms(c; g_kv)
    q_r, k_r rotated at the token's position (deepseek yarn; ONE k_r a token)
    [k_n,h ; v_h] = Wkvb,h c~
    score_h(t,s) = (q_n,h(t).k_n,h(s) + q_r,h(t).k_r(s)) * (d_n+d_r)^-1/2 * m^2
    m      = 0.1 * mscale_all_dim * ln(factor) + 1
    x      = x + Wo concat_h(sum_s softmax_s(score_h) v_h(s))
    m2     = rms(x; g_ffn)
    layer < n_dense_layers:  x = x + W2 (silu(W1 m2) * (W3 m2))
    else:  s = sigmoid(Wg m2); S = the top-k of s + b; w_i = scale * s_i /
           sum_{j in S} s_j;  x = x + sum_{i in S, held here} w_i E_i(m2)
           + E_shared(m2)

Rope pairs (2j, 2j+1) and writes them de-interleaved, as DeepSeek-V2 does;
cos and sin carry yarn_mscale(factor, mscale) / yarn_mscale(factor,
mscale_all_dim). Experts routed to that this chip does not hold add
nothing: the program leaves them out too, and the weights are normalised
over all the chosen. Departures from the published model are the
configuration's `assumed`. Attention runs over blocks of queries so that
4204 tokens fit beside the engine; one tensor's weights resident at a time.
"""

from __future__ import annotations

import math
import struct

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import F32, Q40, ModelFile, highest

Q_BLOCK = 512

_FLOAT_KEYS = ("routed_scaling", "rms_eps", "rope_factor", "rope_beta_fast",
               "rope_beta_slow", "rope_mscale", "rope_mscale_all_dim")


class MlaFile(ModelFile):
    """The `.m` header keys and tensor order of SARVAM_MLA (README.md at the
    root lists them). A float key holds the bits of a float32."""

    KEYS = {**ModelFile.KEYS, 14: "kv_lora_rank", 15: "qk_nope_head_dim",
            16: "qk_rope_head_dim", 17: "v_head_dim", 18: "n_dense_layers",
            19: "dense_hidden_dim", 20: "n_shared_experts",
            21: "n_routed_experts", 22: "expert_offset",
            23: "routed_scaling", 24: "rms_eps", 25: "rope_factor",
            26: "rope_orig_len", 27: "rope_beta_fast", 28: "rope_beta_slow",
            29: "rope_mscale", 30: "rope_mscale_all_dim"}

    def __init__(self, path: str):
        super().__init__(path)
        for k in _FLOAT_KEYS:
            self.h[k] = struct.unpack("<f", struct.pack("<i", self.h[k]))[0]

    def _plan(self):
        h, d = self.h, self.h["dim"]
        heads, r = h["n_heads"], h["kv_lora_rank"]
        d_n, d_r, d_v = (h["qk_nope_head_dim"], h["qk_rope_head_dim"],
                         h["v_head_dim"])
        hid, dense = h["hidden_dim"], h["dense_hidden_dim"]
        yield "tok_emb", (h["vocab_size"], d), F32
        for l in range(h["n_layers"]):
            p = f"layers.{l}."
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
            yield p + "rms_kv", (r,), F32
        yield "rms_final", (d,), F32
        yield "wcls", (h["vocab_size"], d), Q40


def rms(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_tables(h: dict, t: int):
    """cos, sin (T, d_r / 2) of deepseek yarn."""
    d_r, base, factor = h["qk_rope_head_dim"], float(h["rope_theta"]), \
        h["rope_factor"]
    j = np.arange(d_r // 2, dtype=np.float64)
    plain = base ** (-2.0 * j / d_r)
    if factor > 1:
        def turn_dim(turns):
            return (d_r * math.log(h["rope_orig_len"] / (turns * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(turn_dim(h["rope_beta_fast"])), 0)
        high = min(math.ceil(turn_dim(h["rope_beta_slow"])), d_r - 1)
        ramp = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
        inv = plain / factor * ramp + plain * (1.0 - ramp)
        amp = (yarn_mscale(factor, h["rope_mscale"])
               / yarn_mscale(factor, h["rope_mscale_all_dim"]))
    else:
        inv, amp = plain, 1.0
    ang = np.arange(t, dtype=np.float64)[:, None] * inv
    return (jnp.asarray(np.cos(ang) * amp, jnp.float32),
            jnp.asarray(np.sin(ang) * amp, jnp.float32))


def rotate(x, cos, sin):
    """x (T, heads, d_r): pairs (2j, 2j+1) turn, written de-interleaved."""
    x0, x1 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], -1)


def softmax_scale(h: dict) -> float:
    scale = (h["qk_nope_head_dim"] + h["qk_rope_head_dim"]) ** -0.5
    if h["rope_factor"] > 1 and h["rope_mscale_all_dim"]:
        scale *= yarn_mscale(h["rope_factor"], h["rope_mscale_all_dim"]) ** 2
    return scale


def attention(mf: MlaFile, l: int, x, cos, sin, rope: bool = True):
    """Wo . causal attention, expanded. `rope=False` drops the rope term of
    the score (a control: the check must fail without it)."""
    h, p = mf.h, f"layers.{l}."
    t = x.shape[0]
    heads, r = h["n_heads"], h["kv_lora_rank"]
    d_n, d_r, d_v = h["qk_nope_head_dim"], h["qk_rope_head_dim"], \
        h["v_head_dim"]
    u = rms(x, mf.tensor(p + "rms_att"), h["rms_eps"])
    q = (u @ mf.tensor(p + "wq").T).reshape(t, heads, d_n + d_r)
    kva = u @ mf.tensor(p + "wkva").T
    c = rms(kva[:, :r], mf.tensor(p + "rms_kv"), h["rms_eps"])
    q_r = rotate(q[..., d_n:], cos, sin)
    k_r = rotate(kva[:, None, r:], cos, sin)[:, 0]            # (T, d_r)
    kv = (c @ mf.tensor(p + "wkvb").T).reshape(t, heads, d_n + d_v)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    scale = softmax_scale(h)
    outs = []
    for a in range(0, t, Q_BLOCK):
        b = min(a + Q_BLOCK, t)
        s = jnp.einsum("thd,shd->hts", q[a:b, :, :d_n], k_n[:b])
        if rope:
            s = s + jnp.einsum("thd,sd->hts", q_r[a:b], k_r[:b])
        mask = (jnp.arange(b)[None, :] <= jnp.arange(a, b)[:, None])
        s = jnp.where(mask[None], s * scale, -jnp.inf)
        outs.append(jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v[:b]))
    o = jnp.concatenate(outs, 0).reshape(t, heads * d_v)
    return o @ mf.tensor(p + "wo").T


def swiglu(mf: MlaFile, m, gate: str, down: str, up: str):
    return (jax.nn.silu(m @ mf.tensor(gate).T) * (m @ mf.tensor(up).T)) \
        @ mf.tensor(down).T


def route(h: dict, scores, bias):
    """(chosen indices (T, k), their weights (T, k)) from sigmoid scores."""
    _, top_i = jax.lax.top_k(scores + bias, h["n_active_experts"])
    top_s = jnp.take_along_axis(scores, top_i, -1)
    return top_i, h["routed_scaling"] * top_s / top_s.sum(-1, keepdims=True)


def moe(mf: MlaFile, l: int, m, bias: bool = True, shared: bool = True):
    """This chip's part of the expert layer: its held experts for the
    tokens routed to them, plus the shared expert. `bias=False` chooses by
    the scores alone (a control)."""
    h, p = mf.h, f"layers.{l}."
    scores = jax.nn.sigmoid(m @ mf.tensor(p + "moe_router").T)
    b = mf.tensor(p + "moe_bias") if bias else 0.0
    top_i, top_w = route(h, scores, b)
    out = jnp.zeros_like(m)
    for e in range(h["n_experts"]):
        w_e = jnp.where(top_i == h["expert_offset"] + e, top_w, 0.0) \
            .sum(-1, keepdims=True)
        pe = p + f"experts.{e}."
        out = out + w_e * swiglu(mf, m, pe + "gate", pe + "down", pe + "up")
    if shared and h["n_shared_experts"]:
        out = out + swiglu(mf, m, p + "sh_w1", p + "sh_w2", p + "sh_w3")
    return out


@highest
def forward(model_path: str, tokens: np.ndarray, rope: bool = True,
            bias: bool = True) -> np.ndarray:
    """Logits (T, vocab) of every position of one sequence, float32."""
    mf = MlaFile(model_path)
    h = mf.h
    cos, sin = yarn_tables(h, len(tokens))
    x = mf.rows("tok_emb", tokens)
    for l in range(h["n_layers"]):
        p = f"layers.{l}."
        x = x + attention(mf, l, x, cos, sin, rope)
        m = rms(x, mf.tensor(p + "rms_ffn"), h["rms_eps"])
        if l < h["n_dense_layers"]:
            x = x + swiglu(mf, m, p + "w1", p + "w2", p + "w3")
        else:
            x = x + moe(mf, l, m, bias)
    x = rms(x, mf.tensor("rms_final"), h["rms_eps"])
    return np.asarray(x @ mf.tensor("wcls").T, np.float32)
