"""Plain reference of the Mixtral block: the Llama attention block (rotary
embeddings in the half-split form), then a sparse mixture of SwiGLU experts.

    p     = softmax(Wr m)                       m = rms_norm(x, g_ffn)
    S     = the top-k experts of p,  w_e = p_e / sum_{S} p
    x     = x + sum_{e in S} w_e . Down_e . (silu(Gate_e m) * (Up_e m))

Departures from the published model: none in the equations (softmax over
all experts, then top-k renormalised, equals the published top-k-then-
softmax). The router weights are Q40 in the `.m` format like every other
matrix, and are decoded like them. Every expert is applied to every token
and masked by its weight — plain, not fast; one expert's weights resident
at a time.

`forward(..., routing=[])` also appends, a layer, what the router decided:
`top_i` (T, k), the chosen experts of every token, and `margin` (T,), the
gap between the k-th and the (k+1)-th router logit (a token whose margin is
within rounding of zero can be sent elsewhere by a bf16 program and still
be served right). What the yardstick's tests and the controls count routing
with; the logits do not depend on it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (ModelFile, attention_block, head, highest, rms_norm,
                     rope_half_split)


@highest
def forward(model_path: str, tokens: np.ndarray,
            routing: list | None = None) -> np.ndarray:
    """Logits (T, vocab) of every position of one sequence, float32."""
    mf = ModelFile(model_path)
    n_exp, k = mf.h["n_experts"], mf.h["n_active_experts"]
    x = mf.rows("tok_emb", tokens)
    for l in range(mf.h["n_layers"]):
        p = f"layers.{l}."
        x = attention_block(mf, l, x, rope_half_split)
        m = rms_norm(x, mf.tensor(p + "rms_ffn"))
        scores = m @ mf.tensor(p + "moe_router").T
        probs = jax.nn.softmax(scores, -1)
        top_p, top_i = jax.lax.top_k(probs, k)
        if routing is not None:
            best = jax.lax.top_k(scores, k + 1)[0]
            routing.append({"top_i": np.asarray(top_i),
                            "margin": np.asarray(best[:, k - 1] - best[:, k])})
        top_p = top_p / top_p.sum(-1, keepdims=True)
        out = jnp.zeros_like(x)
        for e in range(n_exp):
            w_e = jnp.where(top_i == e, top_p, 0.0).sum(-1, keepdims=True)
            pe = p + f"experts.{e}."
            gate = jax.nn.silu(m @ mf.tensor(pe + "gate").T)
            up = m @ mf.tensor(pe + "up").T
            out = out + w_e * ((gate * up) @ mf.tensor(pe + "down").T)
        x = x + out
    return np.asarray(head(mf, x), np.float32)
