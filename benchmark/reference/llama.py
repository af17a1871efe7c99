"""Plain reference of the dense Llama-family block (Mistral-7B runs through
it at its own sizes): pre-norm attention with grouped-query heads and rotary
embeddings, then a SwiGLU feed-forward, each added to the residual stream.

    x_0   = E[tokens]
    x     = x + Wo . attn(rope(Wq n), rope(Wk n), Wv n),   n = rms_norm(x, g_att)
    x     = x + W2 . (silu(W1 m) * (W3 m)),                m = rms_norm(x, g_ffn)
    logit = Wcls . rms_norm(x_L, g_final)

Departures from the published model: none in the equations. Mistral-7B's
sliding window is null in the v0.2 config, so attention is full. Weights are
the file's Q40 blocks decoded to float32 — the quantisation is part of the
model being served, not of the program.
"""

from __future__ import annotations

import jax
import numpy as np

from .blocks import (ModelFile, attention_block, head, highest, rms_norm,
                     rope_interleaved)


@highest
def forward(model_path: str, tokens: np.ndarray) -> np.ndarray:
    """Logits (T, vocab) of every position of one sequence, float32; one
    layer's weights resident at a time."""
    mf = ModelFile(model_path)
    x = mf.rows("tok_emb", tokens)
    for l in range(mf.h["n_layers"]):
        p = f"layers.{l}."
        x = attention_block(mf, l, x, rope_interleaved)
        m = rms_norm(x, mf.tensor(p + "rms_ffn"))
        gate = jax.nn.silu(m @ mf.tensor(p + "w1").T)
        up = m @ mf.tensor(p + "w3").T
        x = x + (gate * up) @ mf.tensor(p + "w2").T
    return np.asarray(head(mf, x), np.float32)
