"""Operations and bytes the algorithm NEEDS, from a configuration's shapes.

The yardstick for the roofline shares: what one execution of a step program
has to compute and read for its matrix multiplications, whatever the program
really does (a program that computes all eight experts for a token routed to
two, or 256 rows for 32 real tokens, needs no more than this). Kept with the
benchmark so that no later PR can move it.

Arithmetic copied from bench.py (`_decode_read_bytes`, `_decode_flops`,
`_ffn_vals_per_layer`), restricted to the matmul weights and extended to MoE
routing over several rows and to prefill.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

# device layout of one Q40 value (ops/pallas_q40.py): 16 packed bytes and a
# 2-byte scale for every 32 values
Q40_BYTES_PER_VALUE = 18 / 32


def load_peaks(device_kind: str) -> dict:
    """Peaks of this device kind; a device that is not in the table is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({sorted(table)})")
    return table[device_kind]


def shapes(config: dict) -> dict:
    d = config["hidden_size"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return {"d": d, "h": config["intermediate_size"],
            "kv": d * kv_heads // heads, "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"],
            "experts": config.get("num_local_experts", 0),
            "top_k": config.get("num_experts_per_tok", 0)}


def attention_values(s: dict) -> int:
    """wq, wo (d x d) and wk, wv (kv x d) of one layer."""
    return 2 * s["d"] * s["d"] + 2 * s["kv"] * s["d"]


def expert_values(s: dict) -> int:
    """up, gate, down of one expert (or of the dense FFN)."""
    return 3 * s["h"] * s["d"]


def experts_touched(s: dict, tokens: float) -> float:
    """Expected number of distinct experts `tokens` tokens route to, each
    choosing top_k of `experts` uniformly (random weights route evenly)."""
    e, k = s["experts"], s["top_k"]
    return e * (1.0 - (1.0 - k / e) ** max(tokens, 0.0))


def matmul_work(config: dict, tokens: float, logit_rows: float = 1.0) -> dict:
    """FLOPs and weight bytes one forward over `tokens` real tokens (summed
    over the rows of the batch) needs for its Q40 matmuls: every token
    through the attention projections, the router and its top-k experts (or
    the dense FFN), `logit_rows` positions through the head; every weight
    that some token uses read once."""
    s = shapes(config)
    moe = s["experts"] > 0
    ffn_vals_per_token = expert_values(s) * (s["top_k"] if moe else 1)
    ffn_vals_read = expert_values(s) * (experts_touched(s, tokens) if moe else 1)
    router = s["experts"] * s["d"] if moe else 0
    head = s["vocab"] * s["d"]
    per_token = s["layers"] * (attention_values(s) + ffn_vals_per_token + router)
    read = s["layers"] * (attention_values(s) + ffn_vals_read + router) + head
    return {"flops": 2.0 * tokens * per_token + 2.0 * logit_rows * head,
            "bytes": read * Q40_BYTES_PER_VALUE}


def roofline_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which peak bounds it."""
    t_c = work["flops"] / peaks["bf16_flops_per_s"]
    t_m = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def sizing(config: dict) -> dict:
    """Device bytes of a deployment of this configuration: packed weights,
    bf16 embedding, slot cache and prefix arena, from the model's own
    sizes."""
    s = shapes(config)
    f = config["server"]
    moe = s["experts"] > 0
    vals = s["layers"] * (attention_values(s)
                          + expert_values(s) * (s["experts"] if moe else 1)
                          + (s["experts"] * s["d"] if moe else 0)) \
        + s["vocab"] * s["d"]
    per_token = 2 * s["layers"] * s["kv"] * 2  # k + v, bf16
    return {"weights": int(vals * Q40_BYTES_PER_VALUE) + s["vocab"] * s["d"] * 2,
            "cache_per_token": per_token,
            "slots": f["serve_batch"] * f["max_seq_len"] * per_token,
            "arena": f["prefix_blocks"] * f["prefix_block_len"] * per_token}
