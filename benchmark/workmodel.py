"""Operations and bytes the algorithm NEEDS, from a configuration's shapes.

The yardstick for the roofline shares: what one execution of a step program
has to compute and read for its matrix multiplications, whatever the program
really does (a program that computes all eight experts for a token routed to
two, or 256 rows for 32 real tokens, needs no more than this). Kept with the
benchmark so that no later PR can move it.

Arithmetic copied from bench.py (`_decode_read_bytes`, `_decode_flops`,
`_ffn_vals_per_layer`), restricted to the matmul weights and extended to MoE
routing over several rows and to prefill.

This module is the DEFAULT shape: `spec`, `matmul_work` and `sizing` of a
dense or all-experts-held block whose head size is `hidden_size /
num_attention_heads`. A configuration whose architecture that does not
describe names a module of its own (`"shape": "shapes/<name>.py"`) with the
same three functions; `for_config` is the one way to either.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

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


def for_config(config: dict):
    """The module that knows this configuration's shape: the file its
    `shape` key names (a path inside benchmark/, kept with the benchmark and
    never imported by the program), which states `spec(config)`,
    `matmul_work(config, tokens, logit_rows)` and `sizing(config)` (and,
    with experts, `moe(config)` and `matmul_work`'s `experts=`, `pairs=`);
    this module, the default shape, when there is no such key."""
    rel = config.get("shape")
    if rel is None:
        return sys.modules[__name__]
    path = os.path.normpath(os.path.join(HERE, rel))
    if not (path.startswith(HERE + os.sep) and path.endswith(".py")):
        raise KeyError(f"shape {rel!r} is not a .py file inside benchmark/")
    name = "shape_" + os.path.relpath(path, HERE)[:-3].replace(os.sep, "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)     # OSError: no such file
        missing = [f for f in ("spec", "matmul_work", "sizing")
                   if not callable(getattr(module, f, None))]
        if missing:
            raise KeyError(f"shape {rel!r} lacks {missing}")
        sys.modules[name] = module
    return sys.modules[name]


def spec(config: dict):
    """The program's ModelSpec: published names on the left, the program's
    on the right."""
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)
    from distributed_llama_tpu.quants.types import FloatType

    return ModelSpec(
        arch=ArchType[config["arch"]], dim=config["hidden_size"],
        hidden_dim=config["intermediate_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        vocab_size=config["vocab_size"],
        seq_len=config["max_position_embeddings"],
        hidden_act=HiddenAct[config["hidden_act"].upper()],
        rope_theta=float(config["rope_theta"]),
        n_experts=config.get("num_local_experts", 0),
        n_active_experts=config.get("num_experts_per_tok", 0),
        weights_float_type=FloatType.Q40)


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
    """EXPECTED number of distinct experts `tokens` tokens route to, if each
    chose top_k of `experts` uniformly. What even routing would touch: for
    sizing arithmetic and for holding a draw's observed routing against
    (PERF.md section 6, PR 37). No reader charges a step by it: uniform-byte
    weights sent every token to one pair, and the expectation charged a
    program for experts nobody chose (133.9 %; ledger, PR 35)."""
    e, k = s["experts"], s["top_k"]
    return e * (1.0 - (1.0 - k / e) ** max(tokens, 0.0))


def moe(config: dict) -> dict | None:
    """What a reader needs to know of a configuration's experts: how many
    layers have them, and how many experts and (token, expert) pairs a
    layer EVERY routing of `tokens` tokens touches (`floor`). None: no
    experts. Here all the router's experts are held, so a token's top_k
    pairs are arithmetic and one token already touches top_k experts."""
    s = shapes(config)
    if not s["experts"]:
        return None
    return {"layers": s["layers"],
            "floor": lambda tokens: {
                "experts": float(min(s["top_k"], s["experts"]))
                if tokens > 0 else 0.0,
                "pairs": float(s["top_k"] * tokens)}}


def matmul_work(config: dict, tokens: float, logit_rows: float = 1.0,
                experts: float | None = None,
                pairs: float | None = None) -> dict:
    """FLOPs and weight bytes one forward over `tokens` real tokens (summed
    over the rows of the batch) needs for its Q40 matmuls: every token
    through the attention projections, the router and its top-k experts (or
    the dense FFN), `logit_rows` positions through the head; every weight
    that some token uses read once. With experts: `experts` distinct experts
    a layer read and `pairs` (token, expert) pairs a layer computed, as the
    step's own tokens were routed (a reader gives both, observed or `moe`'s
    floor); left out, the expectation under even routing."""
    s = shapes(config)
    has_experts = s["experts"] > 0
    if has_experts:
        if experts is None:
            experts = experts_touched(s, tokens)
        if pairs is None:
            pairs = s["top_k"] * tokens
        ffn_flop_vals = expert_values(s) * pairs
        ffn_vals_read = expert_values(s) * experts
    else:
        ffn_flop_vals = expert_values(s) * tokens
        ffn_vals_read = expert_values(s)
    router = s["experts"] * s["d"] if has_experts else 0
    head = s["vocab"] * s["d"]
    flop_vals = s["layers"] * (tokens * (attention_values(s) + router)
                               + ffn_flop_vals)
    read = s["layers"] * (attention_values(s) + ffn_vals_read + router) + head
    return {"flops": 2.0 * flop_vals + 2.0 * logit_rows * head,
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
