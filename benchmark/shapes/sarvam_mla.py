"""Shape of `sarvam_mla` configurations: the program's ModelSpec from the
published keys, and what the ALGORITHM needs for its Q40 matmuls and its
latent attention, whatever the program does (all held experts for every
row, four passes over the cache a chunk).

Per layer, values: wq H x (d_n + d_r) x d, wkva (r + d_r) x d, wo d x
H x d_v; the leading dense layers 3 x intermediate_size x d; an expert layer
the shared expert(s) whole and, of the top-k routed experts, the share that
lands on the experts HELD here (k x held / routed a token: one expert with
16 of 128 held and top 8). Left out of `matmul_work`, because the program
keeps them as bf16 operands of XLA contractions and not as Q40 kernels: the
latent's up-projection wkvb (W_uk, W_uv) and the router; `sizing` counts
their bytes.
"""

from workmodel import Q40_BYTES_PER_VALUE

BF16 = 2


def spec(config: dict):
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)
    from distributed_llama_tpu.quants.types import FloatType

    rope = config["rope_scaling"]
    return ModelSpec(
        arch=ArchType[config["arch"]], dim=config["hidden_size"],
        hidden_dim=config["moe_intermediate_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"], n_kv_heads=1,
        vocab_size=config["vocab_size"],
        seq_len=config["max_position_embeddings"],
        hidden_act=HiddenAct[config["hidden_act"].upper()],
        rope_theta=float(config["rope_theta"]),
        n_experts=config["num_experts"],
        n_active_experts=config["num_experts_per_tok"],
        weights_float_type=FloatType.Q40,
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_dense_layers=config["first_k_dense_replace"],
        dense_hidden_dim=config["intermediate_size"],
        n_shared_experts=config["num_shared_experts"],
        n_routed_experts=config.get("published_num_experts",
                                    config["num_experts"]),
        expert_offset=config.get("expert_offset", 0),
        routed_scaling=float(config["routed_scaling_factor"]),
        rms_eps=float(config["rms_norm_eps"]),
        rope_factor=float(rope["factor"]),
        rope_orig_len=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]))


def shapes(config: dict) -> dict:
    c = config
    d, h = c["hidden_size"], c["num_attention_heads"]
    r, d_r = c["kv_lora_rank"], c["qk_rope_head_dim"]
    routed = c.get("published_num_experts", c["num_experts"])
    return {
        "d": d, "layers": c["num_hidden_layers"], "vocab": c["vocab_size"],
        "dense_layers": c["first_k_dense_replace"],
        "attention": (h * (c["qk_nope_head_dim"] + d_r) * d   # wq
                      + (r + d_r) * d                         # wkva
                      + d * h * c["v_head_dim"]),             # wo
        "wkvb": h * (c["qk_nope_head_dim"] + c["v_head_dim"]) * r,
        "dense_ffn": 3 * c["intermediate_size"] * d,
        "expert": 3 * c["moe_intermediate_size"] * d,
        "shared": c["num_shared_experts"],
        "held": c["num_experts"], "routed": routed,
        "top_k": c["num_experts_per_tok"],
        "cache_width": r + d_r, "latent": r, "heads": h}


def moe(config: dict) -> dict:
    """Layers with experts, and what every routing touches of the experts
    HELD here: nothing, when a share is held (a token's top-k may all live
    on other chips); top_k a token when all are."""
    s = shapes(config)
    whole = s["held"] == s["routed"]
    return {"layers": s["layers"] - s["dense_layers"],
            "floor": lambda tokens: {
                "experts": float(min(s["top_k"], s["held"]))
                if whole and tokens > 0 else 0.0,
                "pairs": float(s["top_k"] * tokens) if whole else 0.0}}


def matmul_work(config: dict, tokens: float, logit_rows: float = 1.0,
                experts: float | None = None,
                pairs: float | None = None) -> dict:
    """FLOPs and weight bytes one forward over `tokens` real tokens needs
    for its Q40 matmuls; every weight that some token uses read once.
    `experts`: distinct HELD experts a MoE layer read, `pairs`: (token, held
    expert) pairs a MoE layer computed, as the step's tokens were routed (a
    reader gives both); left out, the expectation under even routing, which
    no reader charges a step by (workmodel.experts_touched says why)."""
    s = shapes(config)
    moe_layers = s["layers"] - s["dense_layers"]
    p_held = s["top_k"] / s["routed"]         # a token picks a given expert
    if pairs is None:
        pairs = tokens * s["held"] * p_held
    if experts is None:
        experts = s["held"] * (1.0 - (1.0 - p_held) ** max(tokens, 0.0))
    flop_vals = (tokens * (s["layers"] * s["attention"]
                           + s["dense_layers"] * s["dense_ffn"]
                           + moe_layers * s["expert"] * s["shared"])
                 + moe_layers * s["expert"] * pairs)
    read = (s["layers"] * s["attention"]
            + s["dense_layers"] * s["dense_ffn"]
            + moe_layers * s["expert"] * (s["shared"] + experts)
            + s["vocab"] * s["d"])
    return {"flops": 2.0 * flop_vals
            + 2.0 * logit_rows * s["vocab"] * s["d"],
            "bytes": read * Q40_BYTES_PER_VALUE}


def attention_work(config: dict, program: str, pairs: float,
                   cached_tokens: float) -> dict:
    """What absorbed latent attention needs in one execution. `pairs`:
    (query token, cached position) pairs over its real rows; a pair costs,
    in every layer and head, a score over the cache row's r + d_r columns
    and a value over its r: 2 x (r + d_r + r) FLOPs a head (139,264 a layer
    at 64 heads, 512 + 64). Bytes: decode reads a cache row (bf16) once a
    pair; a chunk reads each row its slots have cached once (`cached_tokens`,
    summed over its real rows), however many query tiles the kernel makes."""
    s = shapes(config)
    per_pair = 2.0 * s["heads"] * (s["cache_width"] + s["latent"])
    row_bytes = s["cache_width"] * BF16
    rows = pairs if program == "decode" else cached_tokens
    return {"flops": pairs * s["layers"] * per_pair,
            "bytes": rows * s["layers"] * row_bytes}


def sizing(config: dict) -> dict:
    s = shapes(config)
    f = config["server"]
    moe_layers = s["layers"] - s["dense_layers"]
    q40 = (s["layers"] * s["attention"] + s["dense_layers"] * s["dense_ffn"]
           + moe_layers * s["expert"] * (s["shared"] + s["held"])
           + s["vocab"] * s["d"])
    bf16 = (s["layers"] * s["wkvb"] + moe_layers * s["routed"] * s["d"]
            + s["vocab"] * s["d"])            # W_uk/W_uv, router, embedding
    per_token = s["layers"] * s["cache_width"] * BF16   # one latent leaf
    return {"weights": int(q40 * Q40_BYTES_PER_VALUE) + bf16 * BF16,
            "cache_per_token": per_token,
            "slots": f["serve_batch"] * f["max_seq_len"] * per_token,
            "arena": f["prefix_blocks"] * f["prefix_block_len"] * per_token}
