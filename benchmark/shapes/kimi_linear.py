"""Shape of `kimi_linear` configurations: the program's ModelSpec from the
published keys, and what the ALGORITHM needs for its Q40 matmuls, for the
KDA recurrence and for its latent attention, whatever the program does.

Per layer, values. A KDA layer's mixer: wq, wk, wv and wo, 4 x H x d_k x d,
as Q40 kernels; a latent layer's: wq H x (d_n + d_r) x d, wkva (r + d_r) x
d, wo d x H x d_v; the leading dense layers 3 x intermediate_size x d; an
expert layer the shared expert(s) whole and, of a token's top-k routed
experts of 3 x moe_intermediate_size x d each, those that land on the
experts HELD here. Left out of `matmul_work`, because the program keeps them
as dense bf16 operands of XLA contractions and not as Q40 kernels: a KDA
layer's thin projections (the decay's and the gate's low-rank pairs, the
step's rows), a latent layer's up-projection wkvb (W_uk, W_uv) and the
router; `sizing` counts their bytes.
"""

from workmodel import Q40_BYTES_PER_VALUE

BF16, F32 = 2, 4
DELTA, LATENT = 2, 1                      # models.spec.LayerKind


def kinds(config: dict) -> list:
    """LayerKind a layer from the published 1-BASED layer lists."""
    lin = config["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    n = config["num_hidden_layers"]
    assert not kda & full and kda | full == set(range(1, n + 1))
    return [DELTA if l + 1 in kda else LATENT for l in range(n)]


def spec(config: dict):
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)
    from distributed_llama_tpu.quants.types import FloatType

    c, lin = config, config["linear_attn_config"]
    assert c["mla_use_nope"] and c["q_lora_rank"] is None
    assert c["moe_router_activation_func"] == "sigmoid"
    assert c["moe_renormalize"] and c["num_expert_group"] == 1
    assert c["topk_group"] == 1 and c["moe_layer_freq"] == 1
    return ModelSpec(
        arch=ArchType[c["arch"]], dim=c["hidden_size"],
        hidden_dim=c["moe_intermediate_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=1, vocab_size=c["vocab_size"],
        seq_len=c["max_position_embeddings"],
        hidden_act=HiddenAct[c["hidden_act"].upper()],
        rope_theta=0.0,            # mla_use_nope: rope_theta 10000 is unused
        n_experts=c["num_experts"],
        n_active_experts=c["num_experts_per_token"],
        weights_float_type=FloatType.Q40,
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        n_dense_layers=c["first_k_dense_replace"],
        dense_hidden_dim=c["intermediate_size"],
        n_shared_experts=c["num_shared_experts"],
        n_routed_experts=c.get("published_num_experts", c["num_experts"]),
        expert_offset=c.get("expert_offset", 0),
        routed_scaling=float(c["routed_scaling_factor"]),
        rms_eps=float(c["rms_norm_eps"]),
        mixers=tuple(kinds(c)), lin_heads=lin["num_heads"],
        lin_k_head_dim=lin["head_dim"], lin_v_head_dim=lin["head_dim"],
        lin_conv_width=lin["short_conv_kernel_size"], lin_beta_scale=1,
        lin_decay_dim=lin["head_dim"])


def shapes(config: dict) -> dict:
    c, lin = config, config["linear_attn_config"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    n, hd = lin["num_heads"], lin["head_dim"]
    r, d_r = c["kv_lora_rank"], c["qk_rope_head_dim"]
    kda = sum(k == DELTA for k in kinds(c))
    return {
        "d": d, "layers": c["num_hidden_layers"], "vocab": c["vocab_size"],
        "kda_layers": kda, "latent_layers": c["num_hidden_layers"] - kda,
        "dense_layers": c["first_k_dense_replace"],
        "kda_mixer": 4 * n * hd * d,                  # wq wk wv | wo
        # the decay's pair, the gate's pair, the step's rows: dense
        "kda_thin": 2 * (hd * d + n * hd * hd) + n * d,
        "latent_mixer": (h * (c["qk_nope_head_dim"] + d_r) * d   # wq
                         + (r + d_r) * d                         # wkva
                         + d * h * c["v_head_dim"]),             # wo
        "wkvb": h * (c["qk_nope_head_dim"] + c["v_head_dim"]) * r,
        "dense_ffn": 3 * c["intermediate_size"] * d,
        "expert": 3 * c["moe_intermediate_size"] * d,
        "shared": c["num_shared_experts"],
        "held": c["num_experts"],
        "routed": c.get("published_num_experts", c["num_experts"]),
        "top_k": c["num_experts_per_token"],
        "kda_heads": n, "head_dim": hd, "taps": lin["short_conv_kernel_size"],
        "conv_channels": 3 * n * hd,
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


def dense_values(s: dict) -> int:
    """Q40 values every token passes: the mixers, the dense layers' FFN and
    the shared expert(s)."""
    return (s["kda_layers"] * s["kda_mixer"]
            + s["latent_layers"] * s["latent_mixer"]
            + s["dense_layers"] * s["dense_ffn"]
            + (s["layers"] - s["dense_layers"]) * s["expert"] * s["shared"])


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
    head = s["vocab"] * s["d"]
    return {"flops": 2.0 * (tokens * dense_values(s)
                            + moe_layers * s["expert"] * pairs)
            + 2.0 * logit_rows * head,
            "bytes": (dense_values(s) + moe_layers * s["expert"] * experts
                      + head) * Q40_BYTES_PER_VALUE}


def state_bytes_per_slot(s: dict) -> int:
    return s["kda_layers"] * (
        s["kda_heads"] * s["head_dim"] * s["head_dim"] * F32
        + (s["taps"] - 1) * s["conv_channels"] * BF16)


def state_work(config: dict, program: str, rows: float, tokens: float) -> dict:
    """What the KDA recurrence needs in one execution, over its LIVE rows
    (`rows`) and their real tokens (`tokens`; decode: one a row). A token
    costs, in every KDA layer and head, three passes over the d_k x d_v
    state (S^T k, the rank-one write, S^T q), 6 d_k d_v FLOPs, plus the
    decay's pass Diag(a) S, d_k d_v more: 7 x 128 x 128 a head. Bytes: a
    live row's float32 state read and written once a program, whatever the
    program (2 x 2,097,152 B a layer); and a token's q, k and g (d_k a
    head), v and o (d_v a head) and beta (one a head) in float32, as the
    rule takes and gives them."""
    s = shapes(config)
    h, dk = s["kda_heads"], s["head_dim"]
    return {"flops": tokens * s["kda_layers"] * h * 7.0 * dk * dk,
            "bytes": s["kda_layers"] * F32 * (
                rows * 2.0 * h * dk * dk + tokens * h * (5.0 * dk + 1.0))}


def attention_work(config: dict, program: str, pairs: float,
                   cached_tokens: float) -> dict:
    """What absorbed latent attention needs in one execution, in the 7
    latent layers: shapes/sarvam_mla.py's count at this model's sizes.
    `pairs`: (query token, cached position) pairs over its real rows; a
    pair costs, in every latent layer and head, a score over the cache
    row's r + d_r columns and a value over its r: 2 x (r + d_r + r) FLOPs a
    head. Bytes: decode reads a cache row (bf16, 576 wide) once a pair; a
    chunk reads each row its slots have cached once (`cached_tokens`)."""
    s = shapes(config)
    per_pair = 2.0 * s["heads"] * (s["cache_width"] + s["latent"])
    row_bytes = s["cache_width"] * BF16
    rows = pairs if program == "decode" else cached_tokens
    return {"flops": pairs * s["latent_layers"] * per_pair,
            "bytes": rows * s["latent_layers"] * row_bytes}


def sizing(config: dict) -> dict:
    s = shapes(config)
    f = config["server"]
    moe_layers = s["layers"] - s["dense_layers"]
    q40 = (dense_values(s) + moe_layers * s["expert"] * s["held"]
           + s["vocab"] * s["d"])
    bf16 = (s["vocab"] * s["d"] + s["kda_layers"] * s["kda_thin"]
            + s["latent_layers"] * s["wkvb"]
            + moe_layers * s["routed"] * s["d"])
    # embedding, thin projections, W_uk/W_uv, router
    per_token = s["latent_layers"] * s["cache_width"] * BF16   # latent rows
    state = state_bytes_per_slot(s)
    return {"weights": int(q40 * Q40_BYTES_PER_VALUE) + bf16 * BF16,
            "cache_per_token": per_token,
            "state_per_slot": state,
            "slots": f["serve_batch"] * (f["max_seq_len"] * per_token + state),
            "arena": f.get("prefix_blocks", 0) * f.get("prefix_block_len", 0)
            * per_token}
