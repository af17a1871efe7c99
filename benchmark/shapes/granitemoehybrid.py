"""Shape of `granitemoehybrid` configurations: the program's ModelSpec from
the published keys, and what the ALGORITHM needs for its Q40 matmuls and for
the state-space recurrence, whatever the program does.

Per layer, values. A mamba layer's mixer: in_proj's gate and x rows, 2 x
d_inner x d, and out_proj d x d_inner, as Q40 kernels; an attention layer:
wq, wo d x d, wk, wv kv x d; EVERY layer: the shared MLP 3 x
shared_intermediate_size x d whole and, of a token's top-k routed experts of
3 x intermediate_size x d each, those that land on the experts HELD here.
Left out of `matmul_work`, because the program keeps them as dense bf16
operands of XLA contractions and not as Q40 kernels: in_proj's B | C | dt
rows (2 x groups x d_state + heads) and the router (all routed experts x d);
`sizing` counts their bytes.
"""

from workmodel import Q40_BYTES_PER_VALUE

BF16, F32 = 2, 4
KINDS = {"attention": 0, "mamba": 3}      # models.spec.LayerKind


def spec(config: dict):
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)
    from distributed_llama_tpu.quants.types import FloatType

    c = config
    assert c["mamba_n_heads"] * c["mamba_d_head"] == (
        c["mamba_expand"] * c["hidden_size"])
    assert not c["mamba_proj_bias"] and not c["attention_bias"]
    assert c["position_embedding_type"] == "nope"
    assert c["shared_intermediate_size"] % c["intermediate_size"] == 0
    return ModelSpec(
        arch=ArchType[c["arch"]], dim=c["hidden_size"],
        hidden_dim=c["intermediate_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        vocab_size=c["vocab_size"], seq_len=c["max_position_embeddings"],
        hidden_act=HiddenAct[c["hidden_act"].upper()],
        rope_theta=0.0,            # position_embedding_type nope
        n_experts=c["num_local_experts"],
        n_active_experts=c["num_experts_per_tok"],
        weights_float_type=FloatType.Q40, rms_eps=float(c["rms_norm_eps"]),
        # a SwiGLU MLP of twice an expert's width IS two experts' sum
        n_shared_experts=(c["shared_intermediate_size"]
                          // c["intermediate_size"]),
        n_routed_experts=c.get("published_num_local_experts",
                               c["num_local_experts"]),
        expert_offset=c.get("expert_offset", 0),
        mixers=tuple(KINDS[k] for k in c["layer_types"]),
        ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
        ssm_d_state=c["mamba_d_state"], ssm_groups=c["mamba_n_groups"],
        ssm_conv_width=c["mamba_d_conv"],
        ssm_conv_bias=int(c["mamba_conv_bias"]),
        embedding_scale=float(c["embedding_multiplier"]),
        residual_scale=float(c["residual_multiplier"]),
        attn_scale=float(c["attention_multiplier"]),
        logit_scale=1.0 / float(c["logits_scaling"]))


def shapes(config: dict) -> dict:
    c = config
    d, h = c["hidden_size"], c["mamba_n_heads"]
    inner = h * c["mamba_d_head"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    kv = d * c["num_key_value_heads"] // c["num_attention_heads"]
    mamba = sum(k == "mamba" for k in c["layer_types"])
    return {
        "d": d, "vocab": c["vocab_size"], "layers": c["num_hidden_layers"],
        "mamba_layers": mamba,
        "attention_layers": c["num_hidden_layers"] - mamba,
        "heads": h, "head_dim": c["mamba_d_head"], "state": c["mamba_d_state"],
        "mamba_mixer": 3 * inner * d,                 # gate, x | out_proj
        "thin_rows": (2 * gn + h) * d,                # B | C | dt, dense
        "attention_mixer": 2 * d * d + 2 * kv * d,
        "expert": 3 * c["intermediate_size"] * d,
        "shared": 3 * c["shared_intermediate_size"] * d,
        "held": c["num_local_experts"],
        "routed": c.get("published_num_local_experts",
                        c["num_local_experts"]),
        "top_k": c["num_experts_per_tok"],
        "kv": kv, "conv_channels": inner + 2 * gn,
        "taps": c["mamba_d_conv"]}


def moe(config: dict) -> dict:
    """Every layer has experts; what every routing touches of the experts
    HELD here: nothing, when a share is held (a token's top-k may all live
    on the other chip); top_k a token when all are."""
    s = shapes(config)
    whole = s["held"] == s["routed"]
    return {"layers": s["layers"],
            "floor": lambda tokens: {
                "experts": float(min(s["top_k"], s["held"]))
                if whole and tokens > 0 else 0.0,
                "pairs": float(s["top_k"] * tokens) if whole else 0.0}}


def dense_values(s: dict) -> int:
    """Q40 values every token passes: the mixers and the shared MLP."""
    return (s["mamba_layers"] * s["mamba_mixer"]
            + s["attention_layers"] * s["attention_mixer"]
            + s["layers"] * s["shared"])


def matmul_work(config: dict, tokens: float, logit_rows: float = 1.0,
                experts: float | None = None,
                pairs: float | None = None) -> dict:
    """FLOPs and weight bytes one forward over `tokens` real tokens needs
    for its Q40 matmuls; every weight that some token uses read once.
    `experts`: distinct HELD experts a layer read, `pairs`: (token, held
    expert) pairs a layer computed, as the step's tokens were routed (a
    reader gives both); left out, the expectation under even routing, which
    no reader charges a step by (workmodel.experts_touched says why)."""
    s = shapes(config)
    p_held = s["top_k"] / s["routed"]         # a token picks a given expert
    if pairs is None:
        pairs = tokens * s["held"] * p_held
    if experts is None:
        experts = s["held"] * (1.0 - (1.0 - p_held) ** max(tokens, 0.0))
    head = s["vocab"] * s["d"]
    return {"flops": 2.0 * (tokens * dense_values(s)
                            + s["layers"] * s["expert"] * pairs)
            + 2.0 * logit_rows * head,
            "bytes": (dense_values(s) + s["layers"] * s["expert"] * experts
                      + head) * Q40_BYTES_PER_VALUE}


def state_bytes_per_slot(s: dict) -> int:
    return s["mamba_layers"] * (
        s["heads"] * s["head_dim"] * s["state"] * F32
        + (s["taps"] - 1) * s["conv_channels"] * BF16)


def state_work(config: dict, program: str, rows: float, tokens: float) -> dict:
    """What the state-space recurrence needs in one execution, over its LIVE
    rows (`rows`) and their real tokens (`tokens`; decode: one a row). A
    token costs, in every mamba layer and head, three passes over the P x N
    state (the decay, the rank-one write, S C): 6 P N FLOPs (6 x 128 x 64 x
    128 a layer). Bytes: a live row's float32 state read and written once a
    program, whatever the program (2 x 4,194,304 B a layer); and a token's
    x and y (P a head), dt (one a head) and B, C (N, shared by the heads)
    in float32, as the scan takes and gives them."""
    s = shapes(config)
    h, p, n = s["heads"], s["head_dim"], s["state"]
    return {"flops": tokens * s["mamba_layers"] * h * 6.0 * p * n,
            "bytes": s["mamba_layers"] * F32 * (
                rows * 2.0 * h * p * n
                + tokens * (2.0 * h * p + h + 2.0 * n))}


def sizing(config: dict) -> dict:
    s = shapes(config)
    f = config["server"]
    q40 = (dense_values(s) + s["layers"] * s["expert"] * s["held"]
           + s["vocab"] * s["d"])
    bf16 = (s["vocab"] * s["d"] + s["mamba_layers"] * s["thin_rows"]
            + s["layers"] * s["routed"] * s["d"])   # embedding, B|C|dt, router
    per_token = s["attention_layers"] * 2 * s["kv"] * BF16   # K and V rows
    state = state_bytes_per_slot(s)
    return {"weights": int(q40 * Q40_BYTES_PER_VALUE) + bf16 * BF16,
            "cache_per_token": per_token,
            "state_per_slot": state,
            "slots": f["serve_batch"] * (f["max_seq_len"] * per_token + state),
            "arena": f.get("prefix_blocks", 0) * f.get("prefix_block_len", 0)
            * per_token}
