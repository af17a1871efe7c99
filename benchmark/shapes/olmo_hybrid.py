"""Shape of `olmo_hybrid` configurations: the program's ModelSpec from the
published keys, and what the ALGORITHM needs for its Q40 matmuls and for the
gated delta rule, whatever the program does.

Per layer, values. A linear-attention layer: wq, wk H x d_k x d each, wv,
the output gate and wo H x d_v x d each; a full-attention layer: wq, wk, wv,
wo d x d (num_key_value_heads = num_attention_heads); both: a SwiGLU MLP of
3 x intermediate_size x d. Left out of `matmul_work`, because the program
keeps them as one dense bf16 operand of an XLA contraction and not as a Q40
kernel: the decay and step rows W_a, W_b (2 x H x d); `sizing` counts their
bytes.
"""

from workmodel import Q40_BYTES_PER_VALUE

BF16, F32 = 2, 4
KINDS = {"full_attention": 0, "linear_attention": 2}   # models.spec.LayerKind


def spec(config: dict):
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)
    from distributed_llama_tpu.quants.types import FloatType

    c = config
    assert c["linear_num_key_heads"] == c["linear_num_value_heads"]
    return ModelSpec(
        arch=ArchType[c["arch"]], dim=c["hidden_size"],
        hidden_dim=c["intermediate_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        vocab_size=c["vocab_size"], seq_len=c["max_position_embeddings"],
        hidden_act=HiddenAct[c["hidden_act"].upper()],
        # null: no rotation, which the program's header writes as 0
        rope_theta=float(c["rope_parameters"]["rope_theta"] or 0.0),
        weights_float_type=FloatType.Q40, rms_eps=float(c["rms_norm_eps"]),
        mixers=tuple(KINDS[k] for k in c["layer_types"]),
        lin_heads=c["linear_num_key_heads"],
        lin_k_head_dim=c["linear_key_head_dim"],
        lin_v_head_dim=c["linear_value_head_dim"],
        lin_conv_width=c["linear_conv_kernel_dim"],
        lin_beta_scale=2 if c["linear_allow_neg_eigval"] else 1)


def shapes(config: dict) -> dict:
    c = config
    d, h = c["hidden_size"], c["linear_num_key_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    kv = d * c["num_key_value_heads"] // c["num_attention_heads"]
    linear = sum(k == "linear_attention" for k in c["layer_types"])
    return {
        "d": d, "vocab": c["vocab_size"], "heads": h, "dk": dk, "dv": dv,
        "linear_layers": linear,
        "full_layers": c["num_hidden_layers"] - linear,
        "linear_mixer": 2 * h * dk * d + 3 * h * dv * d,    # q k | v gate o
        "decay_rows": 2 * h * d,                            # W_a, W_b
        "full_mixer": 2 * d * d + 2 * kv * d,
        "mlp": 3 * c["intermediate_size"] * d,
        "kv": kv,
        "conv_channels": h * (2 * dk + dv),
        "taps": c["linear_conv_kernel_dim"]}


def q40_values(s: dict) -> int:
    return (s["linear_layers"] * (s["linear_mixer"] + s["mlp"])
            + s["full_layers"] * (s["full_mixer"] + s["mlp"]))


def matmul_work(config: dict, tokens: float, logit_rows: float = 1.0) -> dict:
    """FLOPs and weight bytes one forward over `tokens` real tokens needs
    for its Q40 matmuls: every projection of both layer kinds, the MLPs,
    `logit_rows` positions through the head; every weight read once."""
    s = shapes(config)
    head = s["vocab"] * s["d"]
    return {"flops": 2.0 * tokens * q40_values(s) + 2.0 * logit_rows * head,
            "bytes": (q40_values(s) + head) * Q40_BYTES_PER_VALUE}


def state_bytes_per_slot(s: dict) -> int:
    return s["linear_layers"] * (s["heads"] * s["dk"] * s["dv"] * F32
                                 + (s["taps"] - 1) * s["conv_channels"] * BF16)


def state_work(config: dict, program: str, rows: float, tokens: float) -> dict:
    """What the gated delta rule needs in one execution, over its LIVE rows
    (`rows`) and their real tokens (`tokens`; decode: one a row). A token
    costs, in every linear layer and head, three passes over the d_v x d_k
    state (S k, the rank-one update, S q): 6 d_k d_v FLOPs. Bytes: a live
    row's float32 state read and written once a program, whatever the
    program; and a token's q, k (d_k), v and o (d_v) of every head in
    float32, as the rule takes and gives them."""
    s = shapes(config)
    per_head = s["dk"] * s["dv"]
    layers, h = s["linear_layers"], s["heads"]
    return {"flops": tokens * layers * h * 6.0 * per_head,
            "bytes": layers * h * F32 * (rows * 2.0 * per_head
                                         + tokens * 2.0 * (s["dk"] + s["dv"]))}


def sizing(config: dict) -> dict:
    s = shapes(config)
    f = config["server"]
    q40 = q40_values(s) + s["vocab"] * s["d"]
    bf16 = s["vocab"] * s["d"] + s["linear_layers"] * s["decay_rows"]
    per_token = s["full_layers"] * 2 * s["kv"] * BF16     # K and V rows
    state = state_bytes_per_slot(s)
    return {"weights": int(q40 * Q40_BYTES_PER_VALUE) + bf16 * BF16,
            "cache_per_token": per_token,
            "state_per_slot": state,
            "slots": f["serve_batch"] * (f["max_seq_len"] * per_token + state),
            "arena": f.get("prefix_blocks", 0) * f.get("prefix_block_len", 0)
            * per_token}
