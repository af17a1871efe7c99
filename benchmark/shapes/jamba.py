"""Shape of `jamba` configurations: the program's ModelSpec from the
published keys, and what the ALGORITHM needs for its Q40 matmuls and for the
selective scan, whatever the program does.

Per layer, values. A mamba layer's mixer: in_proj 2 x d_inner x d and
out_proj d x d_inner, as Q40 kernels; an attention layer: wq, wo d x d, wk,
wv kv x d (kv = ONE head of d / heads); EVERY layer a dense SwiGLU MLP of 3
x intermediate_size x d (num_experts 1: the expert_layer_* keys select
nothing). Left out of `matmul_work`, because the program keeps them as dense
bf16 operands of XLA contractions and not as Q40 kernels: x_proj ((dt_rank +
2 x d_state) x d_inner) and dt_proj (d_inner x dt_rank); `sizing` counts
their bytes.
"""

from workmodel import Q40_BYTES_PER_VALUE

BF16, F32 = 2, 4
ATTENTION, SSM = 0, 3                     # models.spec.LayerKind


def layer_kinds(config: dict) -> tuple:
    """`transformers`' JambaConfig.layers_block_type: layer l (0-based)
    attends iff l % attn_layer_period == attn_layer_offset."""
    c = config
    return tuple(ATTENTION if l % c["attn_layer_period"]
                 == c["attn_layer_offset"] else SSM
                 for l in range(c["num_hidden_layers"]))


def spec(config: dict):
    from distributed_llama_tpu.models.spec import (ArchType, HiddenAct,
                                                   ModelSpec)
    from distributed_llama_tpu.quants.types import FloatType

    c = config
    assert c["num_experts"] == 1 and c["num_experts_per_tok"] == 1
    assert not c["mamba_proj_bias"] and c["sliding_window"] is None
    return ModelSpec(
        arch=ArchType[c["arch"]], dim=c["hidden_size"],
        hidden_dim=c["intermediate_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        vocab_size=c["vocab_size"], seq_len=c["max_position_embeddings"],
        hidden_act=HiddenAct[c["hidden_act"].upper()],
        rope_theta=0.0,            # Jamba's attention has no positions
        weights_float_type=FloatType.Q40, rms_eps=float(c["rms_norm_eps"]),
        mixers=layer_kinds(c),
        # every channel a head of its own: d_inner heads of width 1
        ssm_heads=c["mamba_expand"] * c["hidden_size"], ssm_head_dim=1,
        ssm_d_state=c["mamba_d_state"], ssm_groups=1,
        ssm_conv_width=c["mamba_d_conv"],
        ssm_conv_bias=int(c["mamba_conv_bias"]),
        ssm_dt_rank=c["mamba_dt_rank"])


def shapes(config: dict) -> dict:
    c = config
    d = c["hidden_size"]
    inner, n, r = c["mamba_expand"] * d, c["mamba_d_state"], c["mamba_dt_rank"]
    kv = d * c["num_key_value_heads"] // c["num_attention_heads"]
    mamba = sum(k == SSM for k in layer_kinds(c))
    return {
        "d": d, "vocab": c["vocab_size"], "layers": c["num_hidden_layers"],
        "mamba_layers": mamba,
        "attention_layers": c["num_hidden_layers"] - mamba,
        "inner": inner, "state": n,
        "mamba_mixer": 3 * inner * d,                 # in_proj | out_proj
        "thin": (r + 2 * n) * inner + inner * r,      # x_proj, dt_proj: dense
        "attention_mixer": 2 * d * d + 2 * kv * d,
        "mlp": 3 * c["intermediate_size"] * d,
        "kv": kv, "taps": c["mamba_d_conv"],
        # float32 leaves a mamba layer: A_log, conv_w, conv_b, dt_bias, D
        # and the three inner norms
        "f32_leaves": inner * (n + c["mamba_d_conv"] + 3) + r + 2 * n}


def q40_values(s: dict) -> int:
    return (s["mamba_layers"] * s["mamba_mixer"]
            + s["attention_layers"] * s["attention_mixer"]
            + s["layers"] * s["mlp"])


def matmul_work(config: dict, tokens: float, logit_rows: float = 1.0) -> dict:
    """FLOPs and weight bytes one forward over `tokens` real tokens needs
    for its Q40 matmuls: in_proj and out_proj of the mamba layers, the
    attention layers' four projections, the MLPs, `logit_rows` positions
    through the head; every weight read once."""
    s = shapes(config)
    head = s["vocab"] * s["d"]
    return {"flops": 2.0 * tokens * q40_values(s) + 2.0 * logit_rows * head,
            "bytes": (q40_values(s) + head) * Q40_BYTES_PER_VALUE}


def state_bytes_per_slot(s: dict) -> int:
    return s["mamba_layers"] * (s["state"] * s["inner"] * F32
                                + (s["taps"] - 1) * s["inner"] * BF16)


def state_work(config: dict, program: str, rows: float, tokens: float) -> dict:
    """What the selective scan needs in one execution, over its LIVE rows
    (`rows`) and their real tokens (`tokens`; decode: one a row). A token
    costs, in every mamba layer, over the N x d_inner state: dt A, the
    decay times the state, dt x times B, the add, C times the state and its
    sum over N, 7 FLOPs a (state index, channel) pair with the
    exponential counted as one (7 x 5120 x 16 a layer). Bytes: a live row's
    float32 state read and written once a program, whatever the program (2
    x 327,680 B a layer); and a token's x, dt and y (d_inner) and B, C (N)
    in float32, as the scan takes and gives them. Only what every
    implementation must move: A, shared by all rows, is left out."""
    s = shapes(config)
    inner, n = s["inner"], s["state"]
    return {"flops": tokens * s["mamba_layers"] * 7.0 * inner * n,
            "bytes": s["mamba_layers"] * F32 * (
                rows * 2.0 * inner * n + tokens * (3.0 * inner + 2.0 * n))}


def sizing(config: dict) -> dict:
    s = shapes(config)
    f = config["server"]
    q40 = q40_values(s) + s["vocab"] * s["d"]
    bf16 = s["vocab"] * s["d"] + s["mamba_layers"] * s["thin"]
    per_token = s["attention_layers"] * 2 * s["kv"] * BF16   # K and V rows
    state = state_bytes_per_slot(s)
    return {"weights": int(q40 * Q40_BYTES_PER_VALUE) + bf16 * BF16
            + s["mamba_layers"] * s["f32_leaves"] * F32,
            "cache_per_token": per_token,
            "state_per_slot": state,
            "slots": f["serve_batch"] * (f["max_seq_len"] * per_token + state),
            "arena": f.get("prefix_blocks", 0) * f.get("prefix_block_len", 0)
            * per_token}
