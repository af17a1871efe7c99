"""The closed vocabulary of `jax.named_scope` names in the step programs.

A scope is op metadata: it changes no compiled program, and it is how a
profiler capture's report (runtime/profiler.capture_report) says which
lines of the model a device op belongs to: an op's `op_name` cut down to
the names below, joined by `/` (`ffn/act_q80`, `moe_shared/ffn`), and
`unscoped` where it carries none. No layer index in a name: the unrolled
layers sum under one key. docs/observability.md has the table of what each
name wraps; tests/test_device_scopes.py holds every served step program to
this tuple.
"""

DEVICE_SCOPES = (
    "embed",        # token lookup and its scales (forward)
    "attn_proj",    # input norm, q / k / v or the latent projections,
    #                 q / k norms, rope
    "attn_cache",   # the cache write: kernel, scatter or update-slice
    "attn_core",    # flash_attention / mla_attention / the XLA attention
    #                 and the relayouts around it
    "attn_out",     # wo
    "mla_absorb",   # W_uk into the query, W_uv out of the attended latent
    "ffn",          # pre-FFN norm, gate / up, activation, down
    "moe_router",   # router matmul, scores, top-k, weights
    "moe_routed",   # the routed experts: pair bookkeeping, the grouped
    #                 kernel calls, the wave loop, the weighted sum
    "moe_shared",   # the shared expert (a dense ffn nests under it)
    "gdn_proj", "gdn_conv", "gdn_rule", "gdn_out",    # gated delta rule
    "ssm_proj", "ssm_conv", "ssm_scan", "ssm_out",    # state-space mixers
    "ssm_dt",       # Mamba-1 alone: x_proj, the norms on dt, B and C,
    #                 dt_proj and the softplus, between convolution and scan
    "kda_proj", "kda_conv", "kda_rule", "kda_out",    # Kimi Delta Attention
    "block_tail",   # residual adds and output norms of no sublayer
    "head",         # final norm, row pick, wcls, logit scales
    "act_q80",      # the Q80 round trip of a matmul's input (ops/matmul),
    #                 nested under its caller
)
