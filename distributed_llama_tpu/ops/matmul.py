"""Weight-format-dispatching matmul.

TPU-native equivalent of the reference's matmul dispatcher over (weight dtype
x input dtype) pairs (ref: src/funcs.cpp:413-454). Weights are stored either
dense (f32/bf16) or as Q40 `QuantizedTensor`s kept packed in HBM; the Q40
path dequantizes inline — XLA fuses the nibble-unpack + scale multiply into
the matmul's operand read, which is the bring-up analogue of the reference's
fused Q40xQ80 NEON/AVX2 kernel (ref: src/funcs.cpp:286-385). The Pallas
int4-dot kernel (ops/pallas_q40.py) replaces this on TPU for the hot path.

Convention matches the reference: weight W has logical shape (d, n) (d output
rows), activations are (..., n), output is (..., d) = x @ W^T.
"""

from __future__ import annotations

import math
from typing import Union

import jax
import jax.numpy as jnp

from ..quants.jax_codec import QuantizedTensor, dequantize_q40_jax, quantize_q80_jax, dequantize_q80_jax

WeightFormat = Union[jnp.ndarray, QuantizedTensor]


def local_matmul(
    x: jnp.ndarray,
    w: WeightFormat,
    *,
    compute_dtype,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-device matmul core: Pallas fused Q40 kernel when the operands
    qualify, XLA dequant einsum otherwise. Shared by matmul() and the
    shard_map per-shard bodies (parallel/tp_q80.py) so the kernel
    preconditions and fallback live in exactly one place. The fallback is
    by design for segments of more than pallas_q40.MAX_T rows (FLOPs-
    amortized prefill) and it is silent HERE — the compile ledger records
    per minted executable which kernels are in it (runtime/profiler.
    _kernels_in -> /stats `compiles.by_key[*].kernels`), which is where a
    serving width that lost the kernel shows (e.g. --serve-batch 8 with
    the default 256-wide chunk: 2048 rows)."""
    x = x.astype(compute_dtype)
    if isinstance(w, QuantizedTensor):
        if use_pallas:
            from .pallas_q40 import q40_matmul, supports_pallas

            t = 1
            for s in x.shape[:-1]:
                t *= s
            if supports_pallas(w, t):
                return q40_matmul(x, w, out_dtype=compute_dtype,
                                  interpret=interpret)
        wd = dequantize_q40_jax(w, dtype=compute_dtype)
    else:
        wd = w.astype(compute_dtype)
    return jnp.einsum("...n,dn->...d", x, wd,
                      preferred_element_type=compute_dtype)


def matmul(
    x: jnp.ndarray,
    w: WeightFormat,
    *,
    activation_q80: bool = False,
    compute_dtype=jnp.float32,
    use_pallas: bool = False,
    tp_mesh=None,
    tp_reduce: str = "exact",
    pallas_interpret: bool = False,
    manual_tp: int = 0,
    manual_ep: int = 0,  # carried in the pp region's cfg for the MoE
    # block (ep_moe._ep_body); dense matmuls ignore it — ep shards only
    # the expert axis, every other weight is replicated across ep
    manual_sp: int = 0,  # likewise: sp shards only the KV cache's
    # sequence dim (transformer._attention_block), never a matmul operand
) -> jnp.ndarray:
    """y[..., d] = sum_n x[..., n] * W[d, n].

    activation_q80=True round-trips the activation through Q80 blocks first,
    reproducing the reference's quantized activation buffers
    (ref: src/tasks.cpp:124-148) for bit-accuracy experiments.

    use_pallas=True routes Q40 weights through the fused dequant-matmul TPU
    kernel (ops/pallas_q40.py) when its layout preconditions hold.

    tp_mesh: mesh for the explicit shard_map execution paths — weights
    arrive as TpRowWeight (row-split, communication-free) or TpColWeight
    (col-split partial sums, reduced per tp_reduce: "exact" psum or the
    reference's "q80" compressed exchange) — parallel/tp_q80.py.

    manual_tp: > 0 when the caller is ALREADY inside a fully-manual region
    (the pipeline-parallel layer loop, parallel/pp.py) with tp manual and
    this many shards: Tp-marked weights are shard-local there, so row splits
    run the local kernel directly and col splits reduce with an explicit
    psum — no shard_map entry (which cannot nest).
    """
    if activation_q80:
        # a DEVICE_SCOPES name (models/scopes.py), nested under the caller's
        with jax.named_scope("act_q80"):
            q, scales = quantize_q80_jax(x)
            x = dequantize_q80_jax(q, scales, dtype=compute_dtype)
    else:
        x = x.astype(compute_dtype)

    from ..parallel.tp_q80 import (
        TpColWeight, TpRowWeight, manual_psum, tp_col_matmul, tp_row_matmul)

    if manual_tp:
        from ..parallel.mesh import TP_AXIS

        if isinstance(w, TpColWeight):
            partial = local_matmul(x, w.w, compute_dtype=compute_dtype,
                                   use_pallas=use_pallas,
                                   interpret=pallas_interpret)
            return (manual_psum(partial, TP_AXIS) if manual_tp > 1
                    else partial)
        if isinstance(w, TpRowWeight):
            w = w.w
        return local_matmul(x, w, compute_dtype=compute_dtype,
                            use_pallas=use_pallas, interpret=pallas_interpret)

    if isinstance(w, TpColWeight):
        assert tp_mesh is not None, "TpColWeight requires the mesh in cfg"
        return tp_col_matmul(x, w, tp_mesh, compute_dtype=compute_dtype,
                             reduce=tp_reduce, use_pallas=use_pallas,
                             interpret=pallas_interpret)
    if isinstance(w, TpRowWeight):
        assert tp_mesh is not None, "TpRowWeight requires the mesh in cfg"
        return tp_row_matmul(x, w, tp_mesh, compute_dtype=compute_dtype,
                             use_pallas=use_pallas,
                             interpret=pallas_interpret)

    return local_matmul(x, w, compute_dtype=compute_dtype,
                        use_pallas=use_pallas, interpret=pallas_interpret)


def reads_experts_in_place(w, rows: int, *, use_pallas: bool = False,
                           tp_mesh=None, **_) -> bool:
    """Whether a stacked (E, d, n) weight leaf is one the expert kernel
    reads in place (ops/pallas_q40.q40_expert_matmul), which rests on what
    a call can see in its operands alone: kernels on, no tp mesh, a
    plain-QuantizedTensor single-shard Q40 stack (which includes
    manual-region pp layers at tp == 1, where the local stack is the whole
    weight) and at most pallas_q40.MAX_T token rows. The mesh paths' Tp/Ep
    wrappers, dense stacks, the XLA dequant path and longer segments are
    sliced an expert at a time (models/transformer._take_expert)."""
    from .pallas_q40 import MAX_T

    return bool(use_pallas and tp_mesh is None
                and isinstance(w, QuantizedTensor) and w.packed.ndim == 3
                and rows <= MAX_T)


def fused_expert_matmul(
    x: jnp.ndarray,
    w,                      # stacked (E, d, n) weight leaf
    e,                      # i32 expert of each row tile of x, or of all x
    *,
    used=None,              # i32 leading row tiles that hold a live row
    token_rows: int | None = None,
    src=None,               # i32 row of x each tile row is gathered from
    activation_q80: bool = False,
    compute_dtype=jnp.float32,
    use_pallas: bool = False,
    tp_mesh=None,
    tp_reduce: str = "exact",
    pallas_interpret: bool = False,
    manual_tp: int = 0,
    manual_ep: int = 0,  # ignored — see matmul()
    manual_sp: int = 0,  # ignored — see matmul()
):
    """Expert-indexed matmul against a stacked (E, d, n) Q40 weight without
    materializing any expert's slice (ops/pallas_q40.q40_expert_matmul,
    which says what `e`, `used`, `token_rows` and `src` are): the grouped
    path of models/transformer._moe_ffn comes here once a projection, with
    the step's token rows and the index that lays them out in row tiles
    (gate, up) or with pair rows already laid out (down); a scalar `e` is
    one expert for every row of x.

    Returns None when reads_experts_in_place says the stack is not the
    kernel's (`token_rows`, else x's own rows, against MAX_T)."""
    del tp_reduce, manual_tp
    rows = math.prod(x.shape[:-1]) if token_rows is None else token_rows
    in_place = reads_experts_in_place(w, rows, use_pallas=use_pallas,
                                      tp_mesh=tp_mesh)
    if not in_place:
        return None
    from .pallas_q40 import q40_expert_matmul

    if activation_q80:  # same round-trip matmul() applies
        with jax.named_scope("act_q80"):
            q, scales = quantize_q80_jax(x)
            x = dequantize_q80_jax(q, scales, dtype=compute_dtype)
    return q40_expert_matmul(x.astype(compute_dtype), w, e, used,
                             out_dtype=compute_dtype,
                             interpret=pallas_interpret,
                             token_rows=token_rows, src=src)
