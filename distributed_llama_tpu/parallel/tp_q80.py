"""Explicit tensor-parallel execution paths (shard_map layer).

Two things GSPMD cannot express live here:

1. **Q80-compressed partial-sum exchange.** The reference quantizes every
   inter-node activation transfer to Q80 int8 blocks (ref:
   src/tasks.cpp:124-163), invoked around each layer's wo/w2 partial-sum
   exchange (ref: src/llama2-tasks.cpp:251-274) — its signature wire
   optimization (README measures 2048 kB -> 544 kB per token). Under pure
   GSPMD the col-split contraction's all-reduce is compiler-inserted and
   always exact/full-precision; `tp_col_matmul(reduce="q80")` is the
   execution path where that reduction moves int8 blocks instead, selected
   by `--buffer-float-type q80`.

2. **Pallas kernels on multi-device meshes.** GSPMD cannot auto-partition
   a `pallas_call` over sharded operands, so the fused Q40 kernel
   (ops/pallas_q40.py), flash decode attention (ops/pallas_attention.py)
   and the in-place cache write (ops/pallas_kv_write.py) would otherwise
   force the slower XLA paths whenever the mesh has more than one device.
   `tp_row_matmul` / `tp_col_matmul(use_pallas=True)` /
   `tp_flash_attention` / `tp_kv_cache_write` run the kernels per-shard
   inside `shard_map`: row-split weights need no communication at all
   (each shard produces its output rows), col-split partial sums reduce
   with an exact psum (default) or the quantized exchange, and attention
   and the cache write shard over (dp, kv-heads).

Layout: a col-split weight (wo, w2, moe_down — ref ColMatmulSlice,
src/transformer.cpp:48-76) is repacked host/device-side into a stacked
(tp, ..., d, n/tp) form where slice k quantization-block-aligns with logical
input columns [k*n/tp, (k+1)*n/tp). The stack is sharded P('tp', ...) so
each device holds exactly its slice; inside `shard_map` the local partial
matmul runs on block-aligned Q40 data (no GSPMD re-tiling of packed bytes),
and the partial sums reduce via the two-shot quantized all-reduce
(parallel/collectives.py:q80_psum_2shot).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.matmul import local_matmul
from ..quants.jax_codec import QuantizedTensor
from .collectives import q80_psum_2shot
from .mesh import DP_AXIS, SP_AXIS, TP_AXIS
from .wrappers import WeightWrapper, weight_marker


@weight_marker
class TpColWeight(WeightWrapper):
    """A col-split weight repacked as a (tp, ..., d, n/tp) stack.

    `w` is a dense array or a QuantizedTensor whose packed/scales carry the
    same leading tp axis. Slice k holds the weight columns contracting with
    input columns [k*n/tp, (k+1)*n/tp) — the reference's ColMatmulSlice shard
    for node k (ref: src/transformer.cpp:60-76)."""

    w: QuantizedTensor | jax.Array


@weight_marker
class TpRowWeight(WeightWrapper):
    """A row-split (output-dim) matmul weight, marked for shard_map kernel
    execution. No repacking: the d axis shards contiguously, so each local
    block is itself a valid weight for its output rows (the reference's
    RowMatmulSlice, ref: src/transformer.cpp:14-46). With tp == 1 (dp-only
    meshes) the weight is replicated and the marker only routes the matmul
    through shard_map so the Pallas kernel sees local (unsharded) operands."""

    w: QuantizedTensor | jax.Array


def tp_row_pspec(w: TpRowWeight) -> TpRowWeight:
    """PartitionSpec pytree: the output-row axis (-2) on tp, rest replicated.
    Packed (lead..., d, m), scales (lead..., d, nb) and dense (lead..., d, n)
    all shard the same axis."""
    def spec(ndim):
        axes: list = [None] * ndim
        axes[ndim - 2] = TP_AXIS
        return P(*axes)

    if isinstance(w.w, QuantizedTensor):
        return TpRowWeight(QuantizedTensor(spec(w.w.packed.ndim),
                                           spec(w.w.scales.ndim)))
    return TpRowWeight(spec(w.w.ndim))


def _batch_axes(mesh, x):
    """(dp_ax, sp_ax) usable for this x's leading dims on this mesh."""
    dp = mesh.shape.get(DP_AXIS, 1)
    sp = mesh.shape.get(SP_AXIS, 1)
    b = x.shape[0]
    t = x.shape[1] if x.ndim == 3 else 1
    dp_ax = DP_AXIS if dp > 1 and b % dp == 0 else None
    sp_ax = (SP_AXIS if x.ndim == 3 and sp > 1 and t > 1 and t % sp == 0
             else None)
    return dp_ax, sp_ax


def tp_row_matmul(
    x: jnp.ndarray,
    w: TpRowWeight,
    mesh,
    *,
    compute_dtype=jnp.float32,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    """y[..., d] = x @ W^T with the OUTPUT dim tp-split — communication-free
    (each shard computes its own output rows; the result stays tp-sharded on
    the last axis, which is exactly how downstream consumers want it: heads
    for attention, hidden columns for w2's col-split contraction).

    x is (B, n) or (B, T, n), replicated over tp (the reference likewise
    gives every node the full normed activation, ref: llama2-tasks.cpp:249).
    """
    from jax import shard_map

    tp = mesh.shape.get(TP_AXIS, 1)
    tp_ax = TP_AXIS if tp > 1 else None
    dp_ax, sp_ax = _batch_axes(mesh, x)
    if x.ndim == 2:
        x_spec, out_spec = P(dp_ax, None), P(dp_ax, tp_ax)
    else:
        x_spec, out_spec = P(dp_ax, sp_ax, None), P(dp_ax, sp_ax, tp_ax)

    def body(x_l, w_l):
        return local_matmul(x_l, w_l.w, compute_dtype=compute_dtype,
                            use_pallas=use_pallas, interpret=interpret)

    fn = shard_map(body, mesh=mesh, in_specs=(x_spec, tp_row_pspec(w)),
                   out_specs=out_spec, check_vma=False)
    return fn(x, w)


def _batch_head_axes(mesh, batch: int):
    """(dp axis or None, tp axis or None) for shard-local attention-side
    kernels: batch shards on dp when it divides, (kv) heads on tp."""
    dp = mesh.shape.get(DP_AXIS, 1)
    tp = mesh.shape.get(TP_AXIS, 1)
    return (DP_AXIS if dp > 1 and batch % dp == 0 else None,
            TP_AXIS if tp > 1 else None)


def tp_flash_attention(
    q: jnp.ndarray,        # (B, T, H, hs)
    k_cache: jnp.ndarray,  # (B, KVH, S, hs)
    v_cache: jnp.ndarray,  # (B, KVH, S, hs)
    q_pos: jnp.ndarray,    # (B, T)
    mesh,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """flash_attention (decode and chunked prefill) over a (dp, tp) mesh:
    batch shards on dp, heads/kv-heads on tp (the reference's KvCacheSlice
    head split, ref: src/transformer.cpp:161-171). Pure shard-local —
    attention never mixes heads, so no collective is needed."""
    from jax import shard_map

    from ..ops.pallas_attention import flash_attention

    dp_ax, tp_ax = _batch_head_axes(mesh, q.shape[0])

    def body(q_l, k_l, v_l, pos_l):
        return flash_attention(q_l, k_l, v_l, pos_l, interpret=interpret)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_ax, None, tp_ax, None), P(dp_ax, tp_ax, None, None),
                  P(dp_ax, tp_ax, None, None), P(dp_ax, None)),
        out_specs=P(dp_ax, None, tp_ax, None), check_vma=False)
    return fn(q, k_cache, v_cache, q_pos)


def tp_kv_cache_write(
    k_cache: jnp.ndarray,  # (B, KVH, S, hs)
    v_cache: jnp.ndarray,  # (B, KVH, S, hs)
    k_new: jnp.ndarray,    # (B, T, KVH, hs), in the cache dtype
    v_new: jnp.ndarray,    # (B, T, KVH, hs)
    pos: jnp.ndarray,      # (B,)
    mesh,
    *,
    interpret: bool = False,
):
    """kv_cache_write over a (dp, tp) mesh, the caches under the specs
    tp_flash_attention reads them with: every shard writes its own batch
    rows' kv heads in place. Shard-local, no collective."""
    from jax import shard_map

    from ..ops.pallas_kv_write import kv_cache_write

    dp_ax, tp_ax = _batch_head_axes(mesh, k_cache.shape[0])
    cache, new = P(dp_ax, tp_ax, None, None), P(dp_ax, None, tp_ax, None)

    def body(kc, vc, kn, vn, pos_l):
        return kv_cache_write(kc, vc, kn, vn, pos_l, interpret=interpret)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(cache, cache, new, new, P(dp_ax)),
                   out_specs=(cache, cache), check_vma=False)
    return fn(k_cache, v_cache, k_new, v_new, pos)


def repack_col_tp(w, tp: int) -> TpColWeight:
    """Split a col-split weight into a block-aligned per-shard stack.

    Dense (..., d, n) -> (tp, ..., d, n/tp). Q40 packed (..., d, 16*nb) with
    lane order m = j*nb + b (quants/jax_codec.py) -> per-shard lane order
    m_local = j*(nb/tp) + b_local, i.e. each shard is itself a valid flattened
    QuantizedTensor for its logical column range — a pure relayout of the
    existing bytes (blocks never straddle shards because n/tp % 32 == 0,
    checked in sharding.check_tp_constraints)."""
    if isinstance(w, QuantizedTensor):
        nb = w.scales.shape[-1]
        assert nb % tp == 0, (nb, tp)
        lead = w.packed.shape[:-1]
        pk = w.packed.reshape(*lead, 16, tp, nb // tp)
        pk = jnp.moveaxis(pk, -2, 0).reshape(tp, *lead, 16 * (nb // tp))
        sc = jnp.moveaxis(w.scales.reshape(*lead, tp, nb // tp), -2, 0)
        return TpColWeight(QuantizedTensor(pk, sc))
    n = w.shape[-1]
    assert n % tp == 0, (n, tp)
    return TpColWeight(jnp.moveaxis(w.reshape(*w.shape[:-1], tp, n // tp), -2, 0))


def tp_col_pspec(w: TpColWeight):
    """PartitionSpec pytree for a TpColWeight: leading stack axis on tp."""
    def spec(ndim):
        return P(TP_AXIS, *([None] * (ndim - 1)))

    if isinstance(w.w, QuantizedTensor):
        return TpColWeight(QuantizedTensor(spec(w.w.packed.ndim), spec(w.w.scales.ndim)))
    return TpColWeight(spec(w.w.ndim))


def take_expert_col(w: TpColWeight, e) -> TpColWeight:
    """Select expert e from a stacked MoE col weight: (tp, E, d, n/tp) on the
    GSPMD path, or the shard-local (E, d, n/tp) form inside a fully-manual
    region (parallel/pp.py strips the tp stack axis) — discriminated by
    rank, since expert col stacks are the only 3D/4D TpColWeight leaves."""
    from jax import lax

    if isinstance(w.w, QuantizedTensor):
        ax = 1 if w.w.packed.ndim == 4 else 0
        return TpColWeight(QuantizedTensor(
            lax.dynamic_index_in_dim(w.w.packed, e, axis=ax, keepdims=False),
            lax.dynamic_index_in_dim(w.w.scales, e, axis=ax, keepdims=False),
        ))
    ax = 1 if w.w.ndim == 4 else 0
    return TpColWeight(lax.dynamic_index_in_dim(w.w, e, axis=ax, keepdims=False))


def manual_psum(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """lax.psum for code already inside a manual region (parallel/pp.py).
    On the CPU backend only, the payload transits in f32: XLA's CPU compiler
    miscompiles a bf16 all-reduce inside a manual region ("Invalid binary
    instruction opcode copy"); TPU keeps the native width."""
    if jax.default_backend() == "cpu" and x.dtype == jnp.bfloat16:
        return jax.lax.psum(x.astype(jnp.float32), axis).astype(x.dtype)
    return jax.lax.psum(x, axis)


def tp_col_matmul(
    x: jnp.ndarray,
    w: TpColWeight,
    mesh,
    *,
    compute_dtype=jnp.float32,
    reduce: str = "q80",
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """y[b, t, d] = sum_n x[b, t, n] * W[d, n] with the contraction tp-split
    and the partial sums reduced exactly (`reduce="exact"`, jax.lax.psum) or
    Q80-compressed (`reduce="q80"`, the reference's wire optimization).

    x is a global (B, T, n) array (GSPMD-resident); the shard_map forces the
    last dim onto tp (matching how row-split producers already shard it), the
    local (B_l, T_l, n/tp) x slice contracts with this shard's weight slice
    (Pallas fused Q40 kernel when use_pallas), and partials all-reduce.
    Output is (B, T, d), replicated over tp like GSPMD's own all-reduce."""
    from jax import shard_map

    tp = mesh.shape[TP_AXIS]
    dp_ax, sp_ax = _batch_axes(mesh, x)
    x_spec = P(dp_ax, sp_ax, TP_AXIS)
    out_spec = P(dp_ax, sp_ax, None)

    def body(x_l, w_l):
        wk = w_l.w
        if isinstance(wk, QuantizedTensor):
            wk = QuantizedTensor(wk.packed[0], wk.scales[0])
        else:
            wk = wk[0]
        partial = local_matmul(x_l, wk, compute_dtype=compute_dtype,
                                use_pallas=use_pallas, interpret=interpret)
        if reduce == "exact":
            return jax.lax.psum(partial, TP_AXIS) if tp > 1 else partial
        return q80_psum_2shot(partial, TP_AXIS, tp)

    fn = shard_map(body, mesh=mesh, in_specs=(x_spec, tp_col_pspec(w)),
                   out_specs=out_spec, check_vma=False)
    return fn(x, w)
