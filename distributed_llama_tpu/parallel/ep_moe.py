"""Expert parallelism: MoE experts PLACED across devices (shard_map path).

Net-new vs the reference, which only tensor-slices every expert — each node
holds a shard of all E experts and computes every active expert
(ref: src/grok1-tasks.cpp:56-143; SURVEY.md §2.5 marks placement-EP absent).
Here the expert axis itself shards over the mesh's `ep` axis: each device
stores E/ep experts (the memory-scaling axis that lets Mixtral/Grok-class
models fit small-HBM chips) and computes only its local experts, masked by
the replicated routing weights; expert contributions and tp partial sums
reduce in a single psum over (ep, tp).

The dataflow inside one shard_map body (all shapes local):

    for each local expert le (E/ep of them, static unroll):
        w_e  = routing_weights[..., ep_index*E/ep + le]   # 0 if not in top-k
        hb   = act(x @ gate_le^T) * (x @ up_le^T)         # hidden/tp local
        acc += w_e * (hb @ down_le^T)                     # dim partial sum
    out = psum(acc, (ep, tp))

Compute cost per device is E/ep dense experts regardless of top-k — at
ep >= E/k this matches the active-only cost of the unsharded decode path
while cutting per-device expert memory by ep. ep composes with tp: within
each expert, up/gate stay row-split and down col-split exactly like the
dense FFN (parallel/tp_q80.py layouts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.matmul import local_matmul
from ..quants.jax_codec import QuantizedTensor
from .collectives import q80_psum_2shot
from .mesh import EP_AXIS, TP_AXIS
from .tp_q80 import TpColWeight, _batch_axes, repack_col_tp
from .wrappers import WeightWrapper, weight_marker


@weight_marker
class EpRowWeight(WeightWrapper):
    """A stacked (E, d, n) MoE row weight (moe_up / moe_gate): experts on
    ep, output rows on tp. No repacking — both axes shard contiguously."""

    w: QuantizedTensor | jax.Array


@weight_marker
class EpColWeight(WeightWrapper):
    """A stacked MoE col weight (moe_down) in TpColWeight layout
    (tp, E, d, n/tp): tp stack on tp, experts on ep. The tp restacking keeps
    Q40 blocks contiguous per shard (see tp_q80.repack_col_tp)."""

    w: QuantizedTensor | jax.Array


def repack_moe_ep(lw: dict, tp: int) -> dict:
    """Mark one layer's MoE weights for the ep shard_map path: up/gate as-is
    (EpRowWeight), down restacked block-aligned for tp (EpColWeight). A
    moe_down already in TpColWeight stack form (the streamed loader's q80
    mode pre-repacks col weights) is re-marked without touching bytes."""
    down = lw["moe_down"]
    if isinstance(down, TpColWeight):
        down = EpColWeight(down.w)
    elif not isinstance(down, EpColWeight):
        down = EpColWeight(repack_col_tp(down, tp).w)
    out = dict(lw)
    out["moe_up"] = EpRowWeight(lw["moe_up"])
    out["moe_gate"] = EpRowWeight(lw["moe_gate"])
    out["moe_down"] = down
    return out


def ep_row_pspec(ndim: int) -> P:
    """(E, d, m/nb/n): experts -> ep, output rows -> tp. The single source
    of the EpRowWeight layout (the streamed loader places with it too)."""
    return P(EP_AXIS, TP_AXIS, *([None] * (ndim - 2)))


def ep_col_pspec(ndim: int) -> P:
    """(tp, E, d, ...): tp stack -> tp, experts -> ep (EpColWeight layout)."""
    return P(TP_AXIS, EP_AXIS, *([None] * (ndim - 2)))


def _row_pspec(w: EpRowWeight) -> EpRowWeight:
    if isinstance(w.w, QuantizedTensor):
        return EpRowWeight(QuantizedTensor(ep_row_pspec(w.w.packed.ndim),
                                           ep_row_pspec(w.w.scales.ndim)))
    return EpRowWeight(ep_row_pspec(w.w.ndim))


def _col_pspec(w: EpColWeight) -> EpColWeight:
    if isinstance(w.w, QuantizedTensor):
        return EpColWeight(QuantizedTensor(ep_col_pspec(w.w.packed.ndim),
                                           ep_col_pspec(w.w.scales.ndim)))
    return EpColWeight(ep_col_pspec(w.w.ndim))


def ep_pspec(w):
    """PartitionSpec pytree for an Ep wrapper (sharding._leaf_spec hook)."""
    return _row_pspec(w) if isinstance(w, EpRowWeight) else _col_pspec(w)


def _take2(w, le):
    """Static-index one local expert out of a local (E_l, d, ...) leaf."""
    if isinstance(w, QuantizedTensor):
        return QuantizedTensor(w.packed[le], w.scales[le])
    return w[le]


def ep_moe_ffn(
    xb: jnp.ndarray,         # (B, T, dim) — post-norm activations
    e_weights: jnp.ndarray,  # (B, T, E) normalized routing weights, 0 if inactive
    lw: dict,                # layer weights with Ep-wrapped moe_{up,gate,down}
    mesh,
    *,
    act_fn,
    compute_dtype,
    use_pallas: bool = False,
    interpret: bool = False,
    reduce: str = "exact",
) -> jnp.ndarray:
    """Expert-parallel MoE FFN; returns (B, T, dim) replicated over (ep, tp).

    reduce="q80" compresses the tp partial-sum hop (the wire-heavy one —
    dim bytes per expert stack) via the quantized two-shot exchange; the ep
    expert-sum hop stays exact.
    """
    from jax import shard_map

    ep = mesh.shape.get(EP_AXIS, 1)
    tp = mesh.shape.get(TP_AXIS, 1)
    e_total = e_weights.shape[-1]
    assert e_total % ep == 0, (e_total, ep)
    dp_ax, sp_ax = _batch_axes(mesh, xb)
    x_spec = P(dp_ax, sp_ax, None)

    def body(x_l, ew_l, up_l, gate_l, down_l):
        return _ep_body(x_l, ew_l, up_l.w, gate_l.w, down_l.w,
                        ep=ep, tp=tp, act_fn=act_fn,
                        compute_dtype=compute_dtype, use_pallas=use_pallas,
                        interpret=interpret, reduce=reduce)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, x_spec, _row_pspec(lw["moe_up"]),
                  _row_pspec(lw["moe_gate"]), _col_pspec(lw["moe_down"])),
        out_specs=x_spec, check_vma=False)
    return fn(xb, e_weights, lw["moe_up"], lw["moe_gate"], lw["moe_down"])


def _ep_body(x_l, ew_l, up_w, gate_w, down_w, *, ep, tp, act_fn,
             compute_dtype, use_pallas, interpret, reduce):
    """The per-shard expert-parallel MoE computation (local shapes): each
    device runs its E/ep local experts masked by the replicated routing
    weights and the partial sums reduce over (ep, tp). Called from
    ep_moe_ffn's shard_map body AND directly inside the fully-manual pp
    region (parallel/pp.py — shard_map cannot nest, so ep under pp must be
    manual exactly like tp is)."""
    e_total = ew_l.shape[-1]
    e_local = e_total // ep
    ep_idx = lax.axis_index(EP_AXIS) if ep > 1 else 0
    acc = jnp.zeros(x_l.shape[:-1] + (down_w.packed.shape[-2]
                    if isinstance(down_w, QuantizedTensor)
                    else down_w.shape[-2],), compute_dtype)
    for le in range(e_local):
        ge = ep_idx * e_local + le
        w_e = lax.dynamic_index_in_dim(ew_l, ge, axis=-1, keepdims=True)
        gate = local_matmul(x_l, _take2(gate_w, le),
                            compute_dtype=compute_dtype,
                            use_pallas=use_pallas, interpret=interpret)
        up = local_matmul(x_l, _take2(up_w, le),
                          compute_dtype=compute_dtype,
                          use_pallas=use_pallas, interpret=interpret)
        hb = act_fn(gate) * up
        down_le = _take2(down_w, 0)       # drop the tp stack axis
        down_le = _take2(down_le, le)     # then the local expert axis
        out = local_matmul(hb, down_le, compute_dtype=compute_dtype,
                           use_pallas=use_pallas, interpret=interpret)
        acc = acc + w_e.astype(out.dtype) * out
    from .tp_q80 import manual_psum

    # manual_psum: f32 transit for bf16 payloads on the CPU backend (the
    # same XLA CPU manual-region miscompile the pp stage broadcast hits)
    if reduce == "q80" and tp > 1:
        acc = q80_psum_2shot(acc, TP_AXIS, tp)
        return manual_psum(acc, EP_AXIS) if ep > 1 else acc
    axes = tuple(ax for ax, n in ((EP_AXIS, ep), (TP_AXIS, tp)) if n > 1)
    return manual_psum(acc, axes) if axes else acc
