"""Wire/collective observability.

The reference surfaces per-token transfer time and sent/received kB from its
socket byte counters (ref: src/socket.cpp:266-271, printed as the T/S/R
columns in benchmark mode — src/apps/dllama/dllama.cpp:74-91). Under XLA the
collectives live inside one compiled program, so the equivalent here is:

  * `estimate_decode_wire` — exact modeled bytes per decoded token per
    device, derived from the mesh and the sharding design (which collectives
    GSPMD/shard_map emit is determined by the partition specs, so the byte
    count is computable, not guessed);
  * `measure_allreduce_ms` — a timed collective microbench on the real mesh,
    giving the per-token transfer-time estimate the reference measures
    directly.

Ring-algorithm cost model: an all-reduce moves 2*(n-1)/n * payload per
device, an all-gather / all-to-all (n-1)/n * payload (SURVEY.md §3.4 maps
the reference's per-layer broadcast/gather pairs onto these).

The MEASURED side (dlwire) lives next to the model: the multihost
control plane's socket ledger (parallel/multihost.py → stats.WireStats)
counts real bytes per (peer, kind, direction), :func:`per_step_op_ms`
attributes real device collective ms per executed step from a profiler
capture, and :func:`reconcile_wire` closes the loop — measured against
modeled, drift flagged at ≥25%.
"""

from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from ..models.spec import ModelSpec


class WireEstimate(NamedTuple):
    sent_kb_per_token: float          # per device, per decoded token
    breakdown: dict                   # component -> kB


def _ar(n: int, payload: float) -> float:
    """Ring all-reduce bytes sent per device."""
    return 2 * (n - 1) / n * payload


def _ag(n: int, payload: float) -> float:
    """Ring all-gather (or all-to-all) bytes sent per device; `payload` is
    the full gathered size."""
    return (n - 1) / n * payload


def estimate_decode_wire(
    spec: ModelSpec,
    mesh,
    *,
    q80: bool = False,
    act_bytes: int = 4,
    batch: int = 1,
    shard_vocab: bool = False,
    vocab_topk: int = 32,
) -> WireEstimate:
    """Modeled bytes each device sends per decoded token.

    tp: 2 partial-sum all-reduces per dense layer (wo, w2 — the reference's
    2 broadcast + 2 gather pairs collapse to these, SURVEY.md §3.4), one per
    active expert + one for wo on MoE layers, plus the vocab-sharded logits
    all-gather. q80 mode swaps the f32 all-reduce for the two-shot quantized
    exchange (int8 + f16 block scales = 1.0625 B/value).
    sp: the decode-attention stat merge (acc + m + l per layer).
    dp: no inter-device traffic at inference.
    shard_vocab (ops/sharded_vocab.py): the embedding gather costs one
    extra dim-sized all-reduce per forward, and the full-logits gather is
    REPLACED by the candidate-summary gather (S·k probs+ids + guards per
    row — hundreds of bytes where the logits were vocab·4).
    """
    if mesh is None:
        return WireEstimate(0.0, {})
    tp = mesh.shape.get("tp", 1)
    sp = mesh.shape.get("sp", 1)
    dp = mesh.shape.get("dp", 1)
    ep = mesh.shape.get("ep", 1)
    b_local = max(1, batch // dp)
    bd: dict[str, float] = {}

    val_bytes = 1.0625 if q80 else act_bytes  # int8 + f16/32-block scale
    if tp > 1:
        # with ep the MoE expert-sum reduce moves out of the tp column (see
        # ep_moe_reduce below); only the attention wo reduce stays per-layer
        if spec.is_moe:
            reduces_per_layer = 1 if ep > 1 else 1 + spec.n_active_experts
        else:
            reduces_per_layer = 2
        per_reduce = spec.dim * b_local * val_bytes
        layer_fn = _ar  # both the f32 all-reduce and the 2-shot q80
        # exchange move 2*(n-1)/n * payload per device
        bd["tp_partial_sums"] = (spec.n_layers * reduces_per_layer
                                 * layer_fn(tp, per_reduce))
        if shard_vocab:
            bd["vocab_embed_psum"] = _ar(tp, spec.dim * b_local
                                         * act_bytes)
            k = min(vocab_topk, max(spec.vocab_size // tp, 1))
            bd["vocab_sample_gather"] = _ag(
                tp, b_local * (tp * k * 8 + tp * 4 + 4))
        else:
            bd["tp_logits_gather"] = _ag(tp,
                                         spec.vocab_size * b_local * 4)
    if ep > 1:
        # one MoE output reduce per layer (parallel/ep_moe.py): exact mode is
        # a single all-reduce over the ep*tp group; q80 mode is a quantized
        # 2-shot over tp followed by an exact f32 psum over ep
        per = spec.dim * b_local
        if q80 and tp > 1:
            moe = _ar(tp, per * val_bytes) + _ar(ep, per * act_bytes)
        else:
            moe = _ar(ep * tp, per * act_bytes)
        bd["ep_moe_reduce"] = spec.n_layers * moe
    if sp > 1:
        stat = spec.n_heads * spec.head_size + 2 * spec.n_heads  # acc + m + l
        bd["sp_attn_merge"] = spec.n_layers * _ar(sp, stat * b_local * 4)
    pp = mesh.shape.get("pp", 1)
    if pp > 1:
        # one masked-psum live-stage broadcast of the activations per stage
        # (parallel/pp.py)
        bd["pp_stage_handoff"] = pp * _ar(pp, spec.dim * b_local * act_bytes)

    total = sum(bd.values())
    return WireEstimate(total / 1024.0,
                        {k: v / 1024.0 for k, v in bd.items()})


def estimate_serve_wire(
    spec: ModelSpec,
    mesh,
    *,
    batch: int = 1,
    occupancy: float | None = None,
    q80: bool = False,
    act_bytes: int = 4,
) -> WireEstimate:
    """Per-EMITTED-token wire under the continuous-batching scheduler
    (runtime/scheduler.py): a slot-scheduler decode step moves the full
    batch-B collective payload no matter how many slots are live (gated
    rows ride through every collective with the rest of the batch), so
    the per-emitted-token cost is the batch-B step estimate divided by
    the mean slot occupancy. occupancy == batch reproduces the static
    batched estimate; occupancy -> 1 degrades to B× the per-token wire —
    the quantitative reason queue pressure, not slot count, sets serving
    efficiency."""
    step = estimate_decode_wire(spec, mesh, q80=q80, act_bytes=act_bytes,
                                batch=batch)
    # `is not None`, not truthiness: a measured occupancy of 0.0 (idle
    # window) must clamp to the degenerate worst case below, not silently
    # take the full-batch best case
    occ = float(occupancy) if occupancy is not None else float(batch)
    occ = max(min(occ, float(batch)), 1e-6)
    return WireEstimate(step.sent_kb_per_token / occ,
                        {k: v / occ for k, v in step.breakdown.items()})


# measured-vs-modeled movement worth flagging (tools/dlprof.py mirrors
# it — it must run with no repo on the path; tests pin the mirror)
WIRE_DRIFT_FRAC = 0.25


def reconcile_wire(measured: float, modeled: float, *,
                   threshold: float = WIRE_DRIFT_FRAC,
                   unit: str = "bytes") -> dict:
    """Measured wire traffic (the dlwire ledger) vs the model — the
    closed loop the reference's printed T/S columns never had. Units are
    the caller's (control-plane bytes against frame-size arithmetic;
    per-token kB against :func:`estimate_decode_wire`) — only the RATIO
    matters here. ``drift`` trips at ``threshold`` relative movement:
    past it either the model is wrong (a collective the estimate does
    not know about) or the measurement is (bytes leaking outside the
    ledger) — both are findings. Modeled == 0 cannot reconcile: the
    result says so instead of dividing."""
    measured = float(measured)
    modeled = float(modeled)
    out = {"measured": round(measured, 4), "modeled": round(modeled, 4),
           "unit": unit, "threshold": threshold,
           "drift_frac": None, "drift": False, "note": None}
    if modeled > 0:
        frac = abs(measured - modeled) / modeled
        out["drift_frac"] = round(frac, 4)
        out["drift"] = frac >= threshold
        if out["drift"]:
            out["note"] = (f"measured {unit} moved {frac:.0%} from the "
                           f"model (>= {threshold:.0%}): the byte model "
                           "or the ledger is wrong — investigate before "
                           "trusting either")
    elif measured > 0:
        out["note"] = ("no model to reconcile against (modeled == 0) — "
                       "measured traffic stands alone")
    return out


COLLECTIVE_MARKERS = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute")


def per_step_op_ms(trace_dir: str, markers: tuple = COLLECTIVE_MARKERS,
                   module_hint: str | None = None) -> list:
    """Parse a jax.profiler trace into PER-STEP summed device time (ms) of
    ops whose name contains any marker — the measured analogue of the
    reference's genuinely per-token T column (ref:
    src/apps/dllama/dllama.cpp:74-79), where `measure_allreduce_ms` is only
    a repeated microbench constant.

    A "step" is one executed XLA module (the engine's jitted forward): the
    device plane's "XLA Modules" line has one event per execution, and each
    op event on the "XLA Ops"/"Async XLA Ops" lines is bucketed into the
    module span containing it (the program's one xplane walker,
    runtime/profiler.walk_trace). Returns one float per module execution in
    timeline order; [] when the trace has no device plane (CPU runs) — the
    caller falls back to the microbench."""
    from .profiler import per_execution_ms, walk_trace

    trace = walk_trace(trace_dir, op_lines=("XLA Ops", "Async XLA Ops"))
    return per_execution_ms(trace.get("devices", ()), markers, module_hint)


def measure_allreduce_ms(mesh, payload_elems: int, iters: int = 16,
                         axes: tuple = ("tp",)) -> float:
    """Time one f32 all-reduce of `payload_elems` over the given mesh axes
    (jointly — e.g. ("ep", "tp") for the MoE group reduce) — the measured
    analogue of the reference's per-token T column. Returns ms per
    all-reduce (amortized over iters; the timed region ends with
    block_until_ready on a local shard)."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if n <= 1:
        return 0.0

    @jax.jit
    def run(x):
        def body(v):
            for _ in range(iters):
                v = jax.lax.psum(v, axes) * (1.0 / n)
            return v
        return shard_map(body, mesh=mesh, in_specs=P(axes),
                         out_specs=P(axes), check_vma=False)(x)

    # explicit placement + local-shard fetch: on a multi-process mesh the
    # sharded output spans non-addressable devices, so sync on a LOCAL shard
    # (its completion implies the collective chain ran); fetching the
    # full array would raise
    x = jax.device_put(np.ones((n, payload_elems), np.float32),
                       NamedSharding(mesh, P(axes)))

    def sync(out):
        out.addressable_shards[0].data.block_until_ready()

    sync(run(x))  # compile + warm
    t0 = time.perf_counter()
    sync(run(x))
    dt = time.perf_counter() - t0
    return dt / iters * 1e3


def measure_ppermute_ms(mesh, payload_elems: int, iters: int = 16,
                        axis: str = "pp") -> float:
    """Time one f32 next-neighbor ppermute of `payload_elems` over `axis` —
    the GPipe microbatch activation hop (parallel/pp.py pp_layers_gpipe's
    shift()). Same sync discipline as measure_allreduce_ms. Returns ms per
    hop, 0.0 when the axis is absent/size 1."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape.get(axis, 1)
    if n <= 1:
        return 0.0
    perm = [(i, i + 1) for i in range(n - 1)]

    @jax.jit
    def run(x):
        def body(v):
            for _ in range(iters):
                v = jax.lax.ppermute(v, axis, perm)
            return v
        return shard_map(body, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis), check_vma=False)(x)

    x = jax.device_put(np.ones((n, payload_elems), np.float32),
                       NamedSharding(mesh, P(axis)))

    def sync(out):
        out.addressable_shards[0].data.block_until_ready()

    sync(run(x))  # compile + warm
    t0 = time.perf_counter()
    sync(run(x))
    dt = time.perf_counter() - t0
    return dt / iters * 1e3
