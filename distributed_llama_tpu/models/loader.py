"""Streamed sharded weight loading — the 70B path.

The reference root streams the mmap'd file tensor-by-tensor, splitting each
matrix and pushing every worker its shard over the socket while only the
current tensor is resident (ref: src/transformer.cpp:562-621, 623-683). The
TPU equivalent: iterate the file in plan order, convert each tensor to its
device layout on the host, `jax.device_put` it with its NamedSharding (each
device receives only its shard), and free the host buffer before the next
tensor. Peak host memory is one fusion group (~3 tensors, or one layer's
expert stack for MoE), never the whole model — `load_params_streamed`
returns the measured peak so callers/tests can hold it to that bound.

The result pytree is final: QKV/w1|w3 pre-fused when tp == 1, col weights
pre-repacked to TpColWeight stacks when q80 collectives are on, every leaf
already placed/sharded. Engine's own transforms detect and skip
already-transformed params, so this feeds Engine(...) directly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..io.model_file import HostTensor, iter_model_tensors, to_q40_host
from ..quants.jax_codec import QuantizedTensor
from ..quants.types import FloatType
from ..parallel.sharding import leaf_pspec
from ..parallel.mesh import EP_AXIS, PP_AXIS, TP_AXIS
from .params import kv_replication, split_wkvb
from .spec import ModelSpec
from .tensors import LEAF_SPLIT, Leaf, group_members, model_tensors


class LoadStats(NamedTuple):
    peak_host_bytes: int   # max bytes of file tensors resident at once
    total_bytes: int       # total tensor bytes streamed


def _host_bytes(t: HostTensor) -> int:
    n = 0
    for a in (t.data, t.scales, t.packed):
        if a is not None:
            n += a.nbytes
    return n


def _replicate_kv_host(t: HostTensor, kvh: int, r: int) -> HostTensor:
    """Repeat a kv projection's per-head row blocks r times (axis 0, row
    order: virtual head j = real head j//r) — the host-side half of
    models/params.replicate_kv_heads, done before placement so each device
    receives only its virtual head's shard."""

    def rep(a):
        if a is None:
            return None
        per = a.shape[0] // kvh
        return np.repeat(a.reshape(kvh, per, *a.shape[1:]), r,
                         axis=0).reshape(kvh * r * per, *a.shape[1:])

    return HostTensor(t.name, t.ftype, (t.shape[0] * r, *t.shape[1:]),
                      data=rep(t.data), scales=rep(t.scales),
                      packed=rep(t.packed))


def _q40_raw_stack(ts: list[HostTensor]) -> tuple[np.ndarray, np.ndarray]:
    """(packed, scales) in raw block layout for one tensor or an E-stacked
    expert list — the single host-side Q40 pipeline every load path uses."""
    qs = [t if t.ftype == FloatType.Q40 else to_q40_host(t.to_f32())
          for t in ts]
    packed = np.stack([q.packed for q in qs]) if len(ts) > 1 else qs[0].packed
    scales = np.stack([q.scales for q in qs]) if len(ts) > 1 else qs[0].scales
    return packed, scales


def _q40_host_stack(ts: list[HostTensor]) -> tuple[np.ndarray, np.ndarray]:
    """Like _q40_raw_stack but in the flattened device layout."""
    packed, scales = _q40_raw_stack(ts)
    return QuantizedTensor.host_layout(scales, packed)


def _dense_host_stack(ts: list[HostTensor]) -> np.ndarray:
    return (np.stack([t.to_f32() for t in ts]) if len(ts) > 1
            else ts[0].to_f32())


class _Placer:
    """Converts one host tensor (or fusion group) to device arrays with the
    right NamedSharding, tracking q80-collective col repacking and
    expert-parallel (ep) placement — each device receives only its E/ep
    experts' shards directly, so peak per-device expert memory at load is
    E/(ep*tp), never full-E (the point of placement-EP)."""

    def __init__(self, mesh, mode: str, dtype, tp: int, q80_collectives: bool,
                 ep: int = 1, vocab_axes: tuple | None = None):
        self.mesh = mesh
        self.mode = mode
        self.dtype = dtype
        self.tp = tp
        self.q80 = q80_collectives and tp > 1
        self.ep = ep
        # vocab sharding (ops/sharded_vocab.py): tok_emb/wcls place
        # row-split over these axes AT LOAD — the 70B-scale path must
        # never hold a replicated 524 MB table per device only for the
        # engine to reshard it
        self.vocab_axes = vocab_axes

    def _put(self, x: np.ndarray, pspec):
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, NamedSharding(self.mesh, pspec))

    def dense(self, key: str, x: np.ndarray):
        return self._put(x, leaf_pspec(key, x.ndim, self.vocab_axes))

    def weight(self, key: str, ts: list[HostTensor], expert: bool = False):
        """A matmul weight: single tensor, or (expert) an E-stacked expert
        list. Applies mode (dense/q40), col repack for q80 collectives, ep
        placement for MoE expert stacks, sharding."""
        moe_ep = self.ep > 1 and expert
        if self.mode != "q40":
            x = _dense_host_stack(ts)
            x = x.astype(np.dtype(self.dtype) if self.dtype != jnp.bfloat16
                         else np.float32)
            if (self.q80 or moe_ep) and LEAF_SPLIT[key] == "col":
                n = x.shape[-1]
                xs = x.reshape(*x.shape[:-1], self.tp, n // self.tp)
                xs = np.moveaxis(xs, -2, 0)
                from ..parallel.ep_moe import EpColWeight
                from ..parallel.tp_q80 import TpColWeight

                wrap = EpColWeight if moe_ep else TpColWeight
                arr = self._put(np.ascontiguousarray(xs),
                                _col_stack_pspec(xs.ndim, ep=moe_ep))
                return wrap(
                    arr if self.dtype != jnp.bfloat16
                    else arr.astype(jnp.bfloat16))
            if moe_ep:
                from ..parallel.ep_moe import EpRowWeight

                from ..parallel.ep_moe import ep_row_pspec

                arr = self._put(x, ep_row_pspec(x.ndim))
                return EpRowWeight(
                    arr.astype(self.dtype) if self.dtype == jnp.bfloat16
                    else arr)
            arr = self._put(x, leaf_pspec(key, x.ndim, self.vocab_axes))
            return arr.astype(self.dtype) if self.dtype == jnp.bfloat16 else arr

        packed, scales = _q40_raw_stack(ts)
        if (self.q80 or moe_ep) and LEAF_SPLIT[key] == "col":
            return self._col_q40(packed, scales, ep=moe_ep)
        pk, sc = QuantizedTensor.host_layout(scales, packed)
        if moe_ep:
            from ..parallel.ep_moe import EpRowWeight, ep_row_pspec

            return EpRowWeight(QuantizedTensor(
                self._put(pk, ep_row_pspec(pk.ndim)),
                self._put(sc, ep_row_pspec(sc.ndim)),
            ))
        return QuantizedTensor(
            self._put(pk, leaf_pspec(key, pk.ndim, self.vocab_axes)),
            self._put(sc, leaf_pspec(key, sc.ndim, self.vocab_axes)),
        )

    def _col_q40(self, packed: np.ndarray, scales: np.ndarray,
                 ep: bool = False):
        """Host-side block-aligned col repack -> TpColWeight stack (or
        EpColWeight for ep-placed expert stacks), placed shard-per-device
        (no transient full copy on one device — the repack the engine-side
        path cannot avoid, parallel/sharding.py)."""
        from ..parallel.ep_moe import EpColWeight
        from ..parallel.tp_q80 import TpColWeight

        pk_dev, sc_dev = _col_q40_host(packed, scales, self.tp)
        wrap = EpColWeight if ep else TpColWeight
        return wrap(QuantizedTensor(
            self._put(pk_dev, _col_stack_pspec(pk_dev.ndim, ep=ep)),
            self._put(sc_dev, _col_stack_pspec(sc_dev.ndim, ep=ep)),
        ))


def _col_q40_host(packed: np.ndarray, scales: np.ndarray, tp: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Raw-layout Q40 (…, nb, 16) -> block-aligned (tp, …, nb/tp…) col stack
    in the flattened device layout (parallel/tp_q80.repack_col_tp semantics,
    host-side)."""
    nb = packed.shape[-2]
    assert nb % tp == 0, (nb, tp)
    lead = packed.shape[:-2]
    pk = np.moveaxis(packed.reshape(*lead, tp, nb // tp, 16), -3, 0)
    sc = np.moveaxis(scales.reshape(*lead, tp, nb // tp), -2, 0)
    return QuantizedTensor.host_layout(
        np.ascontiguousarray(sc), np.ascontiguousarray(pk))


def _col_stack_pspec(ndim: int, ep: bool = False):
    if ep:  # EpColWeight layout — single source in parallel/ep_moe.py
        from ..parallel.ep_moe import ep_col_pspec

        return ep_col_pspec(ndim)
    return P(TP_AXIS, *([None] * (ndim - 1)))


class _PpStacker:
    """Builds stage-stacked PpWeight leaves (parallel/pp.py) one layer
    tensor at a time: a zero-initialized (pp, ...) buffer sharded over pp
    receives each stage's row via a donated dynamic_update_slice, so the
    per-device footprint is the final L/pp share plus one transient host
    tensor — never the full-L restack the engine-side path pays."""

    def __init__(self, mesh, pp: int, tp: int = 1, ep: int = 1):
        self.mesh = mesh
        self.pp = pp
        self.tp = tp
        self.ep = ep

        @functools.partial(jax.jit, donate_argnums=0, static_argnums=3)
        def update(buf, row, stage, sharding):
            row = row.astype(buf.dtype)[None]
            start = (stage,) + (0,) * (buf.ndim - 1)
            out = jax.lax.dynamic_update_slice(buf, row, start)
            return jax.lax.with_sharding_constraint(out, sharding)

        @functools.partial(jax.jit, static_argnums=(0, 1, 2))
        def zeros(shape, dtype, sharding):
            return jax.lax.with_sharding_constraint(
                jnp.zeros(shape, dtype), sharding)

        self._update = update
        self._zeros = zeros  # one jit each — cache hits per distinct shape

    def _row(self, buf, arr: np.ndarray, stage: int, inner_pspec, dtype):
        sh = NamedSharding(self.mesh, P(PP_AXIS, *inner_pspec))
        if buf is None:
            buf = self._zeros((self.pp,) + arr.shape, jnp.dtype(dtype), sh)
        return self._update(buf, jnp.asarray(arr), stage, sh)

    def add(self, slot: dict, key: str, stage: int, mode: str, dtype,
            ts: list[HostTensor], *, keep_f32: bool = False,
            expert: bool = False):
        """Fold one layer tensor (or fused/expert-stacked group) into the
        slot's stage-stacked leaf."""
        from ..parallel.ep_moe import (EpColWeight, EpRowWeight, ep_col_pspec,
                                       ep_row_pspec)
        from ..parallel.pp import PpWeight
        from ..parallel.tp_q80 import TpColWeight

        cur = slot.get(key)
        moe_ep = self.ep > 1 and expert
        if mode != "q40" or keep_f32:
            x = _dense_host_stack(ts)
            leaf_dtype = jnp.float32 if keep_f32 else dtype
            if moe_ep and LEAF_SPLIT[key] == "col":
                # ep x pp dense moe_down: (tp, E, d, n/tp) col stack per
                # stage — PpWeight(EpColWeight(...)), mirroring _Placer
                n = x.shape[-1]
                xs = np.ascontiguousarray(np.moveaxis(
                    x.reshape(*x.shape[:-1], self.tp, n // self.tp), -2, 0))
                old = cur.w.w if cur is not None else None
                slot[key] = PpWeight(EpColWeight(self._row(
                    old, xs, stage, ep_col_pspec(xs.ndim), leaf_dtype)))
                return
            if moe_ep:
                old = cur.w.w if cur is not None else None
                slot[key] = PpWeight(EpRowWeight(self._row(
                    old, x, stage, ep_row_pspec(x.ndim), leaf_dtype)))
                return
            spec = leaf_pspec(key, x.ndim)
            slot[key] = PpWeight(self._row(
                cur.w if cur is not None else None, x, stage, spec,
                leaf_dtype))
            return
        if moe_ep and LEAF_SPLIT[key] == "col":
            # ep x pp q40 moe_down: block-aligned (tp, E, d, ...) col
            # stack, stage-stacked — PpWeight(EpColWeight(QuantizedTensor))
            packed, scales = _q40_raw_stack(ts)
            pk, sc = _col_q40_host(packed, scales, self.tp)
            old = cur.w.w if cur is not None else None
            slot[key] = PpWeight(EpColWeight(QuantizedTensor(
                self._row(old.packed if old is not None else None, pk,
                          stage, ep_col_pspec(pk.ndim), pk.dtype),
                self._row(old.scales if old is not None else None, sc,
                          stage, ep_col_pspec(sc.ndim), sc.dtype),
            )))
            return
        if moe_ep:
            # ep x pp q40 moe_up/moe_gate: expert-stacked rows, experts on
            # ep — PpWeight(EpRowWeight(QuantizedTensor))
            pk, sc = _q40_host_stack(ts)
            old = cur.w.w if cur is not None else None
            slot[key] = PpWeight(EpRowWeight(QuantizedTensor(
                self._row(old.packed if old is not None else None, pk,
                          stage, ep_row_pspec(pk.ndim), pk.dtype),
                self._row(old.scales if old is not None else None, sc,
                          stage, ep_row_pspec(sc.ndim), sc.dtype),
            )))
            return
        if LEAF_SPLIT[key] == "col" and self.tp > 1:
            # pp's fully-manual region slices weights at placement: q40 col
            # shards must be block-aligned TpColWeight stacks, stage-stacked
            # to (pp, tp, ..., d, m/tp) — PpWeight(TpColWeight(...))
            packed, scales = _q40_raw_stack(ts)
            pk, sc = _col_q40_host(packed, scales, self.tp)
            inner = P(TP_AXIS, *([None] * (pk.ndim - 1)))
            old = cur.w.w if cur is not None else None
            slot[key] = PpWeight(TpColWeight(QuantizedTensor(
                self._row(old.packed if old is not None else None, pk,
                          stage, inner, pk.dtype),
                self._row(old.scales if old is not None else None, sc,
                          stage, P(TP_AXIS, *([None] * (sc.ndim - 1))),
                          sc.dtype),
            )))
            return
        pk, sc = _q40_host_stack(ts)
        old = cur.w if cur is not None else None
        slot[key] = PpWeight(QuantizedTensor(
            self._row(old.packed if old is not None else None, pk, stage,
                      leaf_pspec(key, pk.ndim), pk.dtype),
            self._row(old.scales if old is not None else None, sc, stage,
                      leaf_pspec(key, sc.ndim), sc.dtype),
        ))


def _concat_host(ts: list[HostTensor], mode: str) -> list[HostTensor]:
    """Concatenate a fusion group along the output dim on the host."""
    if mode == "q40":
        qs = [t if t.ftype == FloatType.Q40 else to_q40_host(t.to_f32())
              for t in ts]
        return [HostTensor("", FloatType.Q40,
                           (sum(t.shape[0] for t in ts), ts[0].shape[1]),
                           scales=np.concatenate([q.scales for q in qs]),
                           packed=np.concatenate([q.packed for q in qs]))]
    x = np.concatenate([t.to_f32() for t in ts], axis=0)
    return [HostTensor("", FloatType.F32, x.shape, data=x)]


def load_params_streamed(
    spec: ModelSpec,
    path: str | None,
    mesh=None,
    *,
    mode: str = "q40",
    dtype=jnp.bfloat16,
    q80_collectives: bool = False,
    fuse: bool | None = None,
    tensors=None,
    shard_vocab: bool | None = None,
) -> tuple[dict, LoadStats]:
    """Stream the `.m` file into a final, placed params pytree.

    fuse defaults to tp == 1 (matching Engine's single-shard fast path).
    Returns (params, LoadStats) — peak_host_bytes is the loader's measured
    high-water mark of resident file-tensor bytes.

    tensors: optional HostTensor iterator replacing the file read — the
    multihost root-push path feeds parallel.multihost.bcast_model_tensors
    here so a worker WITHOUT the `.m` places shards straight from the
    root's broadcast (path may then be None on workers).
    """
    assert mode in ("dense", "q40")
    tp = mesh.shape.get(TP_AXIS, 1) if mesh is not None else 1
    ep = mesh.shape.get(EP_AXIS, 1) if mesh is not None else 1
    pp = mesh.shape.get(PP_AXIS, 1) if mesh is not None else 1
    kv_rep = 1
    if tp > spec.n_kv_heads:
        # tp beyond the kv-head count: wk/wv rows replicate host-side into
        # tp virtual heads BEFORE placement, so each device still receives
        # exactly its shard (models/params.kv_replication)
        kv_rep = kv_replication(spec, tp)
    if fuse is None:
        fuse = tp == 1
    if pp > 1:
        assert spec.n_layers % pp == 0, (spec.n_layers, pp)
        assert not q80_collectives, (
            "pp loading uses exact reduces (matching Engine)")
    n_slot = spec.n_layers // pp
    # vocab sharding (ops/sharded_vocab.py): place tok_emb/wcls row-split
    # at load — same auto rule as the Engine, so the arrays arrive in the
    # layout shard_params expects and nothing reshards (a replicated 70B
    # table would otherwise cost 524 MB on EVERY device just to be thrown
    # away). shard_vocab=False pins the replicated parity placement.
    from ..ops.sharded_vocab import vocab_shard_axes

    vocab_axes: tuple | None = None
    if shard_vocab is not False:
        vocab_axes = vocab_shard_axes(mesh, spec.vocab_size) or None
        if shard_vocab and vocab_axes is None:
            raise ValueError(
                f"shard_vocab: mesh tp axes cannot split vocab="
                f"{spec.vocab_size} evenly")
    placer = _Placer(mesh, mode, dtype, tp, q80_collectives, ep=ep,
                     vocab_axes=vocab_axes)
    pp_stack = _PpStacker(mesh, pp, tp=tp, ep=ep) if pp > 1 else None

    p: dict = {"layers": [dict() for _ in range(n_slot if pp > 1
                                                else spec.n_layers)]}
    pending: dict[tuple, list[HostTensor]] = {}
    peak = 0
    total = 0
    live = 0

    def compute(arr):
        return arr.astype(dtype) if dtype != jnp.float32 else arr

    if tensors is None:
        tensors = iter_model_tensors(path, spec)
    # the declaration (models/tensors.py) walked beside the stream: an
    # entry says what leaf its tensor becomes
    for (name, l, entry), t in zip(model_tensors(spec), tensors, strict=True):
        assert t.name == name, f"tensor {t.name!r} where the plan has {name!r}"
        if kv_rep > 1 and entry.name in ("wk", "wv"):
            # replicate BEFORE accounting so live/peak measure the r-fold
            # bytes actually resident during placement
            t = _replicate_kv_host(t, spec.n_kv_heads, kv_rep)
        b = _host_bytes(t)
        total += b
        live += b
        peak = max(peak, live)
        # under pp layer l maps to slot l % n_slot at stage l // n_slot
        dest, stage = p, None
        if l is not None:
            dest = p["layers"][l % n_slot if pp > 1 else l]
            stage = l // n_slot if pp > 1 else None

        # the leaf, and how many file tensors make it: an expert stack
        # (experts stream in (up, gate, down) x E order), the rows of a
        # thin leaf, a single-shard fusion group, or the tensor alone
        leaf, want = entry.name, 1
        if entry.leaf is Leaf.EXPERT:
            leaf, want = entry.into, spec.n_experts
        elif entry.leaf is Leaf.ROWS or (fuse and entry.fuse):
            leaf = entry.into if entry.leaf is Leaf.ROWS else entry.fuse
            want = len(group_members(spec, l, leaf))
        ts = pending.setdefault((l, leaf), [])
        ts.append(t)
        if len(ts) < want:
            continue
        del pending[l, leaf]
        if want > 1:
            # a finished group's host buffers outlive the next group's first
            # reads. Freed at once (a layer's three expert stacks: 280 MB),
            # the allocator hands the next layer cold pages and the file
            # read's copies take 14.0 s for 8.0 (kimi-linear-48b-a3b-ep4 on
            # the v5e's host, 37-40 s a load for 29-30; PERF.md section 6,
            # PR 51). The bytes are counted out of `live` below all the same.
            held = ts  # noqa: F841

        if entry.leaf is Leaf.HALVES:
            assert stage is None, "the latent cache does not support --pp"
            for half_name, half in zip(entry.into,
                                       split_wkvb(spec, t.to_f32())):
                dest[half_name] = compute(placer.dense(half_name, half))
        elif entry.leaf is Leaf.ROWS:
            dest[leaf] = compute(placer.dense(leaf, np.concatenate(
                [x.to_f32() for x in ts])))
        elif entry.leaf in (Leaf.F32, Leaf.COMPUTE):
            f32 = entry.leaf is Leaf.F32   # norms stay f32, stacked or not
            if stage is not None:
                pp_stack.add(dest, leaf, stage, "dense", dtype, ts,
                             keep_f32=f32)
            else:
                arr = placer.dense(leaf, t.to_f32())
                dest[leaf] = arr if f32 else compute(arr)
        else:
            expert = entry.leaf is Leaf.EXPERT
            cts = ts if expert or want == 1 else _concat_host(ts, mode)
            if stage is not None:
                pp_stack.add(dest, leaf, stage, mode, dtype, cts,
                             expert=expert)
            else:
                dest[leaf] = placer.weight(leaf, cts, expert=expert)
        live -= sum(_host_bytes(x) for x in ts)

    assert not pending, f"incomplete groups: {list(pending)}"
    return p, LoadStats(peak_host_bytes=peak, total_bytes=total)
