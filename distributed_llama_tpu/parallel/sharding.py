"""Partition specs reproducing the reference's tensor-parallel decomposition.

The reference splits weights two ways (ref: src/transformer.cpp:14-76):

  RowMatmulSlice — output-dim split: wq, wk, wv, w1, w3, MoE up/gate/down
  ColMatmulSlice — input-dim split (partial sums reduced at root): wo, w2

Here the same decomposition is a PartitionSpec per tensor; GSPMD turns the
col-split contractions into psum/reduce-scatter over ICI — the reference's
gather+sum-at-root (ref: src/tasks.cpp:67-90, llama2-tasks.cpp:125-131)
with the star topology replaced by all-reduce.

Unsliced tensors (embeddings, norms, router — the reference's root-only set,
ref: src/transformer.cpp:639-673) are replicated. wcls is vocab-sharded (an
improvement: the reference computes all logits on root).

The reference's `nSlices <= nKvHeads` constraint (ref:
src/transformer.cpp:254-257) becomes `n_kv_heads % tp == 0` here; KV-cache
heads shard on tp exactly like KvCacheSlice (ref: src/transformer.cpp:161-171).
Unlike the reference, tp may also EXCEED the kv-head count: the engine then
replicates wk/wv (and the cache) into tp virtual heads
(models/params.kv_replication) and these specs apply unchanged — the relaxed
form of the rule the reference could not support (SURVEY.md §7 step 4).
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.spec import ModelSpec
from ..models.tensors import LEAF_SPLIT
from ..quants.jax_codec import QuantizedTensor
from .mesh import DP_AXIS, TP_AXIS


def leaf_pspec(name: str, ndim: int, vocab_axes: tuple | None = None) -> P:
    """PartitionSpec for one array leaf.

    Dense weights are (lead..., d, n). Q40 leaves are packed (lead..., d, m)
    — flattened nibble-position-major, m = 16*nb — and scales (lead..., d, nb).
    Row split shards the d axis for all three forms. Col split shards the
    last axis; for the packed form a contiguous m shard is a nibble-position
    stripe rather than a block stripe, which GSPMD handles transparently
    (the dequant reshape introduces a resharding); the shard_map TP path
    slices at the logical-tensor level instead and stays block-aligned.

    Which way a leaf splits ('row': the output dim, 'col': the input dim,
    None: replicated) is declared beside the leaf (models/tensors.py); the
    axis positions account for leading stacking dims (the per-expert E axis
    on MoE weights; layers are a pytree list, not an axis). A leaf that is
    not declared is a KeyError.
    """
    split = LEAF_SPLIT[name]
    axes: list = [None] * ndim
    if name in ("tok_emb", "wcls") and vocab_axes is not None:
        # vocab sharding (ops/sharded_vocab.py): the embedding table
        # row-splits its vocab dim — under pp over BOTH (pp, tp), since
        # the gather/head run outside the manual region and every stage
        # would otherwise hold a full copy. wcls keeps its row split but
        # widens to the same axes.
        if name == "tok_emb" or split == "row":
            axes[ndim - 2] = vocab_axes
            return P(*axes)
    if split is None:
        return P(*axes)
    axes[ndim - 2 if split == "row" else ndim - 1] = TP_AXIS
    return P(*axes)


def _leaf_spec(name: str, w, vocab_axes: tuple | None = None):
    from .ep_moe import EpColWeight, EpRowWeight, ep_pspec
    from .mesh import PP_AXIS
    from .pp import PpWeight
    from .tp_q80 import TpColWeight, TpRowWeight, tp_col_pspec, tp_row_pspec

    if isinstance(w, PpWeight):
        # pipeline mode: stage axis on pp, the weight's usual tp split (or
        # its Tp wrapper's stack layout) on the remaining dims — the ONE
        # spec source shared with the manual region's in_specs, so entering
        # the region moves no bytes (parallel/pp.py)
        from .pp import _leaf_in_spec

        return _leaf_in_spec(name, w, TP_AXIS)
    if isinstance(w, (EpRowWeight, EpColWeight)):
        # expert-parallel mode: expert axis on ep (parallel/ep_moe.py)
        return ep_pspec(w)
    if isinstance(w, TpColWeight):
        # q80-collective mode: col weights are pre-stacked (tp, ..., d, n/tp)
        return tp_col_pspec(w)
    if isinstance(w, TpRowWeight):
        # shard_map-kernel mode: output rows on tp, matching the in_specs of
        # tp_row_matmul so entering the shard_map moves no bytes
        return tp_row_pspec(w)
    if isinstance(w, QuantizedTensor):
        return QuantizedTensor(  # pytree-shaped specs
            leaf_pspec(name, w.packed.ndim, vocab_axes),
            leaf_pspec(name, w.scales.ndim, vocab_axes),
        )
    return leaf_pspec(name, w.ndim, vocab_axes)


def param_pspecs(params: dict, vocab_axes: tuple | None = None) -> dict:
    """Pytree of PartitionSpecs matching the params pytree
    ({"tok_emb", "rms_final", "wcls", "layers": [{...}, ...]}).
    vocab_axes: mesh axes row-splitting the vocab dim of tok_emb/wcls
    (ops/sharded_vocab.vocab_shard_axes; None keeps them replicated/
    tp-split as before)."""
    out = {}
    for name, w in params.items():
        if name == "layers":
            out[name] = [{k: _leaf_spec(k, v) for k, v in lw.items()} for lw in w]
        else:
            out[name] = _leaf_spec(name, w, vocab_axes)
    return out


def cache_pspec(sp: bool = False, pp: bool = False) -> P:
    """Per-layer KV cache leaf (B, KVH, S, hs): batch on dp, kv-heads on tp
    (ref: KvCacheSlice, src/transformer.cpp:161-171). With sp=True the
    sequence dim also shards over sp — per-device cache memory becomes
    seq_len/sp, the long-context scaling axis the reference lacks
    (SURVEY.md §5.7); decode then attends via sp_cache_attention. With
    pp=True the leaf is stage-stacked (pp, B, KVH, S, hs) and the stage
    axis shards over pp — each device holds only its layers' cache
    (parallel/pp.py)."""
    from .mesh import PP_AXIS, SP_AXIS

    spec = (DP_AXIS, TP_AXIS, SP_AXIS if sp else None, None)
    return P(PP_AXIS, *spec) if pp else P(*spec)


def check_tp_constraints(spec: ModelSpec, tp: int, q40: bool = False) -> None:
    """Divisibility rules; the reference asserts the same invariants
    (ref: src/transformer.cpp:15,49,254-257,78-96). The engine calls this
    with its COMPUTE spec: when tp > the file's n_kv_heads it has already
    replicated kv heads to tp virtual heads (models/params.kv_replication),
    so the reference's nSlices <= nKvHeads bound is relaxed upstream."""
    if tp == 1:
        return
    assert spec.n_kv_heads % tp == 0, (
        f"tp={tp} must divide n_kv_heads={spec.n_kv_heads} "
        "(reference constraint nSlices <= nKvHeads, transformer.cpp:254-257; "
        "for tp > n_kv_heads the engine replicates kv heads first)")
    assert spec.n_heads % tp == 0
    assert spec.hidden_dim % tp == 0 and spec.dim % tp == 0
    if q40:
        # col-split shards must keep whole 32-value blocks
        assert spec.hidden_dim % (32 * tp) == 0
        assert spec.dim % (32 * tp) == 0


def repack_col_weights(params: dict, tp: int) -> dict:
    """Repack every col-split weight into the TpColWeight stacked form used
    by the q80-collective shard_map path (parallel/tp_q80.py). Non-mutating
    (callers may keep using the original pytree, e.g. to compare modes).

    Note: on device-resident weights this transiently duplicates each col
    weight on the default device before shard_params distributes it; the
    streamed loader (models/loader.py) repacks host-side per tensor and
    places shards directly, avoiding the spike — prefer it at 70B scale."""
    from .tp_q80 import TpColWeight, repack_col_tp

    def repack(v):
        from .ep_moe import EpColWeight
        from .pp import PpWeight

        # already repacked (streamed loader) or owned by the ep path; a
        # PpWeight is the streamed loader's stage stack, whose q40 col
        # leaves it repacked at build time (models/loader._PpStacker)
        if isinstance(v, (TpColWeight, EpColWeight, PpWeight)):
            return v
        return repack_col_tp(v, tp)

    out = dict(params)
    out["layers"] = [
        {k: (repack(v) if LEAF_SPLIT.get(k) == "col" else v)
         for k, v in lw.items()}
        for lw in params["layers"]
    ]
    return out


def wrap_row_weights(params: dict) -> dict:
    """Mark every remaining Q40 matmul weight as TpRowWeight so matmul()
    routes it through the shard_map Pallas path (parallel/tp_q80.py). Run
    AFTER repack_col_weights when tp > 1 — col-split weights must already be
    TpColWeight stacks; with tp == 1 (dp-only meshes) col weights are
    unsplit and row-wrapping them is correct (marker only, no sharding)."""
    from .tp_q80 import TpRowWeight

    def wrap(name, v):
        if (LEAF_SPLIT.get(name) is not None
                and isinstance(v, QuantizedTensor)):
            return TpRowWeight(v)
        return v

    out = dict(params)
    out["layers"] = [
        {k: wrap(k, v) for k, v in lw.items()} for lw in params["layers"]
    ]
    if isinstance(out.get("wcls"), QuantizedTensor):
        out["wcls"] = TpRowWeight(out["wcls"])
    return out


def shard_params(params: dict, mesh, vocab_axes: tuple | None = None) -> dict:
    """device_put every leaf with its NamedSharding (sharded weight placement —
    the analogue of the reference's per-worker weight push at load,
    ref: src/transformer.cpp:562-591)."""
    specs = param_pspecs(params, vocab_axes)

    def put(w, s):
        return jax.device_put(w, NamedSharding(mesh, s))

    def put_entry(w, sp):
        from .wrappers import WeightWrapper

        if isinstance(w, WeightWrapper):
            return type(w)(put_entry(w.w, sp.w))
        if isinstance(w, QuantizedTensor):
            return QuantizedTensor(put(w.packed, sp.packed), put(w.scales, sp.scales))
        return put(w, sp)

    out = {}
    for name, w in params.items():
        if name == "layers":
            out[name] = [
                {k: put_entry(v, specs[name][i][k]) for k, v in lw.items()}
                for i, lw in enumerate(w)
            ]
        else:
            out[name] = put_entry(w, specs[name])
    return out
