"""Inference engine: compiled prefill/decode over an optional mesh.

TPU-native replacement for the reference's Inference driver + generation
loops (ref: src/tasks.cpp:184-256, src/apps/dllama/dllama.cpp:14-91):

  * one jitted segment-forward instead of the per-token task list; the KV
    cache is donated so decode updates in place (no realloc per token)
  * chunked prefill (the reference feeds the prompt token-by-token)
  * sharded execution: params/cache placed with NamedShardings over a
    (dp, sp, tp) mesh; GSPMD emits the ICI collectives that replace the
    reference's socket broadcast/gather choreography
  * greedy sampling on device (argmax fused into the step); full
    temperature/top-p sampling on host with reference-parity RNG
"""

from __future__ import annotations

import math
import time
from functools import partial
from typing import Callable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.params import fuse_layer_weights
from ..models.spec import LayerKind, ModelSpec
from ..models.transformer import KVCache, forward, takes_slot_map
from ..parallel.mesh import DP_AXIS, SP_AXIS, TP_AXIS
from ..parallel.sharding import cache_pspec, check_tp_constraints, shard_params
from ..sampler import Sampler
from .stats import RunStats, StepStats


class GenerationResult(NamedTuple):
    tokens: list[int]
    stats: RunStats


def seed_rows_from_blocks(cache: KVCache, arena_k, arena_v, row, block_ids
                          ) -> KVCache:
    """Seed cache row ``row``'s leading positions from prefix-arena blocks
    — the traced body of ``Engine.slot_seed_prefix`` (module-level so
    analysis/entrypoints.py fingerprints the SAME program the engine jits;
    dlgrind's DLG204 gate covers the serving seed path by construction).

    arena_k/arena_v: (num_blocks, layers, kv_heads, block_len, head_size)
    — block-major with the per-layer block laid out exactly like a cache
    slice (KVH before the sequence dim), so seed and publish are pure
    gathers/reshapes, never transposed HBM traffic against the cache's
    head-major layout. block_ids is the FIXED-width
    (seq_len // block_len,) int32 vector the scheduler always pads (with
    block 0) — the pad keeps ONE compilation key for every admission
    (the same discipline as slot_prefill_chunk's fixed C). Padded
    blocks' writes land beyond the real seeded prefix and are
    overwritten position-by-position (suffix prefill, then decode)
    before any query can attend them — the same invariant decode
    overruns rely on everywhere in the engine.

    Blocks pass through the f8 NaN-code guard
    (ops/pallas_attention.saturate_f8_nan_codes): arena bytes written by
    this engine's own forwards are saturated already, but the seeding
    boundary must not TRUST its producer — see Engine._seed_guard."""
    from ..ops.pallas_attention import saturate_f8_nan_codes

    mb = block_ids.shape[0]
    z = jnp.int32(0)
    row = jnp.asarray(row, jnp.int32)
    # a cache without V leaves (the latent cache) seeds its K leaves only;
    # its V arena is zero wide
    arenas = (arena_k, arena_v) if cache.v else (arena_k,)
    new: tuple = ([], [])
    for l in range(len(cache.k)):
        for arena, leaves, out in zip(arenas, cache, new):
            if arena.ndim == 3:     # flat blocks (Engine.new_prefix_arena)
                kvh, hs = leaves[l].shape[1], leaves[l].shape[3]
                bl = arena.shape[2] // hs
                seg = arena[block_ids, l * kvh:(l + 1) * kvh].reshape(
                    mb, kvh, bl, hs)
            else:
                _, _, kvh, bl, hs = arena.shape
                seg = arena[block_ids, l]              # (MB, KVH, bl, hs)
            seg = seg.transpose(1, 0, 2, 3).reshape(1, kvh, mb * bl, hs)
            seg = saturate_f8_nan_codes(seg.astype(leaves[l].dtype))
            out.append(lax.dynamic_update_slice(leaves[l], seg,
                                                (row, z, z, z)))
    return KVCache(tuple(new[0]), tuple(new[1]))


def export_arena_block(arena_k, arena_v, src):
    """Gather ONE published arena block pair for the cross-replica KV
    transfer plane (runtime/kv_transfer.py) — the traced body of
    ``Engine.block_export`` (module-level so analysis/entrypoints.py
    fingerprints the SAME program the engine jits). The arenas are only
    READ (never donated: the block stays published locally); the caller
    fetches the returned (layers, kv_heads, block_len, head_size) pair
    to host and ships the raw bytes."""
    src = jnp.asarray(src, jnp.int32)
    return (lax.dynamic_index_in_dim(arena_k, src, 0, keepdims=False),
            lax.dynamic_index_in_dim(arena_v, src, 0, keepdims=False))


def flat_arena(spec: ModelSpec) -> bool:
    """Whether the prefix arena holds a block's rows FLAT, (num_blocks,
    layers x kv_heads, block_len x width): for a cache row that is not
    whole 128-lane tiles (the latent cache's 576). The TPU's own layout
    of the five-dimensional (num_blocks, layers, 1, 32, 576) puts
    num_blocks in the lanes, and one block's write then strides over the
    whole arena (2.85 ms a block on the v5e, 30 % of the device's time
    under long prompts); flat, a block is contiguous."""
    return spec.cache_head_size % 128 != 0


def import_arena_block(arena_k, arena_v, k_blk, v_blk, dst):
    """Write one fetched block pair into arena slot ``dst`` — the traced
    body of ``Engine.slot_import_block``. The arenas are donated
    (in-place block write, same discipline as slot_publish_block). The
    bytes are written RAW: the seeding boundary's f8 NaN-code guard
    (seed_rows_from_blocks -> saturate_f8_nan_codes) runs when a slot is
    SEEDED from the block, so foreign bytes can never decode as finite
    480 in an attention read whatever their producer did."""
    dst = jnp.asarray(dst, jnp.int32)
    return tuple(
        lax.dynamic_update_slice(
            arena, blk.reshape((1,) + arena.shape[1:]),   # flat arenas too
            (dst,) + (jnp.int32(0),) * (arena.ndim - 1))
        for arena, blk in ((arena_k, k_blk), (arena_v, v_blk)))


class Engine:
    def __init__(
        self,
        spec: ModelSpec,
        params: dict,
        mesh: Mesh | None = None,
        *,
        batch: int = 1,
        max_seq_len: int | None = None,
        compute_dtype=jnp.bfloat16,
        cache_dtype=jnp.bfloat16,
        activation_q80: bool = False,
        q80_collectives: bool | None = None,
        prefill_chunk: int = 256,  # = pallas MAX_T: fewest whole-weight
        # passes that still take the fused kernel (A/B on v5e: 3009 tok/s
        # prefill vs 1899 at 128; 512+ would fall to the XLA dequant path
        # and measured slower)
        use_pallas: bool | None = None,
        pallas_interpret: bool = False,
        pp_gpipe: bool = True,  # GPipe sequence-microbatch prefill on pp
        # meshes (parallel/pp.py:pp_layers_gpipe); False pins the
        # all-stages scheme everywhere (A/B knob)
        model_fingerprint: int = 0,  # content hash of the weights the
        # session fingerprint folds in (io.model_file.content_fingerprint);
        # 0 = unknown (in-memory params) — such sessions only check shapes
        force_mesh_kernels: bool = False,  # engage the shard_map kernel
        # path even on a 1-device mesh: the Pallas kernels then compile and
        # run INSIDE manual regions on whatever silicon is present — the
        # single-chip proof of the multi-chip kernel path
        shard_vocab: bool | None = None,  # row-split tok_emb/wcls over the
        # vocab dim (ops/sharded_vocab.py): None = auto (on whenever the
        # mesh's tp axes divide the vocab — the replicated table was
        # 533 MB/chip at 70B widths, VERDICT weak #3); True asserts the
        # mesh can; False pins the replicated parity oracle
        vocab_topk: int = 32,  # per-shard candidate count for the sharded
        # sampled path (k·S candidates provably contain the global top-k;
        # a nucleus larger than the guard allows falls back to one
        # replicated row fetch — docs/parallelism.md "Vocab sharding")
    ):
        self.mesh = mesh
        self.batch = batch
        self.pp_gpipe = pp_gpipe
        self.model_fingerprint = int(model_fingerprint)
        self.seq_len = min(max_seq_len or spec.seq_len, spec.seq_len)
        self.compute_dtype = compute_dtype
        self.cache_dtype = cache_dtype
        self.activation_q80 = activation_q80
        self.prefill_chunk = prefill_chunk
        tp = mesh.shape.get("tp", 1) if mesh is not None else 1
        if tp > spec.n_kv_heads:
            # kv-head replication: tp exceeds the kv-head count, so wk/wv
            # expand to tp virtual heads and the spec the engine computes
            # with reflects that (models/params.kv_replication — the relaxed
            # form of the reference's nSlices <= nKvHeads rule)
            import dataclasses

            from ..models.params import replicate_kv_heads

            params = replicate_kv_heads(params, spec, tp)
            spec = dataclasses.replace(spec, n_kv_heads=tp)
        self.spec = spec
        if spec.is_mla:
            # one latent head and per-head absorb operands: nothing to
            # split over tp, and the manual pp/sp/ep regions do not know
            # the latent block; dp replicas are the way to more chips
            assert mesh is None or all(
                mesh.shape.get(a, 1) == 1 for a in ("tp", "pp", "sp", "ep")), (
                f"{spec.arch.name} keeps a latent cache and runs on one "
                "shard (dp only)")
        if mesh is not None and any(
                mesh.shape.get(a, 1) > 1 for a in ("tp", "pp", "sp", "ep")):
            spec.refuse("parallel")
        # --buffer-float-type q80 with tp>1 => wo/w2 partial sums exchange
        # int8 blocks over ICI instead of the GSPMD-exact f32 all-reduce
        # (the reference's wire compression, ref: src/tasks.cpp:124-163)
        if q80_collectives is None:
            q80_collectives = activation_q80 and tp > 1
        self.q80_collectives = q80_collectives and tp > 1
        self._tp_mesh = mesh if self.q80_collectives else None
        # sp > 1: the KV cache's sequence dim shards over sp (per-device
        # cache = seq_len/sp) and every step attends via sp_cache_attention
        sp = mesh.shape.get(SP_AXIS, 1) if mesh is not None else 1
        if sp > 1:
            assert self.seq_len % sp == 0, (
                f"sp={sp} must divide max_seq_len={self.seq_len} "
                "(sp-sharded KV cache)")
        self._sp_cache_mesh = mesh if sp > 1 else None
        if use_pallas is None:
            # default ON for TPU: the fused kernel reads only packed bytes and
            # keeps the unpack at ~6 VPU ops/byte (measured v5e: 2.4 ms vs
            # 5.0 ms XLA-dequant for the same 0.81 GB packed weight set);
            # prefill segments longer than pallas_q40.MAX_T fall back to the
            # FLOPs-amortized XLA dequant path automatically. On CPU (tests,
            # virtual meshes) Mosaic can't compile — use the XLA path unless
            # pallas_interpret forces the interpreted kernel (tests).
            use_pallas = jax.default_backend() != "cpu" or pallas_interpret
        self.use_pallas = use_pallas
        self.pallas_interpret = pallas_interpret
        # GSPMD cannot auto-partition a pallas_call over sharded operands, so
        # multi-device meshes run the kernels per-shard via shard_map
        # (parallel/tp_q80.py): Q40 weights are marked TpRowWeight/TpColWeight
        # and attention shards over (dp, kv-heads). The col partial-sum
        # reduce is exact unless q80 collectives are on.
        mesh_kernels = use_pallas and mesh is not None and (
            mesh.size > 1 or force_mesh_kernels)
        self.tp_reduce = "q80" if self.q80_collectives else "exact"
        if mesh_kernels:
            self._tp_mesh = mesh
        # ep > 1: MoE experts are PLACED across the ep axis (E/ep experts per
        # device — net-new vs the reference's TP-only expert slicing); the
        # MoE block always runs the shard_map path then (parallel/ep_moe.py)
        from ..parallel.mesh import EP_AXIS

        ep = mesh.shape.get(EP_AXIS, 1) if mesh is not None else 1
        if ep > 1:
            assert spec.is_moe, "--ep requires a MoE model (experts to place)"
            assert spec.n_experts % ep == 0, (
                f"ep={ep} must divide n_experts={spec.n_experts}")
            self._tp_mesh = mesh
        # pp > 1: layers are PLACED in stages across the pp axis (L/pp layers
        # + their KV cache per device — net-new vs the reference, where every
        # node runs every layer). The layer loop runs inside a FULLY-manual
        # shard_map (parallel/pp.py) — tp is manual there too, so the fused
        # Pallas kernels run per shard exactly like the tp path (no 2x
        # XLA-dequant penalty; VERDICT r2 weak #1).
        from ..parallel.mesh import PP_AXIS

        pp = mesh.shape.get(PP_AXIS, 1) if mesh is not None else 1
        self._pp = pp
        self._pp_mesh = mesh if pp > 1 else None
        if pp > 1:
            assert spec.n_layers % pp == 0, (
                f"pp={pp} must divide n_layers={spec.n_layers}")
            # ep composes: experts placed across ep INSIDE the manual pp
            # region (each device holds L/pp stages x E/ep experts — the
            # Grok-class scaling layout; parallel/pp.py + ep_moe._ep_body).
            # sp composes too: the cache's sequence dim shards over sp
            # inside the region (scatter writes at chunk-local slots, flash
            # stats merged over sp — transformer._attention_block manual_sp)
            assert not self.q80_collectives, (
                "pp uses exact tp reduces; --buffer-float-type q80 "
                "is not supported with --pp")

        # vocab sharding (ops/sharded_vocab.py): tok_emb becomes a local
        # (vocab/S, dim) shard with a masked gather + all-reduce; wcls
        # keeps its row split (widened over pp when present). Auto-on for
        # tp > 1 whenever the vocab divides; the replicated path stays as
        # the parity oracle (--shard-vocab off / shard_vocab=False).
        from ..ops.sharded_vocab import vocab_shard_axes

        axes = (vocab_shard_axes(mesh, spec.vocab_size)
                if mesh is not None else ())
        if shard_vocab is None:
            self._vocab_axes = axes
        elif shard_vocab:
            assert axes, (
                f"shard_vocab: mesh tp axes cannot split vocab="
                f"{spec.vocab_size} evenly (tp="
                f"{mesh.shape.get('tp', 1) if mesh is not None else 1})")
            self._vocab_axes = axes
        else:
            self._vocab_axes = ()
        self.shard_vocab = bool(self._vocab_axes)
        self.vocab_topk = int(vocab_topk)
        # counters /stats surfaces: how often the sharded
        # fast path served a sample vs the replicated-row parity fallback
        self.vocab_sample_stats = {"sharded": 0, "fallback": 0}
        # the newest slot step's own summary (_note_summary), for
        # sample_view: (logits, packed summary, temps, n_vocab)
        self._step_summary = None

        if tp == 1:
            # single-shard fast path: fused QKV / w1|w3 kernel calls
            params = fuse_layer_weights(params)
        else:
            # a tp == 1 engine sharing this params dict may have fused it in
            # place; row splits of the fused dims cross the q|k|v boundaries
            from ..models.params import unfuse_layer_weights

            params = unfuse_layer_weights(params, spec)
        if mesh is not None:
            from ..quants.jax_codec import QuantizedTensor

            from ..parallel.wrappers import WeightWrapper

            def _leaf(v):  # loader-marked leaves wrap the quantized tensor
                return v.w if isinstance(v, WeightWrapper) else v

            q40 = any(isinstance(_leaf(v), QuantizedTensor)
                      for lw in params["layers"] for v in lw.values())
            check_tp_constraints(spec, tp, q40=q40)
            if ep > 1:
                from ..parallel.ep_moe import EpRowWeight, repack_moe_ep
                from ..parallel.pp import PpWeight

                params = dict(params)
                params["layers"] = [
                    # PpWeight = the streamed loader's stage stack, whose
                    # ep mode already built PpWeight(Ep...) leaves
                    lw if isinstance(lw.get("moe_up"),
                                     (EpRowWeight, PpWeight))
                    else repack_moe_ep(lw, tp)
                    for lw in params["layers"]
                ]
            if (self.q80_collectives or (mesh_kernels and tp > 1 and q40)
                    or (pp > 1 and tp > 1 and q40)):
                # pp x tp always repacks q40 cols: the manual region slices
                # weights AT PLACEMENT, and a contiguous packed-byte stripe
                # is a nibble-position stripe, not a valid local Q40 tensor
                # (the GSPMD path reshards transparently; manual cannot)
                from ..parallel.sharding import repack_col_weights

                params = repack_col_weights(params, tp)
            if mesh_kernels and q40:
                from ..parallel.sharding import wrap_row_weights

                params = wrap_row_weights(params)
            if pp > 1:
                from ..parallel.pp import stack_stages

                params = stack_stages(params, pp)
            self.params = shard_params(params, mesh,
                                       self._vocab_axes or None)
            self._cache_sharding = NamedSharding(
                mesh, cache_pspec(sp=sp > 1, pp=pp > 1))
            self._token_sharding = NamedSharding(mesh, P(DP_AXIS, None))
        else:
            self.params = params
            self._cache_sharding = None
            self._token_sharding = None

        # whether the rows of the prefill chunk program follow a slot map
        # (slot_prefill_chunk's `slots`; prefill_rows_per_slot says what
        # it takes): decided here, once, from the layer kinds and the mesh
        self._chunk_slot_map = takes_slot_map(
            spec, meshed=self._token_sharding is not None)
        self._identity_map = None   # arange(batch), made at the first chunk

        # mesh spanning >1 process (jax.distributed): host code may only
        # fetch fully-replicated arrays, so logits are all-gathered to every
        # host before sampling (parallel/multihost.py)
        from ..parallel.multihost import is_multihost

        self._multihost = is_multihost(mesh)
        self._replicator = None

        # compile-cache + ledger state BEFORE the first mint (_new_cache
        # below jits the cache maker): every executable this engine ever
        # builds routes through _mint, and _compile_warm arms the
        # recompile sentinel once Scheduler.warmup() has compiled the
        # serving set (runtime/profiler.py)
        self._steps: dict[int | tuple[str, int], Callable] = {}
        self._compile_warm = False
        self._expert_counts: list = []  # (program, device counts) not yet taken
        self.cache = self._new_cache()
        self.pos = 0

    # -- compile ledger ----------------------------------------------------

    def _mint(self, key, fn: Callable) -> Callable:
        """Register one freshly-jitted executable under `key`, routed
        through the compile ledger (runtime/profiler.py): the first call
        is timed as the compile (entry key, wall ms) and — on a warm
        engine — trips the recompile sentinel (a structured error under
        --freeze-compiles, BEFORE the compile runs). The watch swaps the
        raw jitted callable back into _steps after that first call, so
        the steady-state hot path is byte-for-byte the pre-ledger one.
        Host-side bookkeeping only: the jitted program (and dlgrind's
        fingerprint of it) is untouched."""
        from .profiler import COMPILES

        wrapped = COMPILES.watch(self, key, fn)
        self._steps[key] = wrapped
        return wrapped

    def mark_compile_warm(self) -> None:
        """Arm the recompile sentinel: the serving set is compiled
        (Scheduler.warmup calls this last), so from here every new
        compile key is a `compile_after_warmup` event — and, frozen, a
        structured refusal. Per ENGINE: a supervisor rebuild mints a
        fresh engine whose own warmup legitimately recompiles."""
        self._compile_warm = True

    # -- cache ------------------------------------------------------------

    def _new_cache(self) -> KVCache:
        if self._cache_sharding is None:
            return KVCache.create(self.spec, self.batch, self.seq_len,
                                  self.cache_dtype)
        # allocate directly into the sharded layout (out_shardings) — no
        # transient full-size cache on one device (matters for sp-sharded
        # long-context caches). The jitted maker is built once: reset() is a
        # server hot path (per-request) and must not retrace.
        if "cache_maker" not in self._steps:
            n_l = self.spec.n_cache_layers
            if self._pp > 1:  # stage-stacked: n_layers/pp leaves (pp, ...)
                n_l //= self._pp
            # a state and its tail split over dp with their rows
            rows = (NamedSharding(self.mesh, P(DP_AXIS)),
                    ) * self.spec.n_state_layers
            shardings = KVCache(
                (self._cache_sharding,) * n_l,
                (self._cache_sharding,) * (
                    n_l if self.spec.cache_v_head_size else 0),
                rows, rows)
            self._mint("cache_maker", jax.jit(
                lambda: KVCache.create(self.spec, self.batch, self.seq_len,
                                       self.cache_dtype, pp=self._pp),
                out_shardings=shardings))
        return self._steps["cache_maker"]()

    def reset(self) -> None:
        """New session: rewind position (the API server resets per request,
        ref: src/apps/dllama-api/dllama-api.cpp:236-249)."""
        self.cache = self._new_cache()
        self.pos = 0

    # -- session persistence ----------------------------------------------

    def save_session(self, path: str, tokens: list[int] | None = None) -> None:
        """Persist the generation session — pos and the FILLED cache prefix
        (positions < pos) — to an .npz. Net-new vs the reference, which has
        no KV-cache persistence or session resume (SURVEY.md §5.4): a chat
        can continue across process restarts without re-prefilling its
        history. Narrow dtypes (bf16/fp8) are stored as raw bit patterns
        (numpy's format cannot describe them).

        tokens: optional token history to carry alongside the cache (the
        chat CLI stores its conversation so a resumed session can keep
        mining speculative drafts from pre-restart turns)."""
        assert self._pp == 1, "session save/restore does not support --pp"
        self.spec.refuse("session")
        data: dict = {
            "pos": np.int64(self.pos),
            "cache_dtype": np.str_(jnp.dtype(self.cache_dtype).name),
            "config": np.asarray(self._session_fingerprint(), np.int64),
            "tokens": np.asarray(tokens if tokens is not None else [],
                                 np.int32),
        }
        for name, leaves in (("k", self.cache.k), ("v", self.cache.v)):
            for l, leaf in enumerate(leaves):
                arr = np.asarray(leaf[:, :, : self.pos, :])
                if arr.dtype.itemsize == 1:
                    arr = arr.view(np.uint8)
                elif arr.dtype not in (np.float32, np.float64):
                    arr = arr.view(np.uint16)
                data[f"{name}{l}"] = arr
        # write-then-rename: the cache fetch makes this a seconds-long write
        # for big models, and a signal landing mid-write must never leave a
        # truncated file where a good session stood (chat saves every turn).
        # Open handle: np.savez(str_path) appends ".npz" to extension-less
        # names, which load_session/os.path.exists would then never find.
        import os

        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **data)
        os.replace(tmp, path)

    def load_session(self, path: str) -> list[int]:
        """Restore a save_session() file: refuses a mismatched model/engine
        config, rebuilds the cache with the saved prefix in place (sharded
        placement included) and sets pos. Returns the saved token history
        ([] for files saved without one)."""
        assert self._pp == 1, "session save/restore does not support --pp"
        self.spec.refuse("session")
        z = np.load(path)
        saved, mine = list(z["config"]), self._session_fingerprint()
        # the weight-content element compares only when BOTH sides know it:
        # 0 means in-memory params (and 4-element files predate the field)
        # — those degrade to the shape-only check
        content_ok = (len(saved) < 5 or saved[4] == mine[4]
                      or 0 in (saved[4], mine[4]))
        if saved[:4] != mine[:4] or not content_ok:
            raise ValueError(
                "session file does not match this engine's model/config "
                f"(saved {saved}, engine {mine})")
        pos = int(z["pos"])
        assert pos <= self.seq_len
        self.reset()
        dt = jnp.dtype(self.cache_dtype)
        # cache rows are built ON DEVICE through the shared seeding
        # helper (_seed_jit / _seed_guard — one home for the
        # donation-safety fix and the f8 NaN-code guard)
        lead = (self.batch, self.spec.n_kv_heads, self.seq_len)
        # ledger-watched but NOT cached in _steps: each restore builds a
        # fresh closure (no reuse across calls is possible), so storing
        # it would only pin one dead executable per distinct pos for the
        # engine's lifetime — the watch alone records the compile
        from .profiler import COMPILES

        build = COMPILES.watch(self, ("session_restore", pos),
                               self._seed_jit(
            lambda pfx: jnp.zeros(lead + pfx.shape[3:], dt)
            .at[:, :, :pos, :].set(self._seed_guard(pfx)),
            out_tree=0))
        self.cache = KVCache(*(
            tuple(build(z[f"{name}{l}"].view(dt))
                  for l in range(len(leaves)))
            for name, leaves in (("k", self.cache.k), ("v", self.cache.v))))
        self.pos = pos
        return z["tokens"].tolist() if "tokens" in z.files else []

    # -- cache seeding (session restore + prefix-cache arena) -------------

    def _seed_guard(self, x):
        """Sanitize bytes entering the cache from OUTSIDE a forward (the
        cache-SEEDING boundary: load_session's npz prefix, the prefix
        arena's blocks). In-engine writes saturate
        (models/transformer._to_cache_dtype), so the flash kernel's
        _f8_bits_to never sees an e4m3 NaN code — but a session file or
        arena did not necessarily come from a saturating producer, and
        one 0x7F byte would decode as a finite 480.0 and poison every
        later attention read (ADVICE r5). Non-f8 dtypes pass through."""
        from ..ops.pallas_attention import saturate_f8_nan_codes

        return saturate_f8_nan_codes(x)

    def _seed_jit(self, fn, *, out_tree, donate: tuple = ()):
        """The ONE jit wrapper for every path that builds cache rows on
        device (Engine.load_session, Engine.slot_seed_prefix) — the
        single home of the PR 3 donation-safety fix:

          * the result is COMPUTED on device (fresh zeros + scatter, or a
            gather from the arena), never a device_put/asarray of a host
            temporary — a computed output cannot alias host staging
            memory, so donating it into the first jitted step is safe
            (wholesale device_put here produced intermittent NaN-poisoned
            logits: use-after-free of the host buffer after donation);
          * out_shardings pins every cache output to the engine's cache
            layout, so sharded meshes materialize the full-seq_len result
            straight into the sharded placement — no device ever holds a
            whole unsharded row (only transient prefix inputs replicate).

        `out_tree` is any pytree matching the output structure (its leaf
        values are ignored — one cache sharding per leaf)."""
        if self._cache_sharding is None:
            return jax.jit(fn, donate_argnums=donate)
        shardings = jax.tree_util.tree_map(lambda _: self._cache_sharding,
                                           out_tree)
        return jax.jit(fn, donate_argnums=donate, out_shardings=shardings)

    def _session_fingerprint(self) -> list[int]:
        # architecture dims + cache shape/dtype + the WEIGHT CONTENT hash:
        # a session saved from a same-shape different-weight model (a
        # fine-tune, a requant) would otherwise resume against a KV cache
        # the loaded weights never produced — garbage continuations with
        # no error (ADVICE r3; the multihost cluster fingerprint guards
        # the same hazard). model_fingerprint == 0 (in-memory params)
        # degrades to the shape-only check.
        import zlib

        sp = self.spec
        return [zlib.crc32(repr((sp.arch, sp.dim, sp.hidden_dim, sp.n_layers,
                                 sp.n_heads, sp.n_kv_heads,
                                 sp.head_size) + (
                                     (sp.cache_head_size,)
                                     if sp.is_mla else ())).encode()),
                self.batch, self.seq_len,
                zlib.crc32(jnp.dtype(self.cache_dtype).name.encode()),
                self.model_fingerprint]

    # -- observability -----------------------------------------------------

    def wire_estimate(self):
        """Modeled per-token per-device collective bytes for this engine's
        mesh/config (the reference's S/R columns, ref: socket.cpp:266-271)."""
        from .netstats import estimate_decode_wire

        return estimate_decode_wire(
            self.spec, self.mesh,
            q80=self.q80_collectives,
            act_bytes=jnp.dtype(self.compute_dtype).itemsize,
            batch=self.batch,
            shard_vocab=self.shard_vocab,
            vocab_topk=self.vocab_topk)

    def measure_transfer_ms(self) -> float:
        """Measured per-token DECODE transfer estimate: times activation-
        sized collectives on the mesh and scales by the exact per-token
        collective count of the decode schedule (the reference's T column,
        measured not modeled). Mirrors the collective structure
        netstats.estimate_decode_wire models: per-layer tp reduces, plus the
        single (ep, tp)-group MoE reduce when experts are ep-placed, plus —
        for pp meshes — the all-stages scheme's per-stage live broadcast
        (pp psums over the pp axis per token, parallel/pp.py pp_layers;
        decode never runs the GPipe ppermute rotation, see
        measure_prefill_transfer_ms for that schedule). Payloads carry the
        batch dimension: a decode-step activation is (B, 1, dim)."""
        return self._segment_reduce_ms(1) + self._segment_pp_ms(1)

    def measure_prefill_transfer_ms(self, n_prompt: int) -> float:
        """Measured transfer estimate for prefilling an n_prompt-token
        prompt, following the schedules forward() actually runs (VERDICT
        r4 #9 — the pp cost is the real per-microbatch ppermute structure,
        not a psum approximation). prefill() feeds the prompt in
        prefill_chunk-sized segments and forward() picks the schedule PER
        SEGMENT, so the estimate sums per-segment costs: a segment where
        gpipe_microbatches(t, pp) returns M > 1 does (M + pp - 2)
        activation hops of (B, t/M, dim) over the pp ring plus ONE final
        output psum of (B, t, dim) (pp_layers_gpipe); shorter segments take
        the all-stages scheme's pp psums of (B, t, dim). tp/ep reduces
        scale with t like the decode model. Returns total ms."""
        if self.mesh is None:
            return 0.0
        # measure once per DISTINCT segment length (at most two: the full
        # chunk and the tail) — the microbench compiles + times real
        # collectives, so a per-segment loop would redo that ~n_chunks
        # times for identical numbers
        n_full, tail = divmod(n_prompt, self.prefill_chunk)
        total = 0.0
        if n_full:
            t = self.prefill_chunk
            total += (self._segment_reduce_ms(t)
                      + self._segment_pp_ms(t)) * n_full
        if tail:
            total += self._segment_reduce_ms(tail) + self._segment_pp_ms(tail)
        return total

    def _segment_reduce_ms(self, t: int) -> float:
        """tp/ep per-layer reduce cost for one T-token forward segment —
        the shared collective structure of the decode and prefill
        estimates (payload (B, T, dim); netstats.estimate_decode_wire
        models the same shape)."""
        from .netstats import measure_allreduce_ms

        if self.mesh is None:
            return 0.0
        tp = self.mesh.shape.get("tp", 1)
        ep = self.mesh.shape.get("ep", 1)
        elems = self.batch * t * self.spec.dim
        total = 0.0
        if self.spec.is_moe and ep > 1:
            if tp > 1:  # attention wo reduce stays tp-only
                total += (measure_allreduce_ms(self.mesh, elems)
                          * self.spec.n_layers)
            total += (measure_allreduce_ms(self.mesh, elems,
                                           axes=("ep", "tp"))
                      * self.spec.n_layers)
        elif tp > 1:
            per = measure_allreduce_ms(self.mesh, elems)
            reduces = (1 + self.spec.n_active_experts) if self.spec.is_moe else 2
            total += per * reduces * self.spec.n_layers
        return total

    def _segment_pp_ms(self, t: int) -> float:
        """pp collective cost for one T-token forward segment, following
        the schedule forward() picks for that length: GPipe microbatch
        rotation (long segments) or the all-stages per-stage psum."""
        from ..parallel.pp import gpipe_microbatches
        from .netstats import measure_allreduce_ms, measure_ppermute_ms

        pp = (self.mesh.shape.get("pp", 1) if self.mesh is not None else 1)
        if pp <= 1:
            return 0.0
        elems = self.batch * t * self.spec.dim
        n_mb = gpipe_microbatches(t, pp) if self.pp_gpipe else 1
        if n_mb > 1:
            hops = n_mb + pp - 2
            return (measure_ppermute_ms(
                self.mesh, self.batch * (t // n_mb) * self.spec.dim) * hops
                + measure_allreduce_ms(self.mesh, elems, axes=("pp",)))
        return measure_allreduce_ms(self.mesh, elems, axes=("pp",)) * pp

    # -- compiled steps ---------------------------------------------------

    def _forward_kwargs(self) -> dict:
        """The engine's forward() configuration, in exactly one place — every
        execution path (compiled steps, the on-device greedy scan) must build
        its kwargs here so a new forward() knob is threaded once."""
        return dict(
            activation_q80=self.activation_q80,
            compute_dtype=self.compute_dtype,
            use_pallas=self.use_pallas,
            tp_mesh=self._tp_mesh,
            tp_reduce=self.tp_reduce,
            pallas_interpret=self.pallas_interpret,
            sp_cache_mesh=self._sp_cache_mesh,
            pp_mesh=self._pp_mesh,
            pp_gpipe=self.pp_gpipe,
            vocab_mesh=self.mesh if self.shard_vocab else None,
            vocab_axes=self._vocab_axes or ("tp",),
        )

    def _compiled_step(self, key, *, sp_mesh=None,
                       with_logit_index: bool = False,
                       logits_for_all: bool = False) -> Callable:
        """One cached jitted forward wrapper for every execution path.

        Three shapes share it: (params, tokens, pos, cache) with pos scalar
        (step) or (B,) vector (batched decode), the same with per-position
        logits (logits_for_all — the speculative verify forward), and
        (params, tokens, logit_index, cache) for whole-segment prefill from
        pos 0 (right-padded batch; ring when sp_mesh is set). Single builder
        so a new forward() knob is threaded exactly once."""
        if key in self._steps:
            return self._steps[key]
        if logits_for_all:
            self.spec.refuse("speculation")

        common = self._forward_kwargs()
        if with_logit_index:
            def run(params, tokens, logit_index, cache):
                return forward(params, self.spec, tokens, jnp.int32(0), cache,
                               sp_mesh=sp_mesh, logit_index=logit_index,
                               **common)
        else:
            def run(params, tokens, pos0, cache):
                return forward(params, self.spec, tokens, pos0, cache,
                               logits_for_all=logits_for_all, **common)

        # role-specific wrapper names so profiler traces can attribute XLA
        # module executions: with every wrapper named 'run', per-step T
        # alignment mis-attributed whenever extra modules ran inside the
        # trace window (ADVICE r3). decode_step is uniquely the 1-token
        # host-loop step the benchmark hints on.
        run.__name__ = (
            "prefill_seg" if with_logit_index
            else "decode_step" if key == 1
            else f"prefill_chunk_{key}" if isinstance(key, int)
            else f"prefill_chunk_{key[1]}" if key[0] == "prefill"
            else "verify_step" if key[0] == "lookup"
            else "batch_decode_step")
        return self._mint(key, jax.jit(run, donate_argnums=(3,)))

    def _step_fn(self, t: int) -> Callable:
        return self._compiled_step(t)

    def step(self, tokens: np.ndarray, pos0: int, *,
             _key=None) -> jax.Array:
        """Run a (B, T) segment from absolute position pos0; returns last-token
        logits (B, vocab) on device. Advances cache/pos.

        _key overrides the compile-cache key — prefill() routes a width-1
        trailing chunk through ("prefill", 1) so its trace module is named
        prefill_chunk_1, not decode_step (the benchmark counts decode
        executions exactly)."""
        b, t = tokens.shape
        assert b == self.batch
        assert pos0 + t <= self.seq_len, "context overflow"
        tok = jnp.asarray(tokens, jnp.int32)
        if self._token_sharding is not None:
            tok = jax.device_put(tok, self._token_sharding)
        logits, self.cache = self._compiled_step(_key if _key is not None
                                                 else t)(
            self.params, tok, jnp.int32(pos0), self.cache)
        self.pos = pos0 + t
        return logits

    def fetch_logits(self, logits: jax.Array) -> np.ndarray:
        """Bring step() logits to the host. On a multi-process mesh the
        array may be sharded over non-addressable devices; replicate first
        (every host then samples the same logits — the protocol's
        lock-step invariant, parallel/multihost.py)."""
        if self._multihost and not logits.is_fully_replicated:
            if self._replicator is None:
                self._replicator = self._mint("replicator", jax.jit(
                    lambda l: l,
                    out_shardings=NamedSharding(self.mesh, P())))
            logits = self._replicator(logits)
        return np.asarray(logits)

    # -- sharded sampling (ops/sharded_vocab.py) ---------------------------

    @property
    def shard_sampling(self) -> bool:
        """Whether sample_view serves the sharded fast path: vocab is
        sharded and the host can fetch the tiny summaries directly
        (multi-process meshes keep the replicated fetch_logits oracle —
        their serving tiers are single-host anyway)."""
        return self.shard_vocab and not self._multihost

    def sample_view(self, logits, temps: np.ndarray | None, n_vocab: int):
        """Sampling access to one step's (B, vocab) logits. Logits of a
        slot step that computed its own summary (an engine without a
        mesh: _summarises) are served from it: ONE small fetch, greedy
        rows BIT-IDENTICAL to np.argmax, sampled rows through the
        candidate scheme at the temperatures the step was dispatched
        with, and the FIRST row that cannot be proven fetches the whole
        array once for all of the step's unproven rows (what every step
        paid before; no dispatch a row). Other logits of a replicated
        engine return a FullLogitsView (the fetch_logits + host-Sampler
        oracle, exactly the pre-sharding path). Vocab-sharded engines
        run the sharded_sample_prep executable — device argmax +
        per-shard top-k candidates — and fetch ~(B, S·k) floats instead
        of (B, vocab): greedy rows are BIT-IDENTICAL to np.argmax,
        sampled rows are distribution-exact (candidate scheme, guarded;
        anything unprovable fetches ONE replicated row through the
        warmed "vrow" executable — the per-row parity oracle).

        temps: (B,) float32 per-row temperatures (greedy rows pass 1.0 —
        a traced input, never a compile key). n_vocab: the tokenizer
        vocab the candidates/argmax truncate at (one compile key per
        distinct value on a sharded vocab; rows whose sampler vocab
        differs fall back)."""
        from ..ops.sharded_vocab import sharded_sample_prep
        from .sampling import (FullLogitsView, ShardedLogitsView,
                               unpack_summary)

        own = self._step_summary
        if own is not None and own[0] is logits and own[3] == n_vocab:
            whole = []

            def fetch_row(row: int) -> np.ndarray:
                if not whole:
                    whole.append(self.fetch_logits(logits))
                return whole[0][row]

            return ShardedLogitsView(
                *unpack_summary(np.asarray(own[1])), int(n_vocab),
                fetch_row, stats=self.vocab_sample_stats, temps=own[2])
        if not self.shard_sampling:
            return FullLogitsView(self.fetch_logits(logits))
        b = logits.shape[0]
        n_shards = 1
        for a in self._vocab_axes:
            n_shards *= self.mesh.shape[a]
        k = max(1, min(self.vocab_topk, self.spec.vocab_size // n_shards))
        key = ("vprep", b, k, int(n_vocab))
        if key not in self._steps:
            mesh, axes = self.mesh, self._vocab_axes

            def run(logits, temps, nv=int(n_vocab), kk=k):
                return sharded_sample_prep(logits, temps, mesh, axes,
                                           nv, kk)

            run.__name__ = "sharded_sample_prep"
            self._mint(key, jax.jit(run))
        if temps is None:
            temps = np.ones((b,), np.float32)
        amax, cand_p, cand_id, guard = self._steps[key](
            logits, jnp.asarray(temps, jnp.float32))
        return ShardedLogitsView(
            np.asarray(amax), np.asarray(cand_p), np.asarray(cand_id),
            np.asarray(guard), int(n_vocab),
            self._row_fetcher(logits), stats=self.vocab_sample_stats)

    def _row_fetcher(self, logits):
        """One replicated (vocab,) row off the sharded logits — the
        sampled path's parity-oracle fallback. A single warmed key per
        batch shape; the row gather is the ONLY place the serving path
        may materialize a full-vocab vector, and only one row at a
        time."""
        key = ("vrow", logits.shape[0])
        if key not in self._steps:
            out_s = (NamedSharding(self.mesh, P()) if self.mesh is not None
                     else None)
            self._mint(key, jax.jit(
                lambda l, i: lax.dynamic_index_in_dim(l, i, 0,
                                                      keepdims=False),
                out_shardings=out_s))
        fn = self._steps[key]

        def fetch(row: int) -> np.ndarray:
            return np.asarray(fn(logits, jnp.int32(row)))

        return fetch

    def warm_sample_ops(self, logits, n_vocab: int) -> None:
        """Run what sampling from one step's logits runs, fallback
        included, against the warmed decode step's — Scheduler.warmup
        calls this so sampled traffic mints ZERO post-warmup keys: the
        sharded-sampling executables (prep + row gather; the vprep key
        set is bounded: one per (batch, k, vocab)), or the whole-array
        fetch behind a step's own summary."""
        view = self.sample_view(logits, None, n_vocab)
        if view.sharded:
            view.row(0)  # the "vrow" executable / the whole fetch

    # -- generation -------------------------------------------------------

    def prefill(self, prompt: list[int]) -> jax.Array:
        """Feed the prompt in fixed-size chunks; returns last logits.

        When the mesh has an sp axis > 1 and this is the start of a session,
        the whole prompt runs as ONE ring-attention segment with the sequence
        sharded over sp (long-context path, net-new vs the reference)."""
        assert self.batch == 1, "prefill() is single-sequence; use step() for batches"
        sp = self.mesh.shape.get(SP_AXIS, 1) if self.mesh is not None else 1
        if (sp > 1 and self._pp == 1 and self.pos == 0 and len(prompt) > 1
                and len(prompt) + (-len(prompt)) % sp <= self.seq_len):
            # (under pp, prefill goes through the GPipe microbatch schedule
            # instead; the sp-sharded cache is written chunk-locally there)
            return self._prefill_ring(prompt, sp)
        logits = None
        i = 0
        n = len(prompt)
        while i < n:
            chunk = min(self.prefill_chunk, n - i)
            seg = np.asarray(prompt[i:i + chunk], np.int32)[None, :]
            logits = self.step(seg, self.pos,
                               _key=("prefill", 1) if chunk == 1 else None)
            i += chunk
        return logits

    def _prefill_ring(self, prompt: list[int], sp: int) -> jax.Array:
        """Whole-prompt sequence-parallel prefill: pad to a multiple of sp,
        shard tokens over the sp axis, attend via ring attention, sample at
        the true last prompt position. Padded positions land in the cache at
        indices >= pos and are therefore never attended by later decode."""
        n = len(prompt)
        pad = (-n) % sp
        t = n + pad
        assert t <= self.seq_len, "context overflow"  # caller checked padding fits

        fn = self._compiled_step(("ring", t), sp_mesh=self.mesh,
                                 with_logit_index=True)
        seg = np.zeros((1, t), np.int32)
        seg[0, :n] = prompt
        tok = jax.device_put(jnp.asarray(seg),
                             NamedSharding(self.mesh, P(DP_AXIS, SP_AXIS)))
        logits, self.cache = fn(self.params, tok, jnp.int32(n - 1), self.cache)
        self.pos = n
        return logits

    def generate(
        self,
        prompt: list[int],
        max_tokens: int,
        sampler: Sampler,
        eos_id: int | set[int] | None = None,
        on_token: Callable[[int], None] | None = None,
    ) -> GenerationResult:
        """Prefill + decode loop (ref: src/apps/dllama/dllama.cpp:14-91).

        eos_id: stop token id, or a set of them (instruct models often end
        turns with a marker token distinct from the header eos).

        max_tokens is a HARD cap on emitted tokens — max_tokens <= 0 emits
        nothing (prefill still advances the cache), exactly like the
        lookup/batch iterator paths (one contract, VERDICT r4 #9)."""
        stop_ids = ({eos_id} if isinstance(eos_id, int) else eos_id) or set()
        stats = RunStats()
        out: list[int] = []

        if max_tokens <= 0:
            self.prefill(prompt)
            return GenerationResult(out, stats)

        t0 = time.perf_counter()
        logits = self.prefill(prompt)
        logits_np = self.fetch_logits(logits)  # the host fetch ends the timed region
        t1 = time.perf_counter()
        stats.add(StepStats(generation_ms=(t1 - t0) * 1e3, device_ms=(t1 - t0) * 1e3))

        token = sampler.sample(logits_np[0])
        out.append(token)
        if on_token:
            on_token(token)

        while len(out) < max_tokens and self.pos < self.seq_len:
            if token in stop_ids:
                break
            g0 = time.perf_counter()
            logits = self.step(np.asarray([[token]], np.int32), self.pos)
            logits_np = self.fetch_logits(logits)
            g1 = time.perf_counter()
            token = sampler.sample(logits_np[0])
            g2 = time.perf_counter()
            stats.add(StepStats(
                generation_ms=(g2 - g0) * 1e3,
                device_ms=(g1 - g0) * 1e3,
                host_ms=(g2 - g1) * 1e3,
            ))
            out.append(token)
            if on_token:
                on_token(token)
        return GenerationResult(out, stats)

    # -- speculative (prompt-lookup) greedy generation --------------------

    def generate_lookup_stream(
        self,
        prompt: list[int],
        max_tokens: int,
        eos_id: int | set[int] | None = None,
        *,
        draft_len: int = 7,
        max_ngram: int = 3,
        history: list[int] | None = None,
        stats: RunStats | None = None,
        vocab_size: int | None = None,
    ) -> Iterator[int]:
        """Token iterator for prompt-lookup speculative decoding
        (runtime/speculative.py): each forward feeds the last emitted token
        PLUS a draft continuation mined from the context's own n-grams and
        emits one token per confirmed position — decode is weight-read-
        bound, so the t = 1 + k verify forward costs ~one token's HBM time
        and every accepted draft token is nearly free. The yielded stream
        is EXACTLY generate()'s greedy stream (drafts only batch the
        confirmation); `last_accept_stats` records (forwards, tokens) and
        updates per forward, so an abandoned iterator leaves it accurate.

        `prompt` is fed from the current self.pos (the API server's prefix
        reuse passes only the suffix); `history` is the full token context
        drafts are mined from (defaults to `prompt`); `vocab_size` caps the
        argmax at the TOKENIZER's vocab like the host Sampler does — a
        padded model head would otherwise emit undecodable ids and break
        the exact-greedy-parity contract. Greedy only: sampled speculation
        needs rejection resampling to stay distribution-exact — the sampled
        paths keep 1 token/forward."""
        from .speculative import count_accepted

        spec_v = min(vocab_size or self.spec.vocab_size,
                     self.spec.vocab_size)

        def first(row: np.ndarray) -> int:
            return int(np.argmax(row[:spec_v]))

        def verify(seg_logits: np.ndarray, draft: list[int]) -> list[int]:
            greedy = np.argmax(seg_logits[:, :spec_v], axis=-1)
            m = count_accepted(draft, greedy)
            return [int(g) for g in greedy[: m + 1]]

        return self._lookup_loop(prompt, max_tokens, eos_id,
                                 draft_len=draft_len, max_ngram=max_ngram,
                                 history=history, stats=stats,
                                 first_fn=first, verify_fn=verify)

    def _lookup_loop(
        self,
        prompt: list[int],
        max_tokens: int,
        eos_id: int | set[int] | None,
        *,
        draft_len: int,
        max_ngram: int,
        history: list[int] | None,
        stats: RunStats | None,
        first_fn: Callable[[np.ndarray], int],
        verify_fn: Callable[[np.ndarray, list[int]], list[int]],
        draft_fn: Callable | None = None,
    ) -> Iterator[int]:
        """The verify-forward skeleton every speculative mode shares —
        draft sizing, the compiled verify step, eos/budget truncation,
        cache-position bookkeeping, accept stats and timing live HERE
        exactly once. Modes differ only in their callbacks:
        first_fn(logits row) -> first token,
        verify_fn(seg_logits (T, V), draft) -> emitted tokens, where
        emitted = the accepted draft prefix plus exactly one more token
        (emitted[i] must be a valid continuation of segment position i —
        its K/V slot holds the fed token stream), and — for REAL-draft
        modes (runtime/draft.py) — draft_fn(hist, k, token, pos0) ->
        draft token list, replacing the default prompt-lookup n-gram
        miner (the draft model owns its KV state inside the closure)."""
        stop_ids = ({eos_id} if isinstance(eos_id, int) else eos_id) or set()

        from .speculative import find_draft

        if max_tokens <= 0:
            # budget-0 emits nothing (prefill still advances the cache) —
            # the same hard-cap contract as Engine.generate() and the API
            # server's plain token iterator at n_gen == 0
            self.prefill(prompt)
            self.last_accept_stats = (1, 0)
            self.last_spec = {"forwards": 1, "drafted": 0, "accepted": 0,
                              "emitted": 0}
            return

        t0 = time.perf_counter()
        logits = self.prefill(prompt)
        logits_np = self.fetch_logits(logits)
        t1 = time.perf_counter()
        if stats is not None:
            stats.add(StepStats(generation_ms=(t1 - t0) * 1e3,
                                device_ms=(t1 - t0) * 1e3))

        token = first_fn(logits_np[0])
        n_out = 1
        self.last_accept_stats = (1, 1)
        # the richer accept record the legacy API tier aggregates into
        # its `spec` /stats block (accepted counts tokens actually USED
        # after eos/budget truncation — the honest numerator)
        self.last_spec = {"forwards": 1, "drafted": 0, "accepted": 0,
                          "emitted": 1}
        hist = np.asarray((history if history is not None else prompt)
                          + [token], np.int32)
        yield token

        while (n_out < max_tokens and self.pos < self.seq_len
               and token not in stop_ids):
            # draft sized to the remaining budget/context (the +1 below is
            # the fed token itself; its K/V write needs a free slot)
            g0 = time.perf_counter()
            k = min(draft_len, self.seq_len - self.pos - 1,
                    max_tokens - n_out - 1)
            pos0 = self.pos
            if draft_fn is not None:
                draft = draft_fn(hist, k, token, pos0) if k > 0 else []
            else:
                draft = (find_draft(hist, k, max_ngram=max_ngram)
                         if k > 0 else [])
            seg = np.asarray([[token] + draft], np.int32)

            # device_ms covers only the verify forward + the logits D2H
            # (like generate()'s step timing); draft mining and the host
            # accept work are host_ms — benchmark 'Avg inference time'
            # would otherwise overstate device time for lookup runs
            # (ADVICE r3)
            d0 = time.perf_counter()
            fn = self._compiled_step(("lookup", seg.shape[1]),
                                     logits_for_all=True)
            tok_dev = jnp.asarray(seg)
            if self._token_sharding is not None:
                tok_dev = jax.device_put(tok_dev, self._token_sharding)
            logits, self.cache = fn(
                self.params, tok_dev, jnp.int32(pos0), self.cache)
            logits_np = self.fetch_logits(logits)
            d1 = time.perf_counter()

            emitted = verify_fn(logits_np[0], draft)
            # stop token: emit it (generate() parity), drop the rest
            for i, t in enumerate(emitted):
                if t in stop_ids:
                    emitted = emitted[: i + 1]
                    break
            emitted = emitted[: max_tokens - n_out]
            # positions pos0..pos0+a hold [token] + the confirmed draft
            # prefix; unconfirmed draft writes beyond that are overwritten
            # position-by-position before any later query attends them
            # (the same invariant decode overruns rely on)
            a = len(emitted) - 1
            self.pos = pos0 + 1 + a
            n_out += len(emitted)
            self.last_accept_stats = (self.last_accept_stats[0] + 1, n_out)
            self.last_spec["forwards"] += 1
            self.last_spec["drafted"] += len(draft)
            self.last_spec["accepted"] += max(a, 0)
            self.last_spec["emitted"] += len(emitted)
            hist = np.concatenate([hist, np.asarray(emitted, np.int32)])
            token = emitted[-1]
            g1 = time.perf_counter()
            if stats is not None:
                stats.add(StepStats(generation_ms=(g1 - g0) * 1e3,
                                    device_ms=(d1 - d0) * 1e3,
                                    host_ms=(g1 - g0 - (d1 - d0)) * 1e3))
            for t in emitted:
                yield t

    def generate_lookup(
        self,
        prompt: list[int],
        max_tokens: int,
        eos_id: int | set[int] | None = None,
        *,
        draft_len: int = 7,
        max_ngram: int = 3,
        on_token: Callable[[int], None] | None = None,
        vocab_size: int | None = None,
        history: list[int] | None = None,
    ) -> GenerationResult:
        """Collecting wrapper over generate_lookup_stream (the CLI path)."""
        stats = RunStats()
        out: list[int] = []
        for t in self.generate_lookup_stream(prompt, max_tokens, eos_id,
                                             draft_len=draft_len,
                                             max_ngram=max_ngram,
                                             stats=stats,
                                             vocab_size=vocab_size,
                                             history=history):
            out.append(t)
            if on_token:
                on_token(t)
        return GenerationResult(out, stats)

    def generate_lookup_sampled(
        self,
        prompt: list[int],
        max_tokens: int,
        *,
        temperature: float,
        topp: float,
        seed: int,
        eos_id: int | set[int] | None = None,
        draft_len: int = 7,
        max_ngram: int = 3,
        on_token: Callable[[int], None] | None = None,
        vocab_size: int | None = None,
        history: list[int] | None = None,
    ) -> GenerationResult:
        """Speculative decoding at temperature > 0 via rejection
        resampling (VERDICT r3 weak #5) — a SEPARATE mode from the
        parity-exact greedy stream: every emitted token is distributed
        exactly as the host Sampler's draw on the same logits
        (speculative.target_dist materializes that distribution;
        speculative.accept_or_resample is marginal-exact), but the RNG
        stream differs (acceptance consumes a data-dependent number of
        uniforms, so xorshift coin parity with Sampler is impossible by
        construction — numpy PCG64 seeded from `seed` instead).

        Drafts are point masses (prompt-lookup mines the context, there is
        no draft model), so accept(token d) = p(d) and the residual is p
        with d removed, renormalized. One verify forward confirms
        accepted-prefix + 1 tokens exactly like the greedy path; the
        accept RATE is content- and temperature-dependent (peaked
        distributions on repetitive text accept most drafts).
        `last_accept_stats` updates per forward like the greedy mode."""
        stats = RunStats()
        out: list[int] = []
        for t in self.generate_lookup_sampled_stream(
                prompt, max_tokens, temperature=temperature, topp=topp,
                seed=seed, eos_id=eos_id, draft_len=draft_len,
                max_ngram=max_ngram, vocab_size=vocab_size,
                history=history, stats=stats):
            out.append(t)
            if on_token:
                on_token(t)
        return GenerationResult(out, stats)

    def generate_lookup_sampled_stream(
        self,
        prompt: list[int],
        max_tokens: int,
        *,
        temperature: float,
        topp: float,
        seed: int,
        eos_id: int | set[int] | None = None,
        draft_len: int = 7,
        max_ngram: int = 3,
        vocab_size: int | None = None,
        history: list[int] | None = None,
        stats: RunStats | None = None,
    ) -> Iterator[int]:
        """Token-iterator form of generate_lookup_sampled — the shape the
        API server streams from (mirrors generate_lookup_stream's greedy
        iterator; the K/V bookkeeping contract is identical, so a consumer
        appends emitted tokens to its history as they arrive). The stream
        is deterministic in (seed, logits, drafts): replicated multihost
        processes that derive the same seed (Sampler.next_seed) draw the
        same uniforms, accept the same widths, and keep their collectives
        in lock-step."""
        from .speculative import accept_or_resample, draw, target_dist

        assert temperature > 0, "temperature 0 is the parity-exact greedy mode"
        spec_v = min(vocab_size or self.spec.vocab_size,
                     self.spec.vocab_size)
        rng = np.random.default_rng(seed)

        def first(row: np.ndarray) -> int:
            return draw(target_dist(row, temperature, topp, spec_v),
                        rng.random())

        def verify(seg_logits: np.ndarray, draft: list[int]) -> list[int]:
            # position i's logits condition on [token] + draft[:i]; accept
            # draft[i] with prob p_i(draft[i]), resample the residual on
            # the first reject; a fully-accepted draft earns a bonus draw
            # from the last position (a "free" token, exactly like the
            # greedy path's final argmax)
            emitted: list[int] = []
            for i, d in enumerate(draft):
                p_i = target_dist(seg_logits[i], temperature, topp, spec_v)
                ok, t = accept_or_resample(p_i, int(d), rng.random(),
                                           rng.random())
                emitted.append(t)
                if not ok:
                    return emitted
            p_k = target_dist(seg_logits[len(draft)], temperature, topp,
                              spec_v)
            emitted.append(draw(p_k, rng.random()))
            return emitted

        return self._lookup_loop(prompt, max_tokens, eos_id,
                                 draft_len=draft_len, max_ngram=max_ngram,
                                 history=history, stats=stats,
                                 first_fn=first, verify_fn=verify)

    # -- real-draft speculative generation (runtime/draft.py) -------------

    def _draft_catchup(self, draft, state: dict, hist: np.ndarray,
                       target_pos: int) -> None:
        """Bring a draft's KV cache frontier up to ``target_pos`` by
        prefilling the token stream it missed (hist[i] is the token at
        absolute position i). Chunks pad to ONE fixed width (pad writes
        land beyond the real frontier and are overwritten before the
        draft attends them — the engine-wide overrun invariant), so
        catch-up adds no compile keys however ragged the gaps are. Gaps
        happen at start (the whole prompt) and whenever a round skipped
        drafting (k == 0 at a budget edge)."""
        c = min(self.prefill_chunk, self.seq_len)
        target = min(int(target_pos), len(hist))
        while state["pos"] < target:
            dp = state["pos"]
            n = min(c, target - dp)
            tok = np.zeros((self.batch, c), np.int32)
            tok[0, :n] = hist[dp:dp + n]
            pos = np.full((self.batch,), self.seq_len, np.int32)
            pos[0] = dp
            state["cache"] = draft.prefill_chunk(state["cache"], tok, pos)
            state["pos"] = dp + n

    def generate_draft_stream(
        self,
        prompt: list[int],
        max_tokens: int,
        eos_id: int | set[int] | None = None,
        *,
        draft,
        draft_len: int = 7,
        history: list[int] | None = None,
        stats: RunStats | None = None,
        vocab_size: int | None = None,
    ) -> Iterator[int]:
        """Greedy REAL-draft speculative decoding (runtime/draft.py): the
        draft model (`DraftModel` — the target's own truncated-depth
        prefix, or a separate draft .m) proposes k tokens in ONE
        dispatched scan, the verify forward confirms accepted-prefix + 1
        exactly like the lookup path, and the emitted stream is EXACTLY
        generate()'s greedy stream — drafts only batch the confirmation,
        on ANY text (prompt lookup needs repetitive text to propose at
        all). The draft keeps its own d-layer KV cache inside this
        stream's closure, walking the same absolute positions as the
        target; rejected draft positions are overwritten by the next
        round's feed (the engine-wide overrun invariant), and a stale
        draft cache can only lower the accept rate, never change a
        token. `last_accept_stats` updates per forward like the lookup
        modes. batch must be 1 (the scheduler owns the batched path)."""
        assert self.batch == 1, "use the scheduler for batched drafting"
        from .speculative import count_accepted

        spec_v = min(vocab_size or self.spec.vocab_size,
                     self.spec.vocab_size)
        state = {"cache": draft.new_cache(), "pos": 0}

        def first(row: np.ndarray) -> int:
            return int(np.argmax(row[:spec_v]))

        def verify(seg_logits: np.ndarray, dr: list[int]) -> list[int]:
            greedy = np.argmax(seg_logits[:, :spec_v], axis=-1)
            m = count_accepted(dr, greedy)
            return [int(g) for g in greedy[: m + 1]]

        def draft_fn(hist, k, token, pos0):
            self._draft_catchup(draft, state, hist, pos0)
            # always scan the FULL draft_len (one compile key) and
            # truncate to k: the extra steps are d/L-cheap and their
            # writes sit beyond the frontier. state["pos"] may then
            # exceed the VERIFIED frontier past a rejection — safe
            # HERE because every next round's scan re-feeds
            # contiguously from the new pos0, overwriting each stale
            # position before its own query attends it (the scheduler
            # path must clamp instead: plain rounds can interleave
            # there — Scheduler._decode_spec)
            toks, state["cache"] = draft.propose(
                state["cache"], np.asarray([token], np.int32),
                np.asarray([pos0], np.int32), draft_len, n_vocab=spec_v)
            state["pos"] = pos0 + draft_len
            return [int(t) for t in toks[0][:k]]

        return self._lookup_loop(prompt, max_tokens, eos_id,
                                 draft_len=draft_len, max_ngram=0,
                                 history=history, stats=stats,
                                 first_fn=first, verify_fn=verify,
                                 draft_fn=draft_fn)

    def generate_draft(
        self,
        prompt: list[int],
        max_tokens: int,
        eos_id: int | set[int] | None = None,
        *,
        draft,
        draft_len: int = 7,
        on_token: Callable[[int], None] | None = None,
        vocab_size: int | None = None,
        history: list[int] | None = None,
    ) -> GenerationResult:
        """Collecting wrapper over generate_draft_stream (the CLI path)."""
        stats = RunStats()
        out: list[int] = []
        for t in self.generate_draft_stream(prompt, max_tokens, eos_id,
                                            draft=draft,
                                            draft_len=draft_len,
                                            stats=stats,
                                            vocab_size=vocab_size,
                                            history=history):
            out.append(t)
            if on_token:
                on_token(t)
        return GenerationResult(out, stats)

    def generate_draft_sampled_stream(
        self,
        prompt: list[int],
        max_tokens: int,
        *,
        draft,
        temperature: float,
        topp: float,
        seed: int,
        eos_id: int | set[int] | None = None,
        draft_len: int = 7,
        vocab_size: int | None = None,
        history: list[int] | None = None,
        stats: RunStats | None = None,
    ) -> Iterator[int]:
        """Sampled REAL-draft speculation via GENERAL rejection
        resampling (speculative.accept_or_resample_q): the draft SAMPLES
        each proposal from its own temperature/top-p distribution q (a
        real, non-point-mass proposal — unlike prompt-lookup's onehot
        drafts), and the target accepts with min(1, p/q), resampling the
        normalized residual max(p - q, 0) on the first reject. Every
        emitted token is distributed exactly as a host-Sampler draw on
        the same logits; the RNG stream is a derived numpy PCG64 like
        the sampled lookup mode (coin parity with the plain path is
        impossible by construction). The draft loop here is host-paced
        (one d-layer forward per proposal — sampling is data-dependent,
        so it cannot fuse into the greedy scan); the greedy mode is the
        latency headline."""
        from .speculative import (accept_or_resample_q, draw, target_dist)

        assert self.batch == 1, "use the scheduler for batched drafting"
        assert temperature > 0, "temperature 0 is the parity-exact greedy mode"
        spec_v = min(vocab_size or self.spec.vocab_size,
                     self.spec.vocab_size)
        rng = np.random.default_rng(seed)
        state = {"cache": draft.new_cache(), "pos": 0, "q": []}

        def first(row: np.ndarray) -> int:
            return draw(target_dist(row, temperature, topp, spec_v),
                        rng.random())

        def draft_fn(hist, k, token, pos0):
            self._draft_catchup(draft, state, hist, pos0)
            toks: list[int] = []
            qs: list[np.ndarray] = []
            cur, p, cache = int(token), int(pos0), state["cache"]
            for _ in range(k):
                lg, cache = draft.step_logits(
                    cache, np.asarray([[cur]], np.int32),
                    np.asarray([p], np.int32))
                qd = target_dist(lg[0], temperature, topp, spec_v)
                cur = draw(qd, rng.random())
                toks.append(cur)
                qs.append(qd)
                p += 1
            state["cache"], state["pos"], state["q"] = cache, p, qs
            return toks

        def verify(seg_logits: np.ndarray, dr: list[int]) -> list[int]:
            emitted: list[int] = []
            for i, d in enumerate(dr):
                p_i = target_dist(seg_logits[i], temperature, topp, spec_v)
                ok, t = accept_or_resample_q(p_i, state["q"][i], int(d),
                                             rng.random(), rng.random())
                emitted.append(t)
                if not ok:
                    return emitted
            p_k = target_dist(seg_logits[len(dr)], temperature, topp,
                              spec_v)
            emitted.append(draw(p_k, rng.random()))
            return emitted

        return self._lookup_loop(prompt, max_tokens, eos_id,
                                 draft_len=draft_len, max_ngram=0,
                                 history=history, stats=stats,
                                 first_fn=first, verify_fn=verify,
                                 draft_fn=draft_fn)

    def generate_draft_sampled(
        self,
        prompt: list[int],
        max_tokens: int,
        *,
        draft,
        temperature: float,
        topp: float,
        seed: int,
        eos_id: int | set[int] | None = None,
        draft_len: int = 7,
        on_token: Callable[[int], None] | None = None,
        vocab_size: int | None = None,
        history: list[int] | None = None,
    ) -> GenerationResult:
        """Collecting wrapper over generate_draft_sampled_stream."""
        stats = RunStats()
        out: list[int] = []
        for t in self.generate_draft_sampled_stream(
                prompt, max_tokens, draft=draft, temperature=temperature,
                topp=topp, seed=seed, eos_id=eos_id, draft_len=draft_len,
                vocab_size=vocab_size, history=history, stats=stats):
            out.append(t)
            if on_token:
                on_token(t)
        return GenerationResult(out, stats)

    # -- continuous-batching slot steps (runtime/scheduler.py) ------------

    @property
    def _counts_experts(self) -> bool:
        """Whether the two slot step programs count the experts they read
        (forward's expert_counts): a model with experts whose layers are
        traced in forward's own loop. Any other model's programs are
        compiled with the argument at its default, exactly as before."""
        return (self.spec.is_moe and self._pp_mesh is None
                and not self._multihost)

    def _note_expert_counts(self, program: str, counts=None) -> None:
        """Keep a slot step program's (expert_reads, expert_pairs,
        expert_tiles) on the device, its copy to the host started, until
        take_expert_counts."""
        if counts is not None:
            counts.copy_to_host_async()
            self._expert_counts.append((program, counts))

    def take_expert_counts(self) -> list[tuple[str, int, ...]]:
        """(program, expert_reads, expert_pairs, expert_tiles) of the slot
        step programs dispatched since the last call that HAVE RUN, oldest
        first (no tiles from a program whose token rows fit one row tile,
        forward's expert_counts); one
        still running, and those behind it, wait for the next call, so this
        never blocks. After the scheduler has fetched a step's logits every
        program dispatched before the fetch has run and its counts' copy,
        started at dispatch, has landed: no round trip of their own. A
        stretch of mid-prompt chunks fetches nothing, so the scheduler asks
        after each of them too, and the counters trail the step counts by
        the programs in flight, not by the stretch."""
        n = 0
        while (n < len(self._expert_counts)
               and self._expert_counts[n][1].is_ready()):
            n += 1
        taken = self._expert_counts[:n]
        del self._expert_counts[:n]
        return [(program, *map(int, np.asarray(c))) for program, c in taken]

    @property
    def prefill_rows_per_slot(self) -> int:
        """Most rows of one chunk program that may be consecutive segments
        of ONE slot (the scheduler chains them through `slots`): `batch`
        where the program's rows follow a slot map
        (models/transformer.takes_slot_map is the rule: no mesh, no latent
        cache, and every state layer of a kind whose mixer hands a row's
        final state and tail to the row that continues it: SSM, not DELTA).
        Else 1: the chunk program takes no map and row r is slot r. From
        the layer kinds and the mesh, once, at boot: both step programs of
        an engine that takes no map are what they were."""
        return self.batch if self._chunk_slot_map else 1

    def attn_grid_steps(self, t: int) -> int:
        """Grid steps `flash_attention` takes in ONE step program of `t`
        tokens a row: the kernel's own grid (ops/pallas_attention.flash_grid,
        which the call itself runs: rows x head tiles x sequence blocks;
        one shard's rows and heads under a dp / tp mesh) times the layers
        that attend a K/V cache. Static: whatever the rows' positions.
        0 where the program holds no such kernel (the XLA path, the latent
        or the sp-sharded cache, a chunk too wide for it). What the
        scheduler adds to /stats attn_grid_steps_* at a dispatch."""
        from ..ops.pallas_attention import flash_grid, flash_supported

        spec = self.spec
        b, h, kvh = self.batch, spec.n_heads, spec.n_kv_heads
        if (not self.use_pallas or spec.is_mla
                or self._sp_cache_mesh is not None
                or not flash_supported(t, h, kvh)):
            return 0
        if self._tp_mesh is not None:
            dp = self._tp_mesh.shape.get(DP_AXIS, 1)
            tp = self._tp_mesh.shape.get(TP_AXIS, 1)
            b, h, kvh = b // dp if b % dp == 0 else b, h // tp, kvh // tp
        layers = sum(k == LayerKind.ATTENTION for k in spec.layer_kinds)
        return layers * math.prod(flash_grid(
            b, t, h, kvh, self.seq_len, spec.head_size, self.cache_dtype,
            self.compute_dtype))

    @property
    def _summarises(self) -> bool:
        """Whether the two slot step programs end with the sampling
        summary of their own logits (ops/sharded_vocab.step_summary):
        an engine without a mesh. A vocab-sharded engine keeps its
        separate prep executable, any other mesh the replicated fetch."""
        return self.mesh is None

    def _sample_operands(self, temps, n_vocab) -> tuple:
        """The summary's ONE traced operand of a slot step program (none
        where the engine does not summarise), as
        ops/sharded_vocab.step_summary takes it: the (B,) temperatures,
        the tokenizer's vocabulary (the model's unless given) and whether
        this step computes its summary at all: a caller that gives no
        temperatures samples no row of the step (a mid-prompt chunk, the
        benchmark's check), and the step skips the summary's device work
        and its fetch. Never a compile key: the check's call, the
        warm-up's and the scheduler's enter one executable."""
        if not self._summarises:
            return ()
        sample = np.ones((self.batch + 2,), np.float32)
        if temps is not None:
            sample[:self.batch] = temps
        sample[self.batch:] = (n_vocab or self.spec.vocab_size,
                               temps is not None)
        return (sample,)

    def _with_summary(self, out: tuple, sample: tuple) -> tuple:
        """The last lines of a slot step program: forward's outputs and,
        where the engine summarises, the packed sampling summary of the
        logits (the program's LAST output; the logits stay an output and
        stay on the device)."""
        if not sample:
            return out
        from ..ops.sharded_vocab import step_summary

        return (*out, step_summary(out[0], *sample))

    def _note_summary(self, logits, rest: list, sample: tuple) -> None:
        """Keep the step's summary beside its logits for sample_view, its
        copy to the host started (one small leaf, one transfer); none of
        a step that skipped it."""
        if sample:
            packed = rest.pop()
            self._step_summary = None
            operand, = sample
            if operand[-1]:
                packed.copy_to_host_async()
                self._step_summary = (logits, packed, operand[:-2],
                                      int(operand[-2]))

    def slot_prefill_chunk(self, tokens: np.ndarray, pos: np.ndarray,
                           logit_index: np.ndarray,
                           slots: np.ndarray | None = None, *,
                           temps: np.ndarray | None = None,
                           n_vocab: int | None = None) -> jax.Array:
        """One chunked-prefill forward over the batched cache: row r writes
        its (B, C) chunk's K/V at absolute offsets pos[r]..pos[r]+C-1 via
        the per-row write path, without disturbing any other row. Rows
        not prefilling this call are GATED OFF by passing pos[r] ==
        seq_len: their write positions land out of bounds and are dropped
        (models/transformer._scatter_cache_write: the in-place
        kv_cache_write kernel with the kernels on, the drop-mode scatter
        otherwise), so a gated row's cache — mid-decode or idle — is
        untouched.
        Returns (B, vocab) logits read at per-row `logit_index` within the
        chunk (only rows finishing their prompt this chunk are consumed;
        the scheduler skips the D2H fetch entirely for mid-prompt chunks).

        slots (B,), where prefill_rows_per_slot allows it: program row r
        reads and writes cache slot slots[r] at pos[r], so several rows may
        be consecutive segments of one slot (row r attends what rows before
        it wrote in this program). The caller owes what
        ops/pallas_kv_write.py's docstring lists: chained rows start on
        multiples of C, and every row that is not live is gated and names a
        slot no live row names. None is the identity, row r == slot r, and
        enters the SAME program as an arange: one chunk executable an
        engine, with or without chaining.

        temps (B,) float32 and n_vocab, where the engine summarises
        (_summarises): the temperature a program row's candidates are
        computed at (greedy and gated rows pass 1.0) and the tokenizer's
        vocabulary; both traced. The summary stays inside the engine
        (sample_view finds it by these logits); without temps no row of
        the step will be sampled and the step computes none.

        The chunk width C is the ONLY compilation key
        (slot_prefill_chunk_C): the scheduler pads every tail chunk to a
        fixed C, so admission order/prompt lengths never mint new
        executables (the fixed-compilation-key discipline dlgrind DLG204
        pins). Does NOT touch self.pos — per-slot positions are owned by
        the scheduler."""
        from .faults import FAULTS

        FAULTS.fire("prefill_raise")  # injection point: host-side, before
        # any dispatch — arming it never alters the jitted program
        b, c = tokens.shape
        assert b == self.batch, (b, self.batch)
        mapped = self._chunk_slot_map
        assert mapped or slots is None, "this engine's chunk takes no map"
        key = ("slot_prefill", c)
        if key not in self._steps:
            common = dict(self._forward_kwargs(),
                          expert_counts=self._counts_experts)

            def run(params, tokens, pos0, logit_index, cache, *rest):
                slots, sample = ((rest[0], rest[1:]) if mapped
                                 else (None, rest))
                return self._with_summary(
                    forward(params, self.spec, tokens, pos0, cache,
                            logit_index=logit_index, **common, slots=slots),
                    sample)

            run.__name__ = f"slot_prefill_chunk_{c}"
            self._mint(key, jax.jit(run, donate_argnums=(4,)))
        tok = jnp.asarray(tokens, jnp.int32)
        posv = jnp.asarray(pos, jnp.int32)
        if self._token_sharding is not None:
            tok = jax.device_put(tok, self._token_sharding)
            posv = jax.device_put(posv,
                                  NamedSharding(self.mesh, P(DP_AXIS)))
        the_map = ()
        if mapped:
            if self._identity_map is None:    # on the device once, not a call
                self._identity_map = jnp.asarray(np.arange(b, dtype=np.int32))
            the_map = (self._identity_map if slots is None
                       else jnp.asarray(slots, jnp.int32),)
        sample = self._sample_operands(temps, n_vocab)
        logits, self.cache, *rest = self._steps[key](
            self.params, tok, posv, jnp.asarray(logit_index, jnp.int32),
            self.cache, *the_map, *sample)
        self._note_summary(logits, rest, sample)
        self._note_expert_counts("prefill", *rest)
        return logits

    def slot_decode_step(self, tokens: np.ndarray, pos: np.ndarray, *,
                         temps: np.ndarray | None = None,
                         n_vocab: int | None = None) -> jax.Array:
        """One decode step for the slot scheduler: row r feeds tokens[r]
        at its own absolute position pos[r] (per-row write, in place in
        the donated cache). Rows without a decode token this step pass pos[r]
        == seq_len — their write drops out of bounds and their logits row
        is ignored. temps and n_vocab: as slot_prefill_chunk's. One
        compilation key total ("slot_decode"); self.pos is
        untouched (per-slot positions are the scheduler's)."""
        b, t = tokens.shape
        assert b == self.batch and t == 1, (tokens.shape, self.batch)
        key = "slot_decode"
        if key not in self._steps:
            common = dict(self._forward_kwargs(),
                          expert_counts=self._counts_experts)

            def run(params, tokens, pos0, cache, *sample):
                return self._with_summary(
                    forward(params, self.spec, tokens, pos0, cache,
                            **common), sample)

            run.__name__ = "slot_decode_step"
            self._mint(key, jax.jit(run, donate_argnums=(3,)))
        tok = jnp.asarray(tokens, jnp.int32)
        posv = jnp.asarray(pos, jnp.int32)
        if self._token_sharding is not None:
            tok = jax.device_put(tok, self._token_sharding)
            posv = jax.device_put(posv,
                                  NamedSharding(self.mesh, P(DP_AXIS)))
        sample = self._sample_operands(temps, n_vocab)
        logits, self.cache, *rest = self._steps[key](
            self.params, tok, posv, self.cache, *sample)
        self._note_summary(logits, rest, sample)
        self._note_expert_counts("decode", *rest)
        return logits

    def slot_verify_step(self, tokens: np.ndarray, pos: np.ndarray,
                         n_vocab: int) -> tuple[np.ndarray, np.ndarray]:
        """One FIXED-WIDTH speculative verify step for the slot
        scheduler: row r feeds its (1 + K) segment [last token, draft...]
        at absolute positions pos[r]..pos[r]+K (the generate_batch_lookup
        padding trick as a slot executable — rows without a draft pad
        with their own token, gated rows pass pos[r] == seq_len and every
        write drops). Returns (greedy (B, 1+K) int32 — the target's
        argmax AFTER each segment position, computed ON DEVICE over the
        tokenizer vocab, and the position-0 logits (B, vocab) as a DEVICE
        array — what a plain slot_decode_step would have returned, so
        non-speculating rows ride the same forward and sample normally
        through Engine.sample_view).

        The width 1 + K and n_vocab are the ONLY compile keys
        ("slot_verify"): the scheduler always pads to its configured
        draft_len, so speculative serving mints exactly one verify
        executable, warmed by Scheduler.warmup() — the bounded-key
        discipline --freeze-compiles enforces. Unconfirmed draft writes
        beyond each row's accepted prefix are overwritten before any
        later query attends them (the engine-wide overrun invariant).
        self.pos untouched (per-slot positions are the scheduler's)."""
        from .draft import batched_verify

        b, t = tokens.shape
        assert b == self.batch, (b, self.batch)
        self.spec.refuse("speculation")
        key = ("slot_verify", t, int(n_vocab))
        if key not in self._steps:
            common = self._forward_kwargs()
            spec = self.spec

            def run(params, tok, pos, cache, nv=int(n_vocab)):
                return batched_verify(params, spec, tok, pos, cache,
                                      n_vocab=nv, fwd_kwargs=common)

            run.__name__ = f"slot_verify_{t}"
            self._mint(key, jax.jit(run, donate_argnums=(3,)))
        tok = jnp.asarray(tokens, jnp.int32)
        posv = jnp.asarray(pos, jnp.int32)
        if self._token_sharding is not None:
            tok = jax.device_put(tok, self._token_sharding)
            posv = jax.device_put(posv,
                                  NamedSharding(self.mesh, P(DP_AXIS)))
        greedy, logits0, self.cache = self._steps[key](
            self.params, tok, posv, self.cache)
        # logits0 stays ON DEVICE: the scheduler wraps it in a sample
        # view (Engine.sample_view), so vocab-sharded engines never
        # fetch the (B, vocab) array — non-speculating rows sample from
        # the sharded candidates like any decode step
        return np.asarray(greedy), logits0

    # -- prefix-cache arena steps (runtime/prefix_cache.py) ---------------

    def new_prefix_arena(self, num_blocks: int, block_len: int):
        """Allocate the radix prefix cache's block arena: K and V arrays
        of (num_blocks, layers, kv_heads, block_len, head_size) in the
        cache dtype. Computed on device (jitted zeros — donation-safe by
        the _seed_jit discipline, though the arena itself is NEVER
        donated into a forward: blocks are immutable once published and
        shared across requests). The arena dies with the engine — a
        supervisor rebuild mints a fresh engine, a fresh arena, and an
        empty tree (runtime/resilience.EngineSupervisor._make_sched)."""
        assert self._pp == 1, "prefix cache does not support --pp"
        assert num_blocks >= 1 and 1 <= block_len <= self.seq_len
        lead = (num_blocks, self.spec.n_layers, self.spec.n_kv_heads,
                block_len)
        # a cache without V leaves keeps its V arena, zero wide: every
        # caller hands the pair on, and it holds no byte
        shape = lead + (self.spec.cache_head_size,)
        shape_v = lead + (self.spec.cache_v_head_size,)
        if flat_arena(self.spec):
            shape, shape_v = (
                (num_blocks, sh[1] * sh[2], block_len * sh[4])
                for sh in (shape, shape_v))
        dt = self.cache_dtype
        key = ("prefix_arena", shape)
        if key not in self._steps:
            self._mint(key, jax.jit(
                lambda: (jnp.zeros(shape, dt), jnp.zeros(shape_v, dt))))
        return self._steps[key]()

    def _arena_block_len(self, arena_k) -> int:
        if arena_k.ndim == 3:       # flat blocks: flat_arena()
            return arena_k.shape[2] // self.spec.cache_head_size
        return arena_k.shape[3]

    def slot_seed_prefix(self, arena_k, arena_v, row: int,
                         block_ids: np.ndarray) -> None:
        """Seed slot row `row`'s leading cache positions from arena
        blocks (on-device block-gather -> cache row write; the cache is
        donated and updated in place). `block_ids` is the fixed-width
        (seq_len // block_len,) vector — the scheduler pads it with
        block 0, so this is ONE compilation key total ("slot_seed"),
        fingerprinted in analysis/baseline.json like the other two
        serving executables. See seed_rows_from_blocks for the padding
        invariant and the f8 seeding guard; _seed_jit for the
        donation-safety/out_shardings discipline. Does not touch
        self.pos (per-slot positions are the scheduler's)."""
        mb, bl = block_ids.shape[0], self._arena_block_len(arena_k)
        key = ("slot_seed", mb, bl)
        if key not in self._steps:
            run = seed_rows_from_blocks
            self._mint(key, self._seed_jit(run, out_tree=self.cache,
                                           donate=(0,)))
        self.cache = self._steps[key](
            self.cache, arena_k, arena_v, jnp.int32(row),
            jnp.asarray(block_ids, jnp.int32))

    def slot_publish_block(self, arena_k, arena_v, row: int, offset: int,
                           dst: int):
        """Copy slot row `row`'s filled cache positions
        [offset, offset + block_len) into arena block `dst` and return
        the updated (arena_k, arena_v). The arenas are donated (in-place
        block write); the cache is only read. One compilation key total
        (row/offset/dst are traced scalars), so publishing never mints
        executables however requests finish. The copied bytes came from
        this engine's own saturating cache writes — the NaN-code guard
        runs on the SEED side, where the producer cannot be trusted."""
        bl = self._arena_block_len(arena_k)
        key = ("slot_publish", bl)
        if key not in self._steps:
            def run(arena_k, arena_v, cache, row, off, dst):
                z = jnp.int32(0)
                outs = []
                for arena, leaves in ((arena_k, cache.k), (arena_v, cache.v)):
                    if not leaves:      # no V leaf: the zero-wide V arena
                        outs.append(arena)
                        continue
                    kvh, hs = leaves[0].shape[1], leaves[0].shape[3]
                    blk = jnp.stack([
                        lax.dynamic_slice(leaf, (row, z, off, z),
                                          (1, kvh, bl, hs))[0]
                        for leaf in leaves])        # (L, KVH, bl, hs)
                    outs.append(lax.dynamic_update_slice(
                        arena, blk.reshape((1,) + arena.shape[1:]),
                        (dst,) + (z,) * (arena.ndim - 1)))
                return tuple(outs)

            run.__name__ = "slot_publish_block"
            self._mint(key, jax.jit(run, donate_argnums=(0, 1)))
        return self._steps[key](arena_k, arena_v, self.cache,
                                jnp.int32(row), jnp.int32(offset),
                                jnp.int32(dst))

    # -- cross-replica KV block transfer (runtime/kv_transfer.py) ---------

    def block_export(self, arena_k, arena_v, src: int):
        """Gather arena block ``src`` as a device (L, KVH, bl, hs) K/V
        pair for host export. One compilation key per block length
        ("block_export" — src is a traced scalar), minted through the
        compile ledger like every serving executable and warmed by
        ``PrefixCache.warmup`` when transfer is enabled, so donor
        serving mints ZERO post-warmup keys."""
        bl = self._arena_block_len(arena_k)
        key = ("block_export", bl)
        if key not in self._steps:
            run = export_arena_block
            if arena_k.ndim == 3:
                lead = (self.spec.n_layers, self.spec.n_kv_heads, bl)

                def run(arena_k, arena_v, src):
                    return tuple(
                        blk.reshape(lead + (blk.shape[1] // bl,))
                        for blk in export_arena_block(arena_k, arena_v, src))

            self._mint(key, jax.jit(run))
        return self._steps[key](arena_k, arena_v, jnp.int32(src))

    def slot_import_block(self, arena_k, arena_v, k_blk, v_blk, dst: int):
        """Write one fetched host block pair into arena slot ``dst`` and
        return the updated (arena_k, arena_v) — the importer half of the
        transfer plane. Arenas donated; one compilation key per block
        length ("block_import"). See import_arena_block for why the
        bytes land raw (the seed-side f8 guard owns trust)."""
        key = ("block_import", self._arena_block_len(arena_k))
        if key not in self._steps:
            self._mint(key, jax.jit(import_arena_block,
                                    donate_argnums=(0, 1)))
        return self._steps[key](arena_k, arena_v,
                                jnp.asarray(k_blk, self.cache_dtype),
                                jnp.asarray(v_blk, self.cache_dtype),
                                jnp.int32(dst))

    # -- batched speculative (prompt-lookup) greedy generation ------------

    def generate_batch_lookup(
        self,
        prompts: list[list[int]],
        max_tokens: int,
        eos_id: int | set[int] | None = None,
        *,
        draft_len: int = 7,
        max_ngram: int = 3,
        vocab_size: int | None = None,
        histories: list[list[int]] | None = None,
        stop_flags: np.ndarray | None = None,
    ) -> list[list[int]]:
        """Batched prompt-lookup speculative decoding (VERDICT r4 #7):
        every row mines its own draft from its own history each step, the
        drafts RIGHT-PAD to the widest live draft (padding feeds the row's
        current token again — its writes land beyond the accepted prefix
        and are overwritten like any unconfirmed draft), and ONE verify
        forward of (B, 1 + k_max) confirms each row's accepted prefix + 1.
        Emitted streams are EXACTLY the per-row greedy streams (argmax
        verify — same contract as generate_lookup_stream), so decode stays
        weight-read-bound: b rows x multi-token accepts amortize one
        weight read per forward.

        Greedy only, single host loop. Returns one token list per row
        (stop token included — generate() parity). `last_accept_stats`
        holds (verify_forwards, total_tokens) summed over live rows.
        `histories[i]` (defaults to prompts[i]) seeds row i's draft-mining
        context, like the single-row stream's `history`. `stop_flags` rows
        set True BEFORE the call never emit (the API server pads sub-batch
        requests up to the engine's fixed batch with such rows); unlike
        generate_batch_stream's live flags, they are read once at start —
        text-level stops apply post-hoc on the collected rows."""
        from .speculative import count_accepted, find_draft

        b = len(prompts)
        assert b == self.batch, (b, self.batch)
        assert all(prompts), "empty prompt"
        stop_ids = ({eos_id} if isinstance(eos_id, int) else eos_id) or set()
        spec_v = min(vocab_size or self.spec.vocab_size,
                     self.spec.vocab_size)
        lens = np.asarray([len(p) for p in prompts], np.int32)
        t = int(lens.max())
        assert t < self.seq_len, "context overflow"

        # greedy argmax ON DEVICE: the verify loop only consumes argmaxes,
        # and fetching the full (B, T, V) logits per forward is ~8 MB of
        # D2H per forward; (B, T) int32 is ~256 B
        amax_key = ("bl_amax", spec_v)
        if amax_key not in self._steps:
            self._mint(amax_key, jax.jit(
                lambda l: jnp.argmax(
                    l[..., :spec_v].astype(jnp.float32), axis=-1
                ).astype(jnp.int32)))
        amax = self._steps[amax_key]

        # whole-batch right-padded prefill (same path as generate_batch)
        pre_fn = self._compiled_step(("bpre", t), with_logit_index=True)
        padded = np.zeros((b, t), np.int32)
        for i, p in enumerate(prompts):
            padded[i, : len(p)] = p
        tok = jnp.asarray(padded)
        if self._token_sharding is not None:
            tok = jax.device_put(tok, self._token_sharding)
        logits, self.cache = pre_fn(
            self.params, tok, jnp.asarray(lens - 1), self.cache)
        if max_tokens <= 0:  # hard-cap contract, same as generate()
            self.pos = int(lens.max())
            self.last_accept_stats = (1, 0)
            return [[] for _ in range(b)]
        first_np = np.asarray(amax(logits))  # (B,)

        out: list[list[int]] = [[] for _ in range(b)]
        hists: list[np.ndarray] = []
        cur = np.zeros(b, np.int32)
        done = (np.asarray(stop_flags, bool).copy() if stop_flags is not None
                else np.zeros(b, bool))
        pos = lens.copy()
        for i in range(b):
            cur[i] = int(first_np[i])
            hists.append(np.asarray(
                (histories[i] if histories is not None else prompts[i])
                + [int(first_np[i])], np.int32))
            if done[i]:
                continue  # pre-retired padding row: never emits
            tok_i = int(first_np[i])
            out[i].append(tok_i)
            if tok_i in stop_ids:
                done[i] = True
        self.pos = int(pos.max())
        n_forwards = 1
        # stats are valid even if the loop below never runs (budget 1, or
        # every row's first token is a stop token)
        self.last_accept_stats = (n_forwards, sum(len(o) for o in out))

        def alive(i: int) -> bool:
            return (not done[i] and len(out[i]) < max_tokens
                    and pos[i] < self.seq_len)

        while any(alive(i) for i in range(b)):
            drafts: list[list[int]] = []
            for i in range(b):
                if alive(i):
                    k = min(draft_len, self.seq_len - pos[i] - 1,
                            max_tokens - len(out[i]) - 1)
                    drafts.append(find_draft(hists[i], k,
                                             max_ngram=max_ngram)
                                  if k > 0 else [])
                else:
                    drafts.append([])
            k_max = max(len(d) for d in drafts)

            # rows feed [cur] + draft, padded to 1 + k_max with cur (the
            # padding's K/V writes sit beyond the accepted prefix and are
            # overwritten before any later query attends them; rows at the
            # context edge rely on the cache write dropping OOB positions)
            seg = np.empty((b, 1 + k_max), np.int32)
            for i, d in enumerate(drafts):
                seg[i, 0] = cur[i]
                seg[i, 1: 1 + len(d)] = d
                seg[i, 1 + len(d):] = cur[i]

            fn = self._compiled_step(("blookup", 1 + k_max),
                                     logits_for_all=True)
            tok_dev = jnp.asarray(seg)
            posv = jnp.asarray(np.minimum(pos, self.seq_len - 1))
            if self._token_sharding is not None:
                tok_dev = jax.device_put(tok_dev, self._token_sharding)
                posv = jax.device_put(
                    posv, NamedSharding(self.mesh, P(DP_AXIS)))
            logits, self.cache = fn(self.params, tok_dev, posv, self.cache)
            greedy_np = np.asarray(amax(logits))  # (B, 1+k_max)
            n_forwards += 1

            for i in range(b):
                if not alive(i):
                    continue
                greedy = greedy_np[i]
                m = count_accepted(drafts[i], greedy)
                emitted = [int(g) for g in greedy[: m + 1]]
                for j, tk in enumerate(emitted):
                    if tk in stop_ids:
                        emitted = emitted[: j + 1]
                        done[i] = True
                        break
                emitted = emitted[: max_tokens - len(out[i])]
                pos[i] += len(emitted)  # 1 + accepted
                out[i].extend(emitted)
                cur[i] = emitted[-1]
                hists[i] = np.concatenate(
                    [hists[i], np.asarray(emitted, np.int32)])
            self.pos = int(np.minimum(pos, self.seq_len).max())
            self.last_accept_stats = (n_forwards, sum(len(o) for o in out))
        return out

    # -- batched generation (dp path) -------------------------------------

    def generate_batch(
        self,
        prompts: list[list[int]],
        max_tokens: int,
        sampler: Sampler,
        eos_id: int | set[int] | None = None,
    ) -> list[list[int]]:
        """Generate for `batch` independent sequences at once (right-padded
        prompts, per-sequence positions/eos). Net-new vs the reference's
        batch=1 engine (SURVEY.md §2.5 DP row); with a dp mesh the batch
        shards over dp. Greedy results match `batch` independent runs.

        Returns one token list per sequence; a row that hits its stop
        token includes it as the final entry (generate() parity — the
        stream below documents the same contract)."""
        out: list[list[int]] = [[] for _ in prompts]
        for step_toks in self.generate_batch_stream(prompts, max_tokens,
                                                    sampler, eos_id):
            for i, t in enumerate(step_toks):
                if t is not None:
                    out[i].append(t)
        return out

    def generate_batch_stream(
        self,
        prompts: list[list[int]],
        max_tokens: int,
        sampler: Sampler,
        eos_id: int | set[int] | None = None,
        stop_flags: np.ndarray | None = None,
    ) -> Iterator[list[int | None]]:
        """Step-level iterator form of generate_batch — the shape the API
        server's batch endpoint streams from. Each yield is one decode
        step's tokens: b entries, the row's newly sampled token (a stop
        token is included, then the row stops — generate() parity) or None
        for rows that are done/past budget. max_tokens is a hard cap like
        generate()'s: max_tokens <= 0 prefills but samples/emits nothing
        (no coins leave the shared sampler stream).

        `stop_flags` is an optional (b,) bool array OWNED BY THE CALLER:
        setting stop_flags[i] = True between steps retires row i — the API
        server's stop-sequence/marker scan happens on decoded TEXT, which
        the engine cannot see. A retired row yields None and stops stepping
        (its sampler-coin slot also frees, like an eos row's). Rows flagged
        BEFORE the first step never sample at all — the server pads
        sub-batch requests up to the engine's fixed batch with such rows,
        and they draw no coins from the shared sampler stream."""
        b = len(prompts)
        assert b == self.batch, (b, self.batch)
        assert all(prompts), "empty prompt"
        stop_ids = ({eos_id} if isinstance(eos_id, int) else eos_id) or set()
        lens = np.asarray([len(p) for p in prompts], np.int32)
        t = int(lens.max())
        assert t < self.seq_len, "context overflow"

        # whole-batch right-padded prefill; logits read at each row's last
        # real token. Padded slots write garbage K/V at positions >= len(p),
        # but those cache slots are overwritten by decode before any later
        # query position can attend to them (attention masks k_pos <= q_pos).
        pre_fn = self._compiled_step(("bpre", t), with_logit_index=True)
        vec_fn = self._compiled_step(("bvec", 1))

        padded = np.zeros((b, t), np.int32)
        for i, p in enumerate(prompts):
            padded[i, : len(p)] = p
        tok = jnp.asarray(padded)
        if self._token_sharding is not None:
            tok = jax.device_put(tok, self._token_sharding)
        logits, self.cache = pre_fn(
            self.params, tok, jnp.asarray(lens - 1), self.cache)
        if max_tokens <= 0:  # hard-cap contract, same as generate(); no
            self.pos = int(lens.max())  # D2H fetch for discarded logits
            return

        n_out = np.zeros(b, np.int64)
        done = np.zeros(b, bool)
        # one host-sampler call per step, in row order for live rows —
        # the shared xorshift stream's coins are drawn token-for-token
        # identical to per-row sample() calls. On vocab-sharded engines
        # the view serves greedy rows from the device argmax
        # (bit-identical) and sampled rows from the candidate scheme
        # (distribution-exact) instead of fetching (B, vocab) logits.
        # (Batched-numpy sampling was built and measured SLOWER than the
        # row loop in every branch — the negative result and the actual
        # large-dp answer, --device-sampling, are recorded in
        # sample_batch's docstring; VERDICT r3 weak #7.)
        temps = np.full((b,), sampler.temperature if sampler.temperature
                        else 1.0, np.float32)
        n_vocab = int(sampler.vocab_size)

        def sample_rows(lg, mask: np.ndarray) -> np.ndarray:
            view = self.sample_view(lg, temps, n_vocab)
            out = np.full(b, -1, np.int64)
            for i in np.nonzero(mask)[0]:
                out[i] = view.sample(sampler, int(i))
            return out

        live0 = (np.ones(b, bool) if stop_flags is None
                 else ~np.asarray(stop_flags, bool))
        cur = sample_rows(logits, live0).astype(np.int32)
        # sample_batch marks unselected rows -1; a pre-retired (padding)
        # row's token is still FED to the embedding gather every step, so
        # clamp it to a real id rather than lean on XLA's out-of-bounds
        # gather clamping (an implicit dependency otherwise)
        cur = np.where(live0, cur, 0).astype(np.int32)
        for i in range(b):
            if live0[i]:
                n_out[i] = 1
                if int(cur[i]) in stop_ids:
                    done[i] = True
        pos = lens.copy()  # next write position per row
        self.pos = int(pos.max())
        yield [int(c) if live0[i] else None for i, c in enumerate(cur)]

        def alive(i: int) -> bool:
            # a row generates while unstopped (model eos OR caller
            # stop_flags), under budget, and with a free cache slot
            # (pos < seq_len — generate()'s overflow guard, per row)
            if stop_flags is not None and stop_flags[i]:
                return False
            return (not done[i] and n_out[i] < max_tokens
                    and pos[i] < self.seq_len)

        while any(alive(i) for i in range(b)):
            tokv = jnp.asarray(cur[:, None])
            # exhausted rows clamp their (ignored) write to the last slot so
            # the write stays in bounds; their outputs stopped already
            posv = jnp.asarray(np.minimum(pos, self.seq_len - 1))
            if self._token_sharding is not None:
                tokv = jax.device_put(tokv, self._token_sharding)
                posv = jax.device_put(
                    posv, NamedSharding(self.mesh, P(DP_AXIS)))
            logits, self.cache = vec_fn(
                self.params, tokv, posv, self.cache)
            alive_mask = np.asarray([alive(i) for i in range(b)])
            nxt = sample_rows(logits, alive_mask)
            step: list[int | None] = [None] * b
            for i in np.nonzero(alive_mask)[0]:
                step[i] = int(nxt[i])
                n_out[i] += 1
                cur[i] = nxt[i]
                if int(nxt[i]) in stop_ids:
                    done[i] = True  # like generate(): stop token included,
                    # then the row stops
            pos = pos + 1
            self.pos = int(np.minimum(pos, self.seq_len).max())
            yield step

    # -- on-device SAMPLED decode loop ------------------------------------

    def generate_device(
        self,
        prompt: list[int],
        max_tokens: int,
        *,
        temperature: float,
        topp: float,
        seed: int,
        eos_id: int | set[int] | None = None,
        vocab_size: int | None = None,
    ) -> list[int]:
        """Sampled generation with the whole decode loop on device: one
        lax.while_loop whose body samples (temperature/top-p, reference
        xorshift* stream — ops/device_sampler.py) and steps the model, with
        no host round-trip per token. Net-new vs the reference, whose
        sampler is CPU-bound per token (ref: src/tokenizer.cpp:231-364).

        Matches generate()+Sampler semantics step for step (device CDFs
        accumulate in f32 vs the host's float64 — a neighboring-token pick
        is possible only within f32 epsilon of a CDF boundary). The loop
        exits ON DEVICE at the first stop token — an eos at step 3 of a
        512-token budget pays 3 forwards, not 512 — and, like generate(),
        never runs the forward for the last emitted token (no overrun cache
        writes, no rewind). batch == 1.

        vocab_size: sample only over the first vocab_size logits (the host
        Sampler likewise truncates to the TOKENIZER's vocab, which can be
        smaller than the model head — sampler.py:69)."""
        assert self.batch == 1, "generate_device is single-sequence"
        from ..ops.device_sampler import sample_token, state_from_seed

        stop_ids = ({eos_id} if isinstance(eos_id, int) else eos_id) or set()
        n_vocab = min(vocab_size or self.spec.vocab_size,
                      self.spec.vocab_size)
        logits = self.prefill(prompt)
        if max_tokens <= 0:  # hard-cap contract, same as generate()
            self.last_device_steps = 0
            return []
        # every stepped token is followed by its forward's cache write at
        # pos, so writes stay < seq_len; the final token is never stepped
        # (see below), so the loop can emit at the exact context edge
        max_tokens = min(max_tokens, self.seq_len - self.pos + 1)

        spec = self.spec
        key = ("dsample", max_tokens, float(temperature), float(topp),
               n_vocab, tuple(sorted(stop_ids)))
        if key not in self._steps:
            common = self._forward_kwargs()
            stop_arr = jnp.asarray(sorted(stop_ids), jnp.int32)

            @partial(jax.jit, donate_argnums=(3,))
            def run(params, logits0, pos0, cache, rng):
                buf0 = jnp.full((max_tokens,), -1, jnp.int32)

                def cond(carry):
                    _, _, _, _, _, i, stop = carry
                    return jnp.logical_and(~stop, i < max_tokens)

                def body(carry):
                    lgt, pos, cache, rng, buf, i, _ = carry
                    tok, rng = sample_token(lgt[0, :n_vocab], rng,
                                            temperature, topp)
                    buf = buf.at[i].set(tok)
                    stop = (jnp.any(tok == stop_arr) if stop_ids
                            else jnp.bool_(False))
                    # generate() parity: the last emitted token — stop or
                    # budget edge — is never stepped, so skip its forward
                    # (this is the early exit: eos at step k costs k
                    # forwards, not max_tokens)
                    skip = jnp.logical_or(stop, i == max_tokens - 1)
                    lgt, cache = lax.cond(
                        skip,
                        lambda cache: (lgt, cache),
                        lambda cache: forward(params, spec, tok[None, None],
                                              pos, cache, **common),
                        cache)
                    return (lgt, pos + 1, cache, rng, buf, i + 1, stop)

                (_, _, cache, _, buf, n, _) = lax.while_loop(
                    cond, body,
                    (logits0, pos0, cache, rng, buf0, jnp.int32(0),
                     jnp.bool_(False)))
                return buf, n, cache

            self._mint(key, run)

        toks, n, self.cache = self._steps[key](
            self.params, logits, jnp.int32(self.pos), self.cache,
            state_from_seed(seed))
        n = int(n)  # D2H is also the sync point
        # observability: device while-loop iterations this call (== sampled
        # tokens; forwards executed = n - 1) — proves the early exit ran
        self.last_device_steps = n
        out = [int(t) for t in np.asarray(toks[:n]).tolist()]
        # host-parity position: generate() never steps (so never writes) the
        # last emitted token — pos advances by the n - 1 forwards that ran
        self.pos += max(n - 1, 0)
        return out

    def generate_batch_device(
        self,
        prompts: list[list[int]],
        max_tokens: int,
        *,
        temperature: float,
        topp: float,
        seed: int,
        eos_id: int | set[int] | None = None,
        vocab_size: int | None = None,
    ) -> list[list[int]]:
        """Batched sampled generation with the whole decode loop on device:
        `batch` independent sequences, each with its OWN xorshift* stream —
        row i is seeded `seed + i`, so its tokens match a single-sequence
        generate_device run of that prompt with seed + i (greedy AND
        sampled; distinct per-row streams mean dp rows serving the SAME
        prompt still sample distinct continuations at temperature > 0,
        while the host generate_batch instead interleaves one shared
        sampler stream across rows). Composes with dp meshes: the batch and
        every per-row carry shard over dp. Removes generate_batch's
        per-row host sampling loop (the reference has no batching at all —
        SURVEY.md §2.5 DP row).

        Per-row early exit: a row stops at its stop token (recorded, like
        generate()) or when its cache fills; the device loop exits when
        every row is done. (One edge divergence from generate_device: at the
        exact context boundary the single-sequence path can emit one final
        unstepped token, this path — like the host generate_batch — ends
        the row.)"""
        from ..ops.device_sampler import sample_token, state_from_seed

        b = len(prompts)
        assert b == self.batch, (b, self.batch)
        assert all(prompts), "empty prompt"
        stop_ids = ({eos_id} if isinstance(eos_id, int) else eos_id) or set()
        n_vocab = min(vocab_size or self.spec.vocab_size,
                      self.spec.vocab_size)
        lens = np.asarray([len(p) for p in prompts], np.int32)
        t = int(lens.max())
        assert t < self.seq_len, "context overflow"

        # whole-batch right-padded prefill (same path as generate_batch)
        pre_fn = self._compiled_step(("bpre", t), with_logit_index=True)
        padded = np.zeros((b, t), np.int32)
        for i, p in enumerate(prompts):
            padded[i, : len(p)] = p
        tok = jnp.asarray(padded)
        if self._token_sharding is not None:
            tok = jax.device_put(tok, self._token_sharding)
        logits, self.cache = pre_fn(
            self.params, tok, jnp.asarray(lens - 1), self.cache)
        if max_tokens <= 0:  # hard-cap contract, same as generate()
            self.pos = int(lens.max())
            self.last_device_steps = 0
            return [[] for _ in range(b)]

        spec = self.spec
        seq_len = self.seq_len
        key = ("bdsample", max_tokens, float(temperature), float(topp),
               n_vocab, tuple(sorted(stop_ids)))
        if key not in self._steps:
            common = self._forward_kwargs()
            stop_arr = jnp.asarray(sorted(stop_ids), jnp.int32)
            sample_rows = jax.vmap(
                lambda lgt, st: sample_token(lgt, st, temperature, topp))

            @partial(jax.jit, donate_argnums=(3,))
            def run(params, logits0, pos0, cache, rng0):
                buf0 = jnp.full((b, max_tokens), -1, jnp.int32)
                feed0 = jnp.zeros((b,), jnp.int32)

                def cond(carry):
                    _, _, _, _, _, _, i, done = carry
                    return jnp.logical_and(i < max_tokens,
                                           jnp.any(~done))

                def body(carry):
                    lgt, pos, cache, rng, buf, feed, i, done = carry
                    # a full cache ends the row like the host loop's
                    # pos < seq_len guard (generate_batch)
                    done = jnp.logical_or(done, pos >= seq_len)
                    toks, rng_new = sample_rows(lgt[:, :n_vocab], rng)
                    record = ~done
                    buf = buf.at[:, i].set(jnp.where(record, toks, -1))
                    rng = jnp.where(record[:, None], rng_new, rng)
                    if stop_ids:
                        stopped = jnp.any(
                            toks[:, None] == stop_arr[None, :], axis=-1)
                        done = jnp.logical_or(done, record & stopped)
                    # done rows keep feeding their last token; their cache
                    # writes land at fresh (or dropped-OOB) slots no output
                    # depends on
                    feed = jnp.where(record, toks, feed)
                    lgt, cache = forward(params, spec, feed[:, None], pos,
                                         cache, **common)
                    return (lgt, pos + 1, cache, rng, buf, feed, i + 1, done)

                (_, _, cache, _, buf, _, n, _) = lax.while_loop(
                    cond, body,
                    (logits0, pos0, cache, rng0, buf0, feed0,
                     jnp.int32(0), jnp.zeros((b,), bool)))
                return buf, n, cache

            self._mint(key, run)

        posv = jnp.asarray(lens)
        rng0 = jnp.stack([state_from_seed(seed + i) for i in range(b)])
        if self._token_sharding is not None:
            posv = jax.device_put(posv,
                                  NamedSharding(self.mesh, P(DP_AXIS)))
        buf, n, self.cache = self._steps[key](
            self.params, logits, posv, self.cache, rng0)
        buf_np = np.asarray(buf)  # D2H is also the sync point
        # fetch the step-count scalar ONCE; the second int(n) this replaced
        # was a redundant device round-trip per call (dlgrind DLG107)
        n_steps = int(n)
        self.last_device_steps = n_steps
        out: list[list[int]] = []
        for i in range(b):
            row = buf_np[i]
            out.append([int(x) for x in row[row >= 0]])
        self.pos = int(min(lens.max() + n_steps, self.seq_len))
        return out
