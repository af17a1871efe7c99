"""Audited entry points: the public jitted programs, built with tiny
abstract-friendly inputs.

One registry shared by the jaxpr audit (`python -m
distributed_llama_tpu.analysis`) and the test suite (tests/conftest.py
exposes `build_forward_inputs` so tests/test_hlo_wire.py lowers the SAME
programs the audit walks — the wire model, the HLO counter, and the static
analyzer all look at one set of entry points).

Inputs are tiny concrete zero-weight models (dim 64, 2 layers): tracing
never reads values, only shapes/dtypes, and building zeros is cheaper and
simpler than threading ShapeDtypeStructs through the params pytree. No XLA
compilation happens here — `jax.make_jaxpr` stops at the jaxpr.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class EntryPoint:
    name: str
    fn: Callable          # positional-args callable to trace
    args: tuple           # example inputs (tiny, concrete)
    meta: dict            # activation_elems: full (B*T*dim) activation size
    needs_mesh: int = 1   # device count required (skip if unavailable)


def _tiny_spec(arch="LLAMA", **overrides):
    from ..models import ArchType, HiddenAct, ModelSpec

    base = dict(
        arch=getattr(ArchType, arch), dim=64, hidden_dim=128, n_layers=2,
        n_heads=4, n_kv_heads=2, vocab_size=128, seq_len=32,
        hidden_act=HiddenAct.SILU, rope_theta=10000.0,
    )
    if arch in ("MIXTRAL", "GROK1"):
        base.update(n_experts=4, n_active_experts=2)
    base.update(overrides)
    return ModelSpec(**base)


def _zero_params(spec, dtype=jnp.float32):
    from ..models.params import load_params, random_tensors

    # random_tensors builds the full HostTensor plan; zeros would do, but
    # the plan builder is the one source of truth for tensor shapes
    host = random_tensors(spec, seed=0, scale=0.0)
    return load_params(spec, host, mode="dense", dtype=dtype)


def build_forward_inputs(spec=None, *, batch: int = 1, t: int = 1,
                         seq_len: int | None = None, dtype=jnp.float32,
                         arch: str = "LLAMA"):
    """(spec, params, tokens, pos0, cache) for a forward() call — the shared
    builder tests/test_hlo_wire.py and the jaxpr audit both trace through.
    """
    from ..models.transformer import KVCache

    if spec is None:
        spec = _tiny_spec(arch)
    params = _zero_params(spec, dtype)
    cache = KVCache.create(spec, batch=batch,
                           seq_len=seq_len or spec.seq_len, dtype=dtype)
    tokens = jnp.zeros((batch, t), jnp.int32)
    return spec, params, tokens, jnp.int32(0), cache


def entry_points(max_devices: int | None = None,
                 ) -> tuple[list[EntryPoint], list[tuple[str, int]]]:
    """The audited programs: (buildable entries, unavailable ones).

    Mesh-dependent entries can only be BUILT when enough devices exist
    (the CI/lint environment pins 8 virtual CPU devices via XLA_FLAGS,
    same as tests/conftest.py); the ones that cannot are still DECLARED in
    the second list as (name, devices_needed) so the audit can fail loudly
    instead of passing vacuously on a short mesh."""
    from ..models.transformer import forward

    n_dev = jax.device_count() if max_devices is None else max_devices
    out: list[EntryPoint] = []
    unavailable: list[tuple[str, int]] = []
    if n_dev < 2:
        unavailable += [("tp_q80_col", 2), ("tp_exact_col", 2),
                        ("tp_row", 2)]
    if n_dev < 4:
        unavailable += [("ep_moe_decode", 4)]

    # -- decode step (single token, donated cache in the engine) ----------
    spec, params, tok, pos0, cache = build_forward_inputs(t=1)

    def decode_step(params, tok, pos0, cache):
        return forward(params, spec, tok, pos0, cache,
                       compute_dtype=jnp.float32)

    out.append(EntryPoint(
        "decode_step", decode_step, (params, tok, pos0, cache),
        {"activation_elems": 1 * 1 * spec.dim, "dim": spec.dim}))

    # -- prefill segment (T tokens, logit_index like the engine's bpre) ---
    spec_p, params_p, tok_p, pos0_p, cache_p = build_forward_inputs(t=8)

    def prefill(params, tok, logit_index, cache):
        return forward(params, spec_p, tok, jnp.int32(0), cache,
                       logit_index=logit_index, compute_dtype=jnp.float32)

    out.append(EntryPoint(
        "prefill", prefill, (params_p, tok_p, jnp.asarray([7]), cache_p),
        {"activation_elems": 1 * 8 * spec_p.dim, "dim": spec_p.dim}))

    # -- continuous-batching scheduler hot path (runtime/scheduler.py) ----
    # slot_decode_step: (B, 1) tokens at per-row positions (the scatter
    # cache-write path: traced with the kernels off, as every entry point
    # here is — on the chip ops/pallas_kv_write.kv_cache_write takes this
    # write, bit-equal by tests/test_pallas_kv_write.py); gated rows pass
    # pos == seq_len. Any host callback
    # or f64 traced into this program stalls EVERY serving step — the
    # audit is the CI gate the scheduler rides on. Both slot programs of
    # an engine without a mesh end with the sampling summary of their own
    # logits (ops/sharded_vocab.step_summary; Engine._with_summary): one
    # traced operand more (the temperatures, the vocabulary, whether to
    # compute it), one small leaf out.
    from ..ops.sharded_vocab import step_summary

    spec_s, params_s, tok_s, _, cache_s = build_forward_inputs(batch=4, t=1)
    pos_s = jnp.zeros((4,), jnp.int32)
    sample_s = (np.ones((4 + 2,), np.float32),)

    def summarised(outs, sample):
        return (*outs, step_summary(outs[0], sample))

    def slot_decode_step(params, tok, pos, cache, *sample):
        return summarised(forward(params, spec_s, tok, pos, cache,
                                  compute_dtype=jnp.float32), *sample)

    out.append(EntryPoint(
        "slot_decode_step", slot_decode_step,
        (params_s, tok_s, pos_s, cache_s, *sample_s),
        {"activation_elems": 4 * 1 * spec_s.dim, "dim": spec_s.dim}))

    # slot_prefill_chunk: (B, C) chunk at per-row offsets with per-row
    # logit_index (C is the engine's only prefill compilation key — tail
    # chunks pad to C, so this ONE signature covers the whole prefill path)
    spec_c, params_c, tok_c, _, cache_c = build_forward_inputs(batch=4, t=8)
    pos_c = jnp.zeros((4,), jnp.int32)
    lidx_c = jnp.full((4,), 7, jnp.int32)

    def slot_prefill_chunk(params, tok, pos, logit_index, cache, *sample):
        return summarised(
            forward(params, spec_c, tok, pos, cache,
                    logit_index=logit_index, compute_dtype=jnp.float32),
            *sample)

    out.append(EntryPoint(
        "slot_prefill_chunk", slot_prefill_chunk,
        (params_c, tok_c, pos_c, lidx_c, cache_c, *sample_s),
        {"activation_elems": 4 * 8 * spec_c.dim, "dim": spec_c.dim}))

    # slot_prefill_chunk_mapped: the same chunk with the slot map, as an
    # engine whose scheduler chains rows mints it (every layer a dense K/V
    # cache, no mesh: Engine.prefill_rows_per_slot): row r reads and writes
    # cache slot slots[r]. A program of its own beside the map-less chunk,
    # which the other engines keep running unchanged.
    slots_c = jnp.arange(4, dtype=jnp.int32)

    def slot_prefill_chunk_mapped(params, tok, pos, logit_index, cache,
                                  slots, *sample):
        return summarised(
            forward(params, spec_c, tok, pos, cache,
                    logit_index=logit_index, compute_dtype=jnp.float32,
                    slots=slots), *sample)

    out.append(EntryPoint(
        "slot_prefill_chunk_mapped", slot_prefill_chunk_mapped,
        (params_c, tok_c, pos_c, lidx_c, cache_c, slots_c, *sample_s),
        {"activation_elems": 4 * 8 * spec_c.dim, "dim": spec_c.dim}))

    # slot_prefill_chunk_state_mapped: the chunk with the slot map over
    # state layers whose mixer chains (models/transformer.takes_slot_map:
    # SSM; a tiny GRANITE_HYBRID): a row that continues the row before it
    # starts from that row's final state and convolution tail inside the
    # program (the XLA twin's row scan here; ssd_chunk on the chip).
    from ..testing import tiny_granite_spec

    spec_g, params_g, _, _, cache_g = build_forward_inputs(
        tiny_granite_spec(seq_len=32), batch=4, t=8)

    def slot_prefill_chunk_state_mapped(params, tok, pos, logit_index, cache,
                                        slots, *sample):
        return summarised(
            forward(params, spec_g, tok, pos, cache,
                    logit_index=logit_index, compute_dtype=jnp.float32,
                    slots=slots), *sample)

    out.append(EntryPoint(
        "slot_prefill_chunk_state_mapped", slot_prefill_chunk_state_mapped,
        (params_g, tok_c, pos_c, lidx_c, cache_g, slots_c, *sample_s),
        {"activation_elems": 4 * 8 * spec_g.dim, "dim": spec_g.dim}))

    # slot_seed_prefix: the radix prefix cache's admission-time seeding
    # (runtime/prefix_cache.py) — an on-device arena-block gather written
    # as a slot row's leading cache positions. Traced through the SAME
    # module-level body the engine jits (engine.seed_rows_from_blocks),
    # so the pinned fingerprint covers the real serving seed path: a
    # drifting block_ids dtype or arity here would retrace per admission.
    from ..runtime.engine import seed_rows_from_blocks

    spec_x, _, _, _, cache_x = build_forward_inputs(batch=4, t=1)
    bl_x = 8
    mb_x = spec_x.seq_len // bl_x
    arena_shape = (4, spec_x.n_layers, spec_x.n_kv_heads, bl_x,
                   spec_x.head_size)
    arena_k = jnp.zeros(arena_shape, jnp.float32)
    arena_v = jnp.zeros(arena_shape, jnp.float32)
    ids_x = jnp.zeros((mb_x,), jnp.int32)

    def slot_seed_prefix(cache, arena_k, arena_v, row, block_ids):
        return seed_rows_from_blocks(cache, arena_k, arena_v, row,
                                     block_ids)

    out.append(EntryPoint(
        "slot_seed_prefix", slot_seed_prefix,
        (cache_x, arena_k, arena_v, jnp.int32(0), ids_x),
        {"activation_elems": mb_x * bl_x * spec_x.n_kv_heads
         * spec_x.head_size, "dim": spec_x.dim}))

    # block_export / block_import: the cross-replica KV transfer plane's
    # two arena executables (runtime/kv_transfer.py) — traced through
    # the SAME module-level bodies the engine jits
    # (engine.export_arena_block / import_arena_block), so the pinned
    # fingerprints cover the real donor/importer paths: a drifting
    # block-index dtype here would retrace per transferred block.
    from ..runtime.engine import export_arena_block, import_arena_block

    def block_export(arena_k, arena_v, src):
        return export_arena_block(arena_k, arena_v, src)

    out.append(EntryPoint(
        "block_export", block_export,
        (arena_k, arena_v, jnp.int32(0)),
        {"activation_elems": bl_x * spec_x.n_kv_heads * spec_x.head_size,
         "dim": spec_x.dim}))

    blk_k = jnp.zeros(arena_shape[1:], jnp.float32)
    blk_v = jnp.zeros(arena_shape[1:], jnp.float32)

    def block_import(arena_k, arena_v, k_blk, v_blk, dst):
        return import_arena_block(arena_k, arena_v, k_blk, v_blk, dst)

    out.append(EntryPoint(
        "block_import", block_import,
        (arena_k, arena_v, blk_k, blk_v, jnp.int32(0)),
        {"activation_elems": bl_x * spec_x.n_kv_heads * spec_x.head_size,
         "dim": spec_x.dim}))

    # -- speculative-decoding serving executables (runtime/draft.py) ------
    # draft_forward: the k-step greedy draft scan (truncated-depth spec —
    # n_layers 1 of the tiny 2 mirrors the self-draft slice). Traced
    # through the SAME module-level body the engine jits
    # (draft.draft_scan_tokens), so the pinned fingerprint covers the
    # real per-slot draft path; a drifting pos dtype here would retrace
    # per proposal and stall every speculative iteration.
    from ..runtime.draft import batched_verify, draft_scan_tokens

    import dataclasses as _dc

    spec_d = _dc.replace(_tiny_spec(), n_layers=1)
    params_d = _zero_params(spec_d)
    from ..models.transformer import KVCache as _KVC

    cache_d = _KVC.create(spec_d, batch=4, seq_len=spec_d.seq_len,
                          dtype=jnp.float32)
    tok_d = jnp.zeros((4, 1), jnp.int32)
    pos_d = jnp.zeros((4,), jnp.int32)

    def draft_forward(params, tok0, pos, cache):
        return draft_scan_tokens(params, spec_d, tok0, pos, cache, k=2,
                                 n_vocab=spec_d.vocab_size,
                                 fwd_kwargs=dict(
                                     compute_dtype=jnp.float32))

    out.append(EntryPoint(
        "draft_forward", draft_forward, (params_d, tok_d, pos_d, cache_d),
        {"activation_elems": 4 * 1 * spec_d.dim, "dim": spec_d.dim}))

    # slot_verify: the fixed-width (B, 1+K) verify forward with on-device
    # argmax — the scheduler's one speculative target executable
    # (Engine.slot_verify_step jits the same draft.batched_verify body)
    spec_v, params_v, tok_v, _, cache_v = build_forward_inputs(batch=4,
                                                               t=3)
    pos_v = jnp.zeros((4,), jnp.int32)

    def slot_verify(params, tok, pos, cache):
        return batched_verify(params, spec_v, tok, pos, cache,
                              n_vocab=spec_v.vocab_size,
                              fwd_kwargs=dict(compute_dtype=jnp.float32))

    out.append(EntryPoint(
        "slot_verify", slot_verify, (params_v, tok_v, pos_v, cache_v),
        {"activation_elems": 4 * 3 * spec_v.dim, "dim": spec_v.dim}))

    if n_dev < 2:
        unavailable += [("embed_tokens_sharded", 2),
                        ("sharded_sample_prep", 2)]

    if n_dev >= 2:
        from ..parallel import make_mesh
        from ..parallel.tp_q80 import tp_col_matmul, tp_row_matmul

        mesh = make_mesh(tp=2, dp=1)
        dim, hidden = 64, 128
        x = jnp.zeros((1, 1, hidden), jnp.float32)

        # -- vocab sharding (ops/sharded_vocab.py) ------------------------
        # embed_tokens_sharded: the masked local gather + all-reduce that
        # replaces the replicated emb[tokens] lookup. Traced through the
        # SAME module-level body the engine's forward() calls, so the
        # pinned fingerprint covers the real serving embedding path.
        from ..ops.sharded_vocab import (embed_tokens_sharded,
                                         sharded_sample_prep)

        spec_e = _tiny_spec()
        emb_e = jnp.zeros((spec_e.vocab_size, spec_e.dim), jnp.float32)
        tok_e = jnp.zeros((2, 4), jnp.int32)

        def embed_tokens(emb, tok):
            return embed_tokens_sharded(emb, tok, mesh, ("tp",),
                                        jnp.float32)

        out.append(EntryPoint(
            "embed_tokens_sharded", embed_tokens, (emb_e, tok_e),
            {"activation_elems": 2 * 4 * spec_e.dim, "dim": spec_e.dim},
            needs_mesh=2))

        # sharded_sample_prep: the serving-path sampling summary — device
        # argmax + per-shard top-k candidates off vocab-sharded logits.
        # meta["vocab"] arms DLG205: no output (and no all_gather) of
        # this program may carry a vocab-sized dim — the whole point is
        # that full logits never materialize on the serving path.
        lg_s2 = jnp.zeros((4, spec_e.vocab_size), jnp.float32)
        temps_s = jnp.ones((4,), jnp.float32)

        def sample_prep(logits, temps):
            return sharded_sample_prep(logits, temps, mesh, ("tp",),
                                       spec_e.vocab_size, 8)

        out.append(EntryPoint(
            "sharded_sample_prep", sample_prep, (lg_s2, temps_s),
            {"activation_elems": 4 * spec_e.dim, "dim": spec_e.dim,
             "vocab": spec_e.vocab_size},
            needs_mesh=2))

        # -- q80-compressed col-split reduce (the wire-compression path) --
        from ..parallel.tp_q80 import repack_col_tp

        w_col = repack_col_tp(jnp.zeros((dim, hidden), jnp.float32), 2)

        def tp_q80_col(x, w):
            return tp_col_matmul(x, w, mesh, reduce="q80",
                                 compute_dtype=jnp.float32)

        out.append(EntryPoint(
            "tp_q80_col", tp_q80_col, (x, w_col),
            {"activation_elems": 1 * 1 * dim, "dim": dim}, needs_mesh=2))

        # -- exact col-split reduce (GSPMD-equivalent shard_map path) -----
        def tp_exact_col(x, w):
            return tp_col_matmul(x, w, mesh, reduce="exact",
                                 compute_dtype=jnp.float32)

        out.append(EntryPoint(
            "tp_exact_col", tp_exact_col, (x, w_col),
            {"activation_elems": 1 * 1 * dim, "dim": dim}, needs_mesh=2))

        # -- row-split matmul (communication-free by design) --------------
        from ..parallel.tp_q80 import TpRowWeight

        xr = jnp.zeros((1, dim), jnp.float32)
        w_row = TpRowWeight(jnp.zeros((hidden, dim), jnp.float32))

        def tp_row(x, w):
            return tp_row_matmul(x, w, mesh, compute_dtype=jnp.float32,
                                 use_pallas=False)

        out.append(EntryPoint(
            "tp_row", tp_row, (xr, w_row),
            {"activation_elems": 1 * 1 * dim, "dim": dim}, needs_mesh=2))

    if n_dev >= 4:
        from ..parallel import make_mesh
        from ..parallel.ep_moe import repack_moe_ep

        spec_m = _tiny_spec("MIXTRAL")
        mesh_ep = make_mesh(ep=2, tp=2, dp=1)
        params_m = _zero_params(spec_m)
        params_m = dict(params_m)
        params_m["layers"] = [repack_moe_ep(lw, 2)
                              for lw in params_m["layers"]]
        from ..models.transformer import KVCache as _KV

        cache_m = _KV.create(spec_m, batch=1, seq_len=spec_m.seq_len,
                             dtype=jnp.float32)
        tok_m = jnp.zeros((1, 1), jnp.int32)

        def ep_moe_decode(params, tok, pos0, cache):
            return forward(params, spec_m, tok, pos0, cache,
                           compute_dtype=jnp.float32, tp_mesh=mesh_ep)

        out.append(EntryPoint(
            "ep_moe_decode", ep_moe_decode,
            (params_m, tok_m, jnp.int32(0), cache_m),
            {"activation_elems": 1 * 1 * spec_m.dim, "dim": spec_m.dim},
            needs_mesh=4))

    return out, unavailable


def signature_fingerprint(ep: EntryPoint) -> str:
    """Hash of the entry point's COMPILATION KEY — the input avals
    (shape/dtype/weak_type) in pytree order. A drifting fingerprint means
    the jit cache key changed: a host scalar became a weak-typed Python
    int (silent retrace per distinct value), an input dtype widened, or an
    argument was added. DLG204 compares this against the baseline."""
    import hashlib

    leaves = jax.tree_util.tree_leaves(ep.args)
    parts = []
    for leaf in leaves:
        aval = jax.api_util.shaped_abstractify(leaf)
        parts.append(f"{aval.shape}:{aval.dtype}:{getattr(aval, 'weak_type', False)}")
    blob = ep.name + ";" + "|".join(parts)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def make_jaxpr_for(ep: EntryPoint, x64: bool = False):
    """Trace the entry point to a ClosedJaxpr (no compilation). With
    x64=True the trace runs under jax.enable_x64(True) so an
    accidental f64 promotion becomes VISIBLE as an f64 aval instead of
    being silently truncated to f32 by the global x64=off default."""
    if x64:
        with jax.enable_x64(True):
            # re-cast inputs under the x64 regime: well-typed code keeps
            # every explicit dtype; only promotion leaks drift to f64
            return jax.make_jaxpr(ep.fn)(*ep.args)
    return jax.make_jaxpr(ep.fn)(*ep.args)
