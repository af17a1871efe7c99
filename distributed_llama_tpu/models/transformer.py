"""Unified transformer forward for LLAMA / MIXTRAL / GROK1 / SARVAM_MLA /
OLMO_HYBRID / GRANITE_HYBRID / KIMI_LINEAR / JAMBA.

One jittable segment-forward covers both prefill (T tokens at once — net-new
vs the reference, which feeds the prompt token-by-token) and decode (T=1).
The per-layer dataflow reproduces the reference task pipelines:

  * LLAMA dense block  — ref: src/llama2-tasks.cpp:249-275
  * MIXTRAL MoE block  — ref: src/mixtral-tasks.cpp:5-51
  * GROK1 extra norms, input/logit scalings — ref: src/grok1-tasks.cpp:11-41,
    244-272, 274-326

but the reference's broadcast/gather/merge sync tasks vanish: the row/col
weight sharding is expressed as PartitionSpecs (parallel/sharding.py) and
GSPMD inserts the equivalent ICI collectives.

Layers are statically unrolled — the TPU analogue of the reference's flat
per-layer task list (ref: src/tasks.hpp:27-37). An earlier `lax.scan` over
stacked (L, ...) weights/cache was profiled at ~3x the decode cost of the
actual math: every scan step dynamic-sliced the layer's KV cache out of the
stacked array and back in (two 16 MB copies per layer per token at 7B), and
copied+re-laid-out the packed weights before each Pallas call. Unrolling
makes each layer's weights and cache standalone buffers: weights feed the
kernel in place, and the per-layer cache arrays are donated and updated
in place (the functional form of the reference's in-place cache write at
src/llama2-tasks.cpp:38-44): the batched step programs through the aliased
`kv_cache_write` kernel (ops/pallas_kv_write.py), the shared-position
paths through dynamic_update_slice. An XLA-level update of a few rows is
in place only in name where the kernels run: layout assignment re-lays
the whole operand around it (four cache-sized copies a layer on v5e).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.activations import apply_hidden_act
from ..ops.attention import decode_attention
from ..ops.matmul import (fused_expert_matmul, matmul,
                          reads_experts_in_place)
from ..ops.norms import rmsnorm
from ..ops.rope import apply_rope
from ..quants.jax_codec import QuantizedTensor
from .spec import ArchType, LayerKind, ModelSpec

GROK_INPUT_SCALE = 78.38367176906169      # ref: src/grok1-tasks.cpp:13
GROK_LOGIT_SCALE = 0.5773502691896257     # ref: src/grok1-tasks.cpp:271


def _flash_ok(t: int, h: int, kvh: int) -> bool:
    from ..ops.pallas_attention import flash_supported

    return flash_supported(t, h, kvh)


class KVCache(NamedTuple):
    """A slot batch's memory of the past, one leaf SET a layer kind
    (ModelSpec.layer_kinds; a layer finds its leaves at
    ModelSpec.cache_index): `k` and `v`, one (B, KVH, S, hs) array for
    every layer that attends rows; `s` and `conv`, for every DELTA layer
    its float32 state (B, H, d_k, d_v) and the last taps - 1 rows the
    convolution has seen (B, taps - 1, channels). A DELTA layer holds NO
    context-sized leaf.

    Separate per-layer buffers (not one stacked (L, ...) array) so that a
    donated cache is updated strictly in place — profiling showed XLA copies
    stacked caches wholesale through scan/while carries — and each leaf is
    the aliased in/out operand of the step programs' kv_cache_write kernel
    (_scatter_cache_write). Head-major (KVH
    before S) so decode attention reads each head's keys sequentially;
    with S-major XLA picked a head-minor layout that ran the per-layer
    score contraction at ~75 GB/s instead of ~600."""

    k: tuple
    v: tuple
    s: tuple = ()
    conv: tuple = ()

    @classmethod
    def create(cls, spec: ModelSpec, batch: int, seq_len: int | None = None,
               dtype=jnp.float32, pp: int = 1) -> "KVCache":
        """pp > 1: stage-stacked layout — n_layers/pp leaves of
        (pp, B, KVH, S, hs), the stage axis sharded over pp so each device
        stores only its own layers' cache (parallel/pp.py)."""
        s = seq_len or spec.seq_len
        shape = (batch, spec.n_kv_heads, s, spec.cache_head_size)
        n = spec.n_cache_layers
        if pp > 1:
            assert n % pp == 0, (n, pp)
            shape = (pp,) + shape
            n = n // pp
        # the latent cache (SARVAM_MLA) is ONE leaf a layer, (B, 1, S,
        # r + d_r): the normed latent is key and value at once, so `v` is
        # empty
        # the convolution's tail keeps the projection's own values: never
        # narrower than bf16, whatever the rows are kept in
        item = jnp.dtype(dtype).itemsize
        tail_dtype = dtype if spec.tail_itemsize(item) == item else jnp.bfloat16
        leaves = [spec.state_leaves(k) for k in spec.layer_kinds
                  if k.has_state]
        return cls(
            tuple(jnp.zeros(shape, dtype) for _ in range(n)),
            tuple(jnp.zeros(shape, dtype) for _ in range(n))
            if spec.cache_v_head_size else (),
            tuple(jnp.zeros((batch,) + state, jnp.float32)
                  for state, _ in leaves),
            tuple(jnp.zeros((batch,) + tail, tail_dtype)
                  for _, tail in leaves),
        )


def _to_cache_dtype(x, dtype):
    """Cast k/v to the cache dtype; sub-bf16 caches (fp8 e4m3) saturate at
    the format's max first — the jax cast is non-saturating and |v| > 448
    would become NaN, permanently poisoning every later attention read
    (read-side counterpart: ops/attention.is_narrow_cache)."""
    from ..ops.attention import is_narrow_cache

    if is_narrow_cache(dtype):
        lim = float(jnp.finfo(dtype).max)
        x = jnp.clip(x, -lim, lim)
    return x.astype(dtype)


def _scatter_cache_write(k_cache, v_cache, k, v, idx, write_gate,
                         kernel_cfg=None, slots=None):
    """Write (B, T, KVH, hs) K/V at per-position indices (B, T) into
    (B, KVH, S, hs) caches, dropping every index >= S. write_gate (traced
    bool) pushes gated-off writes to the out-of-bounds slot S — shared by
    the batched per-row write path and the manual-sp chunk-local write path
    so the OOB-gating idiom cannot diverge.

    kernel_cfg: the forward's cfg, passed by callers whose index rows are
    CONTIGUOUS and start inside the cache or at S (idx[b] = pos[b] +
    arange(T), pos[b] >= 0). With the kernels on and a context of whole row
    tiles, the in-place `kv_cache_write` kernel then takes the write
    (ops/pallas_kv_write.py: the XLA scatter costs four cache-sized layout
    copies a layer); bit-equal to the drop-mode scatter, which stays for
    every other caller, as decode_attention stays behind flash_attention.

    slots: (B,) the cache slot row b writes (forward's `slots`); None is
    the identity. Both forms take the same map."""
    oob = k_cache.shape[2]
    if write_gate is not None:
        idx = jnp.where(write_gate, idx, oob)
    k = _to_cache_dtype(k, k_cache.dtype)
    if v_cache is not None:    # None: a cache of one leaf (the latent cache)
        v = _to_cache_dtype(v, v_cache.dtype)
    if kernel_cfg is not None and kernel_cfg.get("use_pallas"):
        from ..ops.pallas_kv_write import kv_cache_write, kv_write_supported

        if kv_write_supported(oob, k_cache.dtype):
            interpret = kernel_cfg.get("pallas_interpret", False)
            mesh = kernel_cfg.get("tp_mesh")
            if mesh is not None and not kernel_cfg.get("manual_tp"):
                # GSPMD can't partition a pallas_call: per shard, next to
                # tp_flash_attention (inside the manual pp region the
                # cache is already local)
                from ..parallel.tp_q80 import tp_kv_cache_write

                assert slots is None, "a slot map on a mesh"
                return tp_kv_cache_write(k_cache, v_cache, k, v, idx[:, 0],
                                         mesh, interpret=interpret)
            return kv_cache_write(k_cache, v_cache, k, v, idx[:, 0],
                                  slots=slots, interpret=interpret)
    bidx = (jnp.arange(k_cache.shape[0], dtype=jnp.int32)
            if slots is None else slots)[:, None]
    k_cache = k_cache.at[bidx, :, idx].set(k, mode="drop")
    if v_cache is not None:
        v_cache = v_cache.at[bidx, :, idx].set(v, mode="drop")
    return k_cache, v_cache


def _mla_attention_block(x, lw, spec: ModelSpec, cache, q_pos, cfg,
                         per_row_pos=False, write_gate=None):
    """Latent attention in the absorbed form, for decode and chunks alike.

    u = norm(x); q = Wq u, per head [q_n ; q_r]; [c ; k_r] = Wkva u;
    c~ = norm(c; g_kv); q_r, k_r rotated (ONE k_r for all heads; not
    rotated where the header's rope_theta is 0). The cache row is
    [c~ ; rot(k_r)]. Absorbed: q^_h = W_uk,h^T q_n,h, score =
    (q^_h . c~ + q_r,h . k_r) * scale, o^_h = sum a c~, o_h = W_uv,h o^_h —
    the per-head keys and values W_kvb c~ are never built, so a cached
    token costs its 2 * (r + d_r + r) FLOPs a head and no up-projection.
    Returns (wo projection not yet added to the residual, new cache leaf).
    """
    from ..ops.rope import rope_yarn

    b, t, _ = x.shape
    h, r = spec.n_heads, spec.kv_lora_rank
    d_n, d_r = spec.qk_nope_head_dim, spec.qk_rope_head_dim
    eps = spec.norm_eps

    with jax.named_scope("attn_proj"):
        u = rmsnorm(x, lw["rms_att"], eps)
        q = matmul(u, lw["wq"], **cfg).reshape(b, t, h, d_n + d_r)
        kva = matmul(u, lw["wkva"], **cfg)                   # (B, T, r + d_r)
        c = rmsnorm(kva[..., :r], lw["rms_kv"], eps)
        if spec.rope_theta > 0:
            q_r = rope_yarn(q[..., d_n:], q_pos, spec)
            k_r = rope_yarn(kva[..., None, r:], q_pos, spec)[..., 0, :]
        else:   # 0: no rotation; the d_r columns are plain shared keys
            # (position reaches such a model's attention through its
            # recurrent layers)
            q_r, k_r = q[..., d_n:], kva[..., r:]
        new = jnp.concatenate([c, k_r.astype(c.dtype)],
                              axis=-1)[:, :, None, :]

    with jax.named_scope("mla_absorb"):
        q_abs = jnp.einsum("bthn,hnr->bthr", q[..., :d_n], lw["w_uk"],
                           preferred_element_type=q.dtype)
        q_abs = jnp.concatenate([q_abs, q_r], axis=-1)
    scale = spec.attn_softmax_scale
    # a mesh (dp replicas of the batch) keeps the XLA forms: GSPMD cannot
    # partition a pallas_call, and nothing here is split over tp
    if (per_row_pos and cfg.get("use_pallas") and cfg.get("tp_mesh") is None
            and _mla_kernels_ok(t, h, cache.shape[2])):
        # both kernels take the leaf as the TPU holds it: sequence minor,
        # a token a column (ops/pallas_kv_write.py says why); these two
        # transposes are bitcasts of that layout
        from ..ops.pallas_attention import mla_attention
        from ..ops.pallas_kv_write import kv_cache_write_seq_minor

        assert write_gate is None
        interpret = cfg.get("pallas_interpret", False)
        with jax.named_scope("attn_cache"):
            cache_t = kv_cache_write_seq_minor(
                cache.transpose(0, 1, 3, 2),
                _to_cache_dtype(new, cache.dtype), q_pos[:, 0],
                interpret=interpret)
        with jax.named_scope("attn_core"):
            att = mla_attention(q_abs, cache_t, q_pos, v_width=r,
                                scale=scale, interpret=interpret)
        with jax.named_scope("attn_cache"):
            cache = cache_t.transpose(0, 1, 3, 2)
    else:
        with jax.named_scope("attn_cache"):
            if per_row_pos:
                cache, _ = _scatter_cache_write(cache, None, new, None,
                                                q_pos, write_gate)
            else:
                assert write_gate is None
                zero = jnp.int32(0)
                cache = lax.dynamic_update_slice(
                    cache, _to_cache_dtype(new.transpose(0, 2, 1, 3),
                                           cache.dtype),
                    (zero, zero, q_pos[0, 0], zero))
        with jax.named_scope("attn_core"):
            att = decode_attention(q_abs, cache, cache[..., :r], q_pos,
                                   scale=scale)              # (B, T, H, r)
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("bthr,hvr->bthv", att, lw["w_uv"],
                       preferred_element_type=x.dtype)
    with jax.named_scope("attn_out"):
        out = matmul(o.reshape(b, t, h * spec.v_head_dim), lw["wo"], **cfg)
    return out, cache


def _mla_kernels_ok(t: int, h: int, seq_len: int) -> bool:
    from ..ops.pallas_attention import mla_supported
    from ..ops.pallas_kv_write import kv_write_seq_minor_supported

    return mla_supported(t, h) and kv_write_seq_minor_supported(seq_len)


def _attention_block(x, lw, spec: ModelSpec, k_cache, v_cache, q_pos, cfg,
                     sp_mesh=None, sp_cache_mesh=None, per_row_pos=False,
                     write_gate=None, slots=None):
    """Norm -> QKV -> RoPE -> cache update -> attention -> output proj.

    Returns (attn_out, new_k_cache, new_v_cache). attn_out is the wo
    projection NOT yet added to the residual (archs differ there).
    write_gate: optional traced bool — when False the cache update re-writes
    the existing values (pipeline parallelism runs every stage's layers on
    every device each iteration, but only the live stage may write its
    cache — parallel/pp.py).
    slots: (B,) int32, forward's slot map: row b writes and attends the
    cache rows of slot slots[b] (one chip, per-row positions). The write
    comes first, so a row attends what the rows before it wrote for the
    same slot in this very program.
    """
    b, t, d = x.shape
    h, kvh, hs = spec.n_heads, spec.n_kv_heads, spec.head_size
    assert slots is None or (
        per_row_pos and sp_mesh is None and sp_cache_mesh is None
        and cfg.get("tp_mesh") is None and not cfg.get("manual_tp")
        and not cfg.get("manual_sp")), "a slot map off the one-chip slot path"
    f = cfg.get("manual_tp") or 1
    if f > 1:
        # fully-manual pp region: this shard computes h/tp query heads and
        # kvh/tp kv heads (row-split projections, head-sharded cache) — the
        # same per-shard shapes tp_q80's shard_map bodies see. RoPE and
        # attention are per-head, so only the reshape bookkeeping changes.
        h, kvh = h // f, kvh // f

    with jax.named_scope("attn_proj"):
        if spec.post_norm:
            xb = x          # the norm sits on the sublayer's OUTPUT (_layer)
        else:
            # ref: llama2-tasks.cpp:10-21
            xb = rmsnorm(x, lw["rms_att"], spec.norm_eps)
        if "wqkv" in lw:
            # fused QKV projection (single-shard path): one kernel call, one
            # shared activation prep, deeper DMA pipeline
            qkv = matmul(xb, lw["wqkv"], **cfg)
            q = qkv[..., : h * hs].reshape(b, t, h, hs)
            k = qkv[..., h * hs: (h + kvh) * hs].reshape(b, t, kvh, hs)
            v = qkv[..., (h + kvh) * hs:].reshape(b, t, kvh, hs)
        else:
            q = matmul(xb, lw["wq"], **cfg).reshape(b, t, h, hs)
            k = matmul(xb, lw["wk"], **cfg).reshape(b, t, kvh, hs)
            v = matmul(xb, lw["wv"], **cfg).reshape(b, t, kvh, hs)

        if spec.post_norm:
            # q and k normed over the whole projected width, before the heads
            q = rmsnorm(q.reshape(b, t, h * hs), lw["rms_q"],
                        spec.norm_eps).reshape(b, t, h, hs)
            k = rmsnorm(k.reshape(b, t, kvh * hs), lw["rms_k"],
                        spec.norm_eps).reshape(b, t, kvh, hs)
        if spec.rope_theta > 0:     # 0: no rotation (position reaches such a
            # model's attention through its recurrent layers)
            q = apply_rope(q, q_pos, spec.rope_theta, spec.arch)
            k = apply_rope(k, q_pos, spec.rope_theta, spec.arch)
    # a published softmax scale other than head^-1/2 (None: that one). The
    # sp and tp paths below take none; a model that has one is refused there
    scale = spec.attn_scale or None

    # functional cache update at positions q_pos (contiguous per row:
    # pos[b]..pos[b]+T); cache is head-major (B, KVH, S, hs) — see KVCache
    sp_n = cfg.get("manual_sp") or 1
    if sp_n > 1:
        # fully-manual pp region with an sp-sharded cache: this device
        # holds the S/sp chunk starting at sp_index * s_local. Writes go
        # through a per-position scatter at chunk-LOCAL indices; positions
        # owned by other devices (and bubble-step writes, write_gate) are
        # pushed to the OOB slot — scatter drops them. Negative local
        # indices would WRAP, not drop, so they are clamped to OOB first.
        # The scatter STAYS here: a row's window may start before this
        # chunk and end inside it, which the kv_cache_write kernel's one
        # start position per row cannot say.
        from ..parallel.mesh import SP_AXIS as _SP
        from ..parallel.ring_attention import sp_cache_attention_local

        with jax.named_scope("attn_cache"):
            s_local = k_cache.shape[2]
            local = q_pos - lax.axis_index(_SP) * s_local
            local = jnp.where(local < 0, s_local, local)
            k_cache, v_cache = _scatter_cache_write(k_cache, v_cache, k, v,
                                                    local, write_gate)
        assert scale is None, "a softmax scale of its own under manual sp"
        with jax.named_scope("attn_core"):
            att = sp_cache_attention_local(q, k_cache, v_cache, q_pos)
        with jax.named_scope("attn_out"):
            out = matmul(att.reshape(b, t, h * hs), lw["wo"], **cfg)
        return out, k_cache, v_cache
    with jax.named_scope("attn_cache"):
        if per_row_pos:
            # batched generation: each sequence writes at its own position
            # (net-new vs the reference's batch=1 — SURVEY.md §2.5 DP row).
            # Gated (pp off-turn) writes are pushed out of bounds and dropped
            # — cheaper than a read-modify-write, and XLA's partitioner
            # handles the scatter where it miscompiles the equivalent gather
            # under manual pp. q_pos rows are contiguous, so the in-place
            # kernel may take the write; a cache whose sequence GSPMD shards
            # over sp keeps the scatter (a pallas_call would gather it whole).
            k_cache, v_cache = _scatter_cache_write(
                k_cache, v_cache, k, v, q_pos, write_gate,
                kernel_cfg=cfg if sp_cache_mesh is None else None,
                slots=slots)
        else:
            pos0 = q_pos[:, 0]
            k_w = _to_cache_dtype(k.transpose(0, 2, 1, 3), k_cache.dtype)
            v_w = _to_cache_dtype(v.transpose(0, 2, 1, 3), v_cache.dtype)
            # index literals pinned to the position dtype: bare Python 0s trace
            # as int64 under x64 and dynamic_(update_)slice rejects mixed index
            # dtypes — int32 everywhere keeps the program x64-proof (dlgrind
            # DLG202 traces entry points under enable_x64)
            zero = jnp.int32(0)
            start = (zero, zero, pos0[0], zero)
            if write_gate is not None:
                k_w = jnp.where(write_gate, k_w,
                                lax.dynamic_slice(k_cache, start, k_w.shape))
                v_w = jnp.where(write_gate, v_w,
                                lax.dynamic_slice(v_cache, start, v_w.shape))
            k_cache = lax.dynamic_update_slice(k_cache, k_w, start)
            v_cache = lax.dynamic_update_slice(v_cache, v_w, start)
        if sp_cache_mesh is not None:
            # keep the cache sp-sharded through the functional update:
            # during ring prefill the T-sharded K/V reshards into the
            # S-sharded cache (one K/V-sized shuffle per layer); decode's
            # single-position write lands in the owning shard. Per-device
            # cache stays seq_len/sp.
            from jax.sharding import NamedSharding

            from ..parallel.sharding import cache_pspec

            cs = NamedSharding(sp_cache_mesh, cache_pspec(sp=True))
            k_cache = jax.lax.with_sharding_constraint(k_cache, cs)
            v_cache = jax.lax.with_sharding_constraint(v_cache, cs)

    assert scale is None or (sp_mesh is None and sp_cache_mesh is None
                             and cfg.get("tp_mesh") is None), (
        "a softmax scale of its own under sp or tp")
    with jax.named_scope("attn_core"):
        if sp_mesh is not None:
            # sequence-parallel prefill: the segment starts at pos 0 and IS
            # the whole context so far, so attention runs q-chunk vs
            # ring-rotating k/v chunks instead of against the cache (net-new
            # vs the reference — SURVEY.md §5.7)
            from ..parallel.ring_attention import ring_attention

            att = ring_attention(q, k, v, sp_mesh, pos0=0)
        elif sp_cache_mesh is not None:
            # sp-sharded cache: per-chunk flash stats + exact psum merge.
            # Must outrank the pallas branch — the pallas kernel is not
            # shard_map'd, so routing it an sp-sharded cache would all-gather
            # the full sequence per layer and void the seq_len/sp memory
            # scaling.
            from ..parallel.ring_attention import sp_cache_attention

            att = sp_cache_attention(q, k_cache, v_cache, q_pos, sp_cache_mesh)
        elif cfg.get("use_pallas") and _flash_ok(t, h, kvh):
            # decode (T=1) and chunked prefill (T>1) both take the flash
            # kernel: online-softmax in VMEM instead of the dense path's
            # (B,T,KVH,G,S) score materialization in HBM
            # (ops/pallas_attention.py)
            if cfg.get("manual_tp"):
                # already inside the fully-manual pp region: heads are local,
                # call the kernel directly (no shard_map entry)
                from ..ops.pallas_attention import flash_attention

                att = flash_attention(
                    q, k_cache, v_cache, q_pos,
                    interpret=cfg.get("pallas_interpret", False), scale=scale)
            elif cfg.get("tp_mesh") is not None:
                # multi-device mesh: GSPMD can't partition a pallas_call, so
                # the kernel runs per-shard inside shard_map (dp on batch, tp
                # on kv-heads — head-local, no collective)
                from ..parallel.tp_q80 import tp_flash_attention

                att = tp_flash_attention(
                    q, k_cache, v_cache, q_pos, cfg["tp_mesh"],
                    interpret=cfg.get("pallas_interpret", False))
            else:
                from ..ops.pallas_attention import flash_attention

                att = flash_attention(
                    q, k_cache, v_cache, q_pos,
                    interpret=cfg.get("pallas_interpret", False), scale=scale,
                    slots=slots)
        elif slots is not None:
            # the XLA twin of the kernel's index map gathers the rows' slots
            att = decode_attention(q, k_cache[slots], v_cache[slots], q_pos,
                                   scale=scale)
        else:
            att = decode_attention(q, k_cache, v_cache, q_pos,
                                   scale=scale)                # (B, T, H, hs)
    with jax.named_scope("attn_out"):
        out = matmul(att.reshape(b, t, h * hs), lw["wo"], **cfg)
    return out, k_cache, v_cache


class SegmentRows(NamedTuple):
    """What a recurrent state has to know of a segment's rows and a cache
    of rows never did (ops/pallas_delta_rule.py says why): n_valid (B,)
    int32, the tokens of row b that advance its state — 0 for a gated row
    (pos == the context), logit_index + 1 in a right-padded tail chunk,
    else T; fresh (B,) bool, the row's segment starts at position 0, so
    its state starts from zeros whatever the slot held. Under a slot map
    (forward's `slots`; None without one, and the next two with it):
    chained (B,) bool, the row continues the row before it (the same slot,
    both live), so it starts from that row's final state and tail, not the
    slot's; last (B,) bool, a live row that no row continues, whose state
    and tail are its slot's new ones."""

    n_valid: jnp.ndarray
    fresh: jnp.ndarray
    slots: jnp.ndarray | None = None
    chained: jnp.ndarray | None = None
    last: jnp.ndarray | None = None


def _short_conv(xin, conv, lw, rows: SegmentRows, taps: int):
    """The causal depthwise convolution of a state layer's mixer, then SiLU:
    row t of the output sees rows t .. t + taps - 1 of [tail ; xin] (plus
    the layer's conv_b where it has one); the new tail is the last taps - 1
    rows that COUNT. `conv` is the layer's tail leaf, a row a slot: without
    a slot map row b's tail is conv[b]; with one it is conv[slots[b]] or,
    for a row that continues the row before it, the last taps - 1 rows of
    [that row's tail ; its inputs] (a whole segment, so its inputs alone
    where T >= taps - 1), and a slot's last live row alone writes the leaf.
    Returns (float32 output, the leaf's new value)."""
    t = xin.shape[1]
    tail = conv if rows.slots is None else conv[rows.slots]
    tail = first = jnp.where(rows.fresh[:, None, None], 0, tail)
    if rows.slots is not None:
        for _ in range(-(-(taps - 1) // t)):    # once, where T >= taps - 1
            left = jnp.concatenate([tail.astype(xin.dtype), xin],
                                   axis=1)[:, -(taps - 1):].astype(tail.dtype)
            tail = jnp.where(rows.chained[:, None, None],
                             jnp.roll(left, 1, axis=0), first)
    xcat = jnp.concatenate([tail.astype(xin.dtype), xin], axis=1)
    conv_w = lw["conv_w"]
    y = sum(conv_w[j] * xcat[:, j:j + t].astype(jnp.float32)
            for j in range(taps))
    if "conv_b" in lw:
        y = y + lw["conv_b"]
    y = jax.nn.silu(y)
    tail = jax.vmap(
        lambda xc, n: lax.dynamic_slice_in_dim(xc, n, taps - 1, 0))(
            xcat, rows.n_valid).astype(tail.dtype)
    if rows.slots is None:
        return y, tail
    return y, conv.at[jnp.where(rows.last, rows.slots, conv.shape[0])].set(
        tail, mode="drop")


def _unit(u):
    """A head's q or k scaled to unit length (the delta rules' keys)."""
    return u * lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + 1e-6)


def _delta_block(x, lw, spec: ModelSpec, state, tail, rows: SegmentRows,
                 cfg):
    """Gated delta rule mixer: projections -> causal depthwise convolution
    and SiLU on [q ; k ; v] -> the rule on unit-length q, k -> per-head
    gated RMS norm -> output projection. Returns (the wo projection, not
    yet normed or added to the residual, new state, new tail)."""
    from ..ops.pallas_delta_rule import delta_rule

    b, t, _ = x.shape
    h, dk, dv = spec.lin_heads, spec.lin_k_head_dim, spec.lin_v_head_dim
    taps, f32 = spec.lin_conv_width, jnp.float32
    with jax.named_scope("gdn_proj"):
        if "wqkv" in lw:
            qkv = matmul(x, lw["wqkv"], **cfg)
        else:
            qkv = jnp.concatenate(
                [matmul(x, lw[w], **cfg) for w in ("wq", "wk", "wv")], -1)
        z = matmul(x, lw["wg"], **cfg)
        ab = matmul(x, lw["w_ab"], **cfg).astype(f32)      # (B, T, 2H)
    with jax.named_scope("gdn_conv"):
        y, tail = _short_conv(qkv, tail, lw, rows, taps)

    with jax.named_scope("gdn_rule"):
        q = y[..., :h * dk].reshape(b, t, h, dk)
        k = y[..., h * dk:2 * h * dk].reshape(b, t, h, dk)
        v = y[..., 2 * h * dk:].reshape(b, t, h, dv)
        g = -jnp.exp(lw["a_log"]) * jax.nn.softplus(ab[..., :h]
                                                    + lw["dt_bias"])
        beta = spec.lin_beta_scale * jax.nn.sigmoid(ab[..., h:])
        o, state = delta_rule(
            _unit(q) * dk ** -0.5, _unit(k), v, g, beta, state, rows.n_valid,
            rows.fresh, use_pallas=bool(cfg.get("use_pallas")),
            interpret=cfg.get("pallas_interpret", False))
    with jax.named_scope("gdn_out"):
        o = (rmsnorm(o, lw["rms_o"], spec.norm_eps)
             * jax.nn.silu(z.reshape(b, t, h, dv).astype(f32)))
        out = matmul(o.astype(x.dtype).reshape(b, t, h * dv), lw["wo"],
                     **cfg)
    return out, state, tail


def _kda_block(x, lw, spec: ModelSpec, state, tail, rows: SegmentRows, cfg):
    """Kimi Delta Attention mixer under a norm on its INPUT: q, k, v and
    the thin projections (the decay's and the gate's first halves, the
    step's rows) -> causal depthwise convolution and SiLU on [q ; k ; v] ->
    the decay a key CHANNEL through the second half of its low-rank pair ->
    the rule on unit-length q, k (ops/pallas_kda.py) -> per-head RMS norm
    gated by a SIGMOID of the gate's low-rank pair -> output projection.
    _delta_block's contract."""
    from ..ops.pallas_kda import kda_rule

    b, t, _ = x.shape
    h, dk, dv = spec.lin_heads, spec.lin_k_head_dim, spec.lin_v_head_dim
    f32 = jnp.float32
    with jax.named_scope("kda_proj"):
        u = rmsnorm(x, lw["rms_att"], spec.norm_eps)
        if "wqkv" in lw:
            qkv = matmul(u, lw["wqkv"], **cfg)
        else:
            qkv = jnp.concatenate(
                [matmul(u, lw[w], **cfg) for w in ("wq", "wk", "wv")], -1)
        thin = matmul(u, lw["w_fgb"], **cfg)           # (B, T, d_k + H + d_v)
        f = matmul(thin[..., :dk], lw["wf_b"], **cfg).astype(f32)
        z = matmul(thin[..., dk + h:], lw["wg_b"], **cfg)
    with jax.named_scope("kda_conv"):
        y, tail = _short_conv(qkv, tail, lw, rows, spec.lin_conv_width)

    with jax.named_scope("kda_rule"):
        q = y[..., :h * dk].reshape(b, t, h, dk)
        k = y[..., h * dk:2 * h * dk].reshape(b, t, h, dk)
        v = y[..., 2 * h * dk:].reshape(b, t, h, dv)
        g = (-jnp.exp(lw["a_log"])[:, None]
             * jax.nn.softplus(f + lw["dt_bias"]).reshape(b, t, h, dk))
        beta = spec.lin_beta_scale * jax.nn.sigmoid(
            thin[..., dk:dk + h].astype(f32))
        o, state = kda_rule(
            _unit(q) * dk ** -0.5, _unit(k), v, g, beta, state, rows.n_valid,
            rows.fresh, use_pallas=bool(cfg.get("use_pallas")),
            interpret=cfg.get("pallas_interpret", False))
    with jax.named_scope("kda_out"):
        o = (rmsnorm(o, lw["rms_o"], spec.norm_eps)
             * jax.nn.sigmoid(z.reshape(b, t, h, dv).astype(f32)))
        out = matmul(o.astype(x.dtype).reshape(b, t, h * dv), lw["wo"],
                     **cfg)
    return out, state, tail


def _gate_and_x(u, lw, inner: int, cfg):
    """Both state-space mixers' input projection [z ; x] of the normed
    input: one fused call on one shard, its two halves elsewhere."""
    if "wzx" in lw:
        zx = matmul(u, lw["wzx"], **cfg)
        return zx[..., :inner], zx[..., inner:]
    return matmul(u, lw["wz"], **cfg), matmul(u, lw["wx"], **cfg)


def _ssm_block(x, lw, spec: ModelSpec, state, tail, rows: SegmentRows, cfg):
    """State-space (Mamba-2) mixer under a norm on its INPUT: projections
    [z ; x ; B | C | dt] -> causal depthwise convolution (+ bias) and SiLU
    on [x ; B ; C] -> the SSD recurrence, B and C shared by a group's heads
    -> the skip D x -> gate, THEN one RMS norm over d_inner -> output
    projection. Returns (the wo projection, not yet scaled or added to the
    residual, new state, new tail): _delta_block's contract."""
    from ..ops.pallas_ssd import ssd_scan

    b, t, _ = x.shape
    h, p, n = spec.ssm_heads, spec.ssm_head_dim, spec.ssm_d_state
    inner, gn = spec.ssm_inner, spec.ssm_groups * spec.ssm_d_state
    f32 = jnp.float32
    with jax.named_scope("ssm_proj"):
        u = rmsnorm(x, lw["rms_att"], spec.norm_eps)
        z, xs = _gate_and_x(u, lw, inner, cfg)
        bcdt = matmul(u, lw["w_bcdt"], **cfg)              # (B, T, 2GN + H)
    with jax.named_scope("ssm_conv"):
        y, tail = _short_conv(
            jnp.concatenate([xs, bcdt[..., :2 * gn]], axis=-1), tail, lw,
            rows, spec.ssm_conv_width)
    with jax.named_scope("ssm_scan"):
        xh = y[..., :inner].reshape(b, t, h, p)
        dt = jax.nn.softplus(bcdt[..., 2 * gn:].astype(f32) + lw["dt_bias"])
        o, state = ssd_scan(
            xh, dt, -jnp.exp(lw["a_log"]),
            y[..., inner:inner + gn].reshape(b, t, spec.ssm_groups, n),
            y[..., inner + gn:].reshape(b, t, spec.ssm_groups, n),
            state, rows.n_valid, rows.fresh, rows.slots, rows.chained,
            use_pallas=bool(cfg.get("use_pallas")),
            interpret=cfg.get("pallas_interpret", False))
        o = o + lw["ssm_d"][:, None] * xh
    with jax.named_scope("ssm_out"):
        o = o.reshape(b, t, inner) * jax.nn.silu(z.astype(f32))
        out = matmul(rmsnorm(o, lw["rms_o"], spec.norm_eps).astype(x.dtype),
                     lw["wo"], **cfg)
    return out, state, tail


def _inner_norms(rbc, lw, spec: ModelSpec):
    """The selective scan's three inner RMS norms, each with its weights:
    [r ; B ; C] (..., R + 2N) float32 -> (r, B, C)."""
    n, r, eps = spec.ssm_d_state, spec.ssm_dt_rank, spec.norm_eps
    return (rmsnorm(rbc[..., :r], lw["rms_dt"], eps),
            rmsnorm(rbc[..., r:r + n], lw["rms_b"], eps),
            rmsnorm(rbc[..., r + n:], lw["rms_c"], eps))


def _selective_block(x, lw, spec: ModelSpec, state, tail, rows: SegmentRows,
                     cfg):
    """Selective-scan (Mamba-1) mixer under a norm on its INPUT: ONE input
    projection [z ; x] -> causal depthwise convolution (+ bias) and SiLU on
    x ALONE -> from the CONVOLVED x the thin projection [r ; B ; C], an RMS
    norm with weights on each of the three, the step dt = softplus(W_dt r +
    dt_bias) a channel -> the scan, whose decay exp(dt A) is a number a
    (state index, channel) pair -> the skip D x -> gate -> output projection
    (NO norm before it). _delta_block's contract."""
    from ..ops.pallas_selective_scan import selective_scan

    inner, f32 = spec.ssm_inner, jnp.float32
    with jax.named_scope("ssm_proj"):
        z, xs = _gate_and_x(rmsnorm(x, lw["rms_att"], spec.norm_eps), lw,
                            inner, cfg)
    with jax.named_scope("ssm_conv"):
        y, tail = _short_conv(xs, tail, lw, rows, spec.ssm_conv_width)
    with jax.named_scope("ssm_dt"):
        low, bm, cm = _inner_norms(
            matmul(y.astype(x.dtype), lw["wxp"], **cfg).astype(f32), lw, spec)
        dt = jax.nn.softplus(
            matmul(low.astype(x.dtype), lw["wdt"], **cfg).astype(f32)
            + lw["dt_bias"])
    with jax.named_scope("ssm_scan"):
        o, state = selective_scan(
            y, dt, -jnp.exp(lw["a_log"]), bm, cm, state, rows.n_valid,
            rows.fresh, rows.slots, rows.chained,
            use_pallas=bool(cfg.get("use_pallas")),
            interpret=cfg.get("pallas_interpret", False))
        o = o + lw["ssm_d"] * y
    with jax.named_scope("ssm_out"):
        out = matmul((o * jax.nn.silu(z.astype(f32))).astype(x.dtype),
                     lw["wo"], **cfg)
    return out, state, tail


def _delta_mixer(x, lw, spec: ModelSpec, *rest):
    """A DELTA layer's mixer by the width of its decay (the spec's data):
    a scalar a head, or a vector over the key channels (KDA)."""
    block = _kda_block if spec.lin_vector_decay else _delta_block
    return block(x, lw, spec, *rest)


def _ssm_mixer(x, lw, spec: ModelSpec, *rest):
    """An SSM layer's mixer by the rank of its step (the spec's data): 0,
    Mamba-2 (a scalar decay a head); R > 0, Mamba-1's selective scan."""
    block = _selective_block if spec.ssm_selective else _ssm_block
    return block(x, lw, spec, *rest)


# a state layer's mixer by its kind; all keep _delta_block's contract
_STATE_MIXERS = {LayerKind.DELTA: _delta_mixer, LayerKind.SSM: _ssm_mixer}
# the kinds whose mixer follows a slot map: its kernel hands a row's final
# state to the row that continues it (ssd_chunk and selective_scan_chunk
# do; delta_rule_chunk not)
_CHAINING_MIXERS = frozenset({LayerKind.SSM})


def takes_slot_map(spec: ModelSpec, meshed: bool) -> bool:
    """Whether the rows of a chunk program may follow a slot map (forward's
    `slots`), so that consecutive rows prefill ONE slot: rows that are not
    sharded (no mesh: dp splits the rows, pp and sp trace other regions),
    every cache leaf the dense K/V cache that `kv_cache_write` and
    `flash_attention` address by slot (not the latent one), and every state
    layer of a kind whose mixer chains. From the layer kinds and the mesh,
    never a model's name: the ONE place the rule is written
    (Engine.prefill_rows_per_slot, forward, tools/rehearse_chip_compile)."""
    return (not meshed and not spec.is_mla
            and all(k in _CHAINING_MIXERS
                    for k in spec.layer_kinds if k.has_state))


def _scaled(out, spec: ModelSpec):
    """A sublayer's output times the published residual multiplier (1: the
    output itself, and nothing enters the program)."""
    if spec.residual_scale == 1.0:
        return out
    return out * jnp.asarray(spec.residual_scale, out.dtype)


def _pre_norm_tail(x, mix_out, lw, spec: ModelSpec, cfg, n_valid=None,
                   moe_counts=None):
    """h = x + r mixer(norm(x)) (the mixer normed its own input); x' = h +
    r ffn(norm(h)), the FFN dense or, where the layer has a router, the
    routed experts (and the shared one, inside _moe_ffn)."""
    with jax.named_scope("block_tail"):
        x = x + _scaled(mix_out, spec).astype(x.dtype)
    ffn = _normed_ffn(x, lw, spec, cfg, n_valid, moe_counts)
    with jax.named_scope("block_tail"):
        return x + _scaled(ffn, spec).astype(x.dtype)


def _post_norm_tail(x, mix_out, lw, spec: ModelSpec, cfg):
    """h = x + norm(mixer(x)); x' = h + norm(ffn(h)): both norms on the
    sublayers' outputs."""
    eps = spec.norm_eps
    with jax.named_scope("block_tail"):
        x = x + rmsnorm(mix_out, lw["rms_att"], eps).astype(x.dtype)
    ffn = _dense_ffn(x, lw, spec, cfg)
    with jax.named_scope("block_tail"):
        return x + rmsnorm(ffn, lw["rms_ffn"], eps).astype(x.dtype)


def _state_layer(x, lw, spec: ModelSpec, kind: LayerKind, state, tail, rows,
                 cfg, moe_counts=None):
    """A layer that keeps a state: the mixer by the layer's KIND, the block
    around it (norm placement, FFN kind, residual scale) by the spec, the
    two chosen apart."""
    mix_out, state, tail = _STATE_MIXERS[kind](x, lw, spec, state, tail,
                                               rows, cfg)
    if spec.post_norm:
        return _post_norm_tail(x, mix_out, lw, spec, cfg), state, tail
    return (_pre_norm_tail(x, mix_out, lw, spec, cfg, rows.n_valid,
                           moe_counts), state, tail)


def _normed_ffn(x, lw, spec: ModelSpec, cfg, n_valid=None, moe_counts=None):
    """A pre-norm block's FFN sublayer: the norm of the residual stream,
    then the FFN dense or, where the layer has a router, the routed experts
    (and the shared one, inside _moe_ffn)."""
    with jax.named_scope("ffn"):
        xb = rmsnorm(x, lw["rms_ffn"], spec.norm_eps)
    if "moe_router" in lw:
        return _moe_ffn(xb, lw, spec, cfg, n_valid, moe_counts)
    return _dense_ffn(xb, lw, spec, cfg)


def _dense_ffn(xb, lw, spec: ModelSpec, cfg):
    """SwiGLU FFN (ref: src/llama2-tasks.cpp:158-189)."""
    with jax.named_scope("ffn"):
        if "w13" in lw:
            # fused gate|up (single-shard path)
            h13 = matmul(xb, lw["w13"], **cfg)
            hd = h13.shape[-1] // 2
            gate, up = h13[..., :hd], h13[..., hd:]
        else:
            gate = matmul(xb, lw["w1"], **cfg)
            up = matmul(xb, lw["w3"], **cfg)
        hb = apply_hidden_act(gate, spec.hidden_act) * up
        return matmul(hb, lw["w2"], **cfg)


def _moe_ffn(xb, lw, spec: ModelSpec, cfg, n_valid=None, counts=None):
    """Top-k routed expert FFN (ref: src/grok1-tasks.cpp:56-227).

    Router/top-k runs replicated (the reference runs it root-only and
    broadcasts — ref: grok1-tasks.cpp:121-126). A (token, chosen expert)
    pair is LIVE when the token is real (token j of row b iff j <
    n_valid[b], SegmentRows; None: every token) and the expert is held
    here. Where the expert stacks are the in-place kernel's
    (ops/matmul.reads_experts_in_place: the served step programs), the
    live pairs alone are computed, grouped by expert, one kernel call a
    projection (_grouped_experts); a pad or gated token's routed output is
    then zero, and nothing reads it. Anything else slices an expert at a
    time (_expert_matmul): one row (B == T == 1) computes its active
    experts, every other shape all held experts for every row, masked.
    All compile to static shapes and give a live token the same bits.

    counts: a list that receives this layer's (expert_reads, expert_pairs,
    expert_tiles) — distinct held experts with a live pair, live pairs, and
    the row tiles the grouped call ran over (_pair_tiles' `used`, 0 where
    no grouped call runs: tiles over reads is the row tiles one unpack of
    an expert serves where the kernel runs stationary,
    ops/pallas_q40._unpacks_once) — as int32 scalars (forward sums them for
    the step programs' counters). A program whose token rows fit ONE row
    tile (the served decode step) sends no third count: a group there is
    one tile, the tiles ARE the reads, and the program keeps the text it
    had.
    """
    b, t, d = xb.shape
    k_active = spec.n_active_experts

    # experts held here: all the router's, or (SARVAM_MLA) the share
    # [expert_offset, expert_offset + n_experts) of a wider router, whose
    # other experts live on other chips and add nothing here
    held_share = spec.router_width != spec.n_experts
    with jax.named_scope("moe_router"):
        router_logits = matmul(xb, lw["moe_router"], **cfg)  # (B, T, E)
        if "moe_bias" in lw:
            # sigmoid scores; the bias picks the experts and stays out of
            # the weights, which are the scores normalised over ALL the
            # chosen (held or not) times the routed scaling factor
            probs = jax.nn.sigmoid(router_logits.astype(jnp.float32))
            _, top_idx = lax.top_k(probs + lw["moe_bias"], k_active)
            top_p = jnp.take_along_axis(probs, top_idx, axis=-1)
            weights = (top_p / top_p.sum(axis=-1, keepdims=True)
                       * spec.routed_scaling)
        else:
            probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
            top_p, top_idx = lax.top_k(probs, k_active)           # (B, T, K)
            weights = top_p / top_p.sum(axis=-1, keepdims=True)   # ref: grok1-tasks.cpp:99-114

    in_place = reads_experts_in_place(lw["moe_up"], b * t, **cfg)
    if in_place or counts is not None:
        with jax.named_scope("moe_routed"):
            held = top_idx - (spec.expert_offset if held_share else 0)
            live = (held >= 0) & (held < spec.n_experts)          # (B, T, K)
            if n_valid is not None:
                live &= (jnp.arange(t)[None, :]
                         < n_valid[:, None])[..., None]
            # (B T K, E): pair p is live and its expert is held expert e
            member = ((held[..., None] == jnp.arange(spec.n_experts))
                      & live[..., None]).reshape(-1, spec.n_experts)
            sizes = member.sum(axis=0, dtype=jnp.int32)           # (E,)
            if counts is not None:
                counts.append([jnp.sum(sizes > 0, dtype=jnp.int32),
                               sizes.sum()])

    def count_tiles(tiles):
        if counts is not None and b * t > _pair_layout(spec, b * t)[0]:
            counts[-1].append(tiles)

    def scatter_weights():
        # (B, T, E) dense scatter of the normalized top-k weights (0 for
        # inactive experts) — shared by the ep and dense-prefill paths;
        # cut to the held experts' columns where this chip holds a share
        dense = jnp.zeros_like(probs).at[
            jnp.arange(b)[:, None, None],
            jnp.arange(t)[None, :, None],
            top_idx,
        ].set(weights)
        if held_share:
            dense = dense[..., spec.expert_offset:
                          spec.expert_offset + spec.n_experts]
        return dense

    from ..parallel.ep_moe import EpRowWeight, ep_moe_ffn

    def ep_experts():
        # expert-parallel placement (ep mesh axis): each ep shard computes
        # only its local experts, masked by the scattered routing weights
        e_weights = scatter_weights()
        if cfg.get("manual_tp"):
            # already inside a fully-manual region (pp — parallel/pp.py):
            # shard_map cannot nest, so the ep body runs directly with the
            # region's manual (ep, tp) axes
            from ..parallel.ep_moe import _ep_body

            return _ep_body(
                xb, e_weights, lw["moe_up"].w, lw["moe_gate"].w,
                lw["moe_down"].w,
                ep=cfg.get("manual_ep") or 1, tp=cfg["manual_tp"],
                act_fn=lambda g: apply_hidden_act(g, spec.hidden_act),
                compute_dtype=cfg["compute_dtype"],
                use_pallas=cfg.get("use_pallas", False),
                interpret=cfg.get("pallas_interpret", False),
                reduce=cfg.get("tp_reduce", "exact"),
            ).astype(xb.dtype)
        return ep_moe_ffn(
            xb, e_weights, lw, cfg["tp_mesh"],
            act_fn=lambda g: apply_hidden_act(g, spec.hidden_act),
            compute_dtype=cfg["compute_dtype"],
            use_pallas=cfg.get("use_pallas", False),
            interpret=cfg.get("pallas_interpret", False),
            reduce=cfg.get("tp_reduce", "exact"),
        ).astype(xb.dtype)

    if isinstance(lw["moe_up"], EpRowWeight):
        with jax.named_scope("moe_routed"):
            count_tiles(jnp.int32(0))
            return ep_experts()

    def expert_apply(e):
        gate = _expert_matmul(xb, lw["moe_gate"], e, cfg)
        up = _expert_matmul(xb, lw["moe_up"], e, cfg)
        hb = apply_hidden_act(gate, spec.hidden_act) * up
        return _expert_matmul(hb, lw["moe_down"], e, cfg)

    def with_shared(acc):
        # the shared expert(s): every token, unweighted, every chip alike
        if "sh_w1" not in lw:
            return acc
        with jax.named_scope("moe_shared"):
            return acc + _dense_ffn(xb, {"w1": lw["sh_w1"], "w2": lw["sh_w2"],
                                         "w3": lw["sh_w3"]}, spec, cfg)

    def sliced_experts():
        acc = jnp.zeros((b, t, d), xb.dtype)
        if t == 1 and b == 1 and not held_share:
            # one row: only its K active experts, by their traced indices
            # (the reference likewise computes just the active experts —
            # grok1-tasks.cpp:128-143)
            idx = top_idx.reshape(k_active)
            for ae in range(k_active):  # K is tiny and static — unrolled
                out = expert_apply(idx[ae])
                acc = acc + weights[..., ae, None].astype(out.dtype) * out
            return acc
        # every other shape: every held expert for every row, masked by
        # the routing weights
        e_weights = scatter_weights()
        for e in range(spec.n_experts):
            out = expert_apply(e)
            acc = acc + e_weights[..., e, None].astype(out.dtype) * out
        return acc

    with jax.named_scope("moe_routed"):
        acc, tiles = (_grouped_experts(xb, lw, spec, cfg, held, live, member,
                                       sizes, weights)
                      if in_place else (sliced_experts(), jnp.int32(0)))
        count_tiles(tiles)
    return with_shared(acc)


def _grouped_experts(xb, lw, spec: ModelSpec, cfg, held, live, member, sizes,
                     weights):
    """The routed experts' weighted sum over the step's LIVE (token,
    expert) pairs alone, one ops/pallas_q40.q40_expert_matmul call a
    projection. held, live (B, T, K): each pair's expert among those held
    here and whether it counts; member (B T K, E) one-hot of the live
    pairs; sizes (E,) their count an expert. Returns the sum and the row
    tiles it ran over (_pair_tiles' `used`).

    The pairs are laid out by expert in row tiles (_pair_tiles;
    expert_row_tile sizes the tile): an expert's group starts on a tile and
    keeps its tokens' order, so a tile belongs to one expert and the used
    tiles come first, in ascending expert order. rows x K / tile + E tiles
    hold ANY routing (an expert adds at most one ragged tile); the buffer
    the projections run over holds the tiles an EVEN routing needs
    (`wave`), and a step whose routing needs more runs again over the next
    tiles: one wave where every expert is held (Mixtral, Grok-1: the two
    numbers are one), seldom a second where a share of a wide router is (a
    wave is then an eighth of what any routing needs, and what XLA does to
    the buffer costs by its rows). The gate and up projections never see
    the buffer filled: they take the TOKEN rows and the wave's slice of
    `src`, and the kernel gathers a used tile's rows itself
    (q40_expert_matmul's `src`), so their input is prepared once a token
    row; `hb` is born in the layout and the down projection reads it so.

    A pair's three projections and its Q80 round trips are a row's own, so
    a live token's contributions carry the bits the all-experts loop gives
    them, and they are summed as it sums them: in ascending expert order
    (so wave after wave), in the activations' dtype; its other experts add
    exact zeros there and nothing here."""
    from ..quants.jax_codec import dequantize_q80_jax, quantize_q80_jax

    b, t, d = xb.shape
    rows, k = b * t, spec.n_active_experts
    tile, n_tiles, wave = _pair_layout(spec, rows)
    dest, src, tile_expert, used = _pair_tiles(held, live, member, sizes,
                                               tile, n_tiles)

    x = xb.reshape(rows, d)
    if cfg["activation_q80"]:  # once a token row, not once a pair
        with jax.named_scope("act_q80"):
            x = dequantize_q80_jax(*quantize_q80_jax(x),
                                   dtype=cfg["compute_dtype"])
    # a token's K pairs in ascending expert order
    order = jnp.argsort(held, axis=-1)                            # (B, T, K)
    dest, live, weights = (jnp.take_along_axis(a, order, axis=-1)
                           for a in (dest, live, weights))

    def run_wave(w, acc):
        first = w * wave
        grouped = dict(cfg, used=jnp.clip(used - first, 0, wave),
                       token_rows=rows)
        experts = lax.dynamic_slice(tile_expert, (first,), (wave,))
        # gate and up read the TOKEN rows: the call gathers a used tile's
        # rows itself, by the wave's slice of src
        once = dict(grouped, activation_q80=False, src=lax.dynamic_slice(
            src, (first * tile,), (wave * tile,)))
        gate = fused_expert_matmul(x, lw["moe_gate"], experts, **once)
        up = fused_expert_matmul(x, lw["moe_up"], experts, **once)
        hb = apply_hidden_act(gate, spec.hidden_act) * up
        out = fused_expert_matmul(hb, lw["moe_down"], experts, **grouped)
        # a pair outside this wave, or dead, adds nothing: its row may
        # never have been written, so it is selected out, not zeroed
        at = dest - first * tile
        here = live & (at >= 0) & (at < wave * tile)
        at = jnp.clip(at, 0, wave * tile - 1)
        for j in range(k):  # K is tiny and static — unrolled
            o = jnp.where(here[..., j, None], out[at[..., j]], 0)  # (B, T, d)
            acc = acc + weights[..., j, None].astype(o.dtype) * o
        return acc

    acc = jnp.zeros((b, t, d), xb.dtype)
    if wave == n_tiles:
        return run_wave(0, acc), used
    return lax.fori_loop(0, -(-used // wave), run_wave, acc), used


def _pair_layout(spec: ModelSpec, rows: int) -> tuple[int, int, int]:
    """(tile, n_tiles, wave) of _grouped_experts for a program of `rows`
    token rows, from the program's shape alone: the rows of a row tile
    (ops/pallas_q40.expert_row_tile of an even routing's group), the tiles
    that hold ANY routing of rows x K pairs over the held experts (a whole
    number of waves), and the tiles one pass of the three projections runs
    over: what an EVEN routing needs plus a ragged tile an expert."""
    from ..ops.pallas_q40 import expert_row_tile

    k, n_e, width = spec.n_active_experts, spec.n_experts, spec.router_width
    tile = expert_row_tile(rows * k / width)
    n_tiles = min(rows * k // tile + n_e, n_e * -(-rows // tile))
    wave = min(n_tiles, n_e + -(-rows * k * n_e // (width * tile)))
    return tile, -(-n_tiles // wave) * wave, wave


def _pair_tiles(held, live, member, sizes, tile: int, n_tiles: int):
    """Where _grouped_experts puts each (token, expert) pair, from the
    pairs' held expert and liveness (B, T, K), their one-hot (B T K, E) and
    the groups' sizes (E,). Returns dest (B, T, K): the pair's row in the
    buffer of n_tiles x tile rows — its expert's first tile, then its rank
    among the expert's live pairs in token order — and n_tiles x tile, past
    the end, for a dead pair; src (n_tiles x tile,): the token whose
    activations a buffer row holds (token 0 where no pair lands: computed
    if its tile is used, never read); tile_expert (n_tiles,): each tile's
    expert, in ascending order over the used tiles, which come first;
    used: how many they are. A tile is named for an expert only if a live
    pair of that expert lies in it."""
    n_e = sizes.shape[0]
    e_of = jnp.clip(held, 0, n_e - 1).reshape(-1)                 # (P,)
    member = member.astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(member, axis=0) - member,
                               e_of[:, None], axis=1)[:, 0]
    tiles_of = -(-sizes // tile)
    tile_end = jnp.cumsum(tiles_of)
    dest = jnp.where(live.reshape(-1),
                     ((tile_end - tiles_of) * tile)[e_of] + rank,
                     n_tiles * tile)
    token = jnp.arange(dest.shape[0], dtype=jnp.int32) // held.shape[-1]
    src = jnp.zeros((n_tiles * tile,), jnp.int32).at[dest].set(
        token, mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
        n_e - 1).astype(jnp.int32)
    return dest.reshape(held.shape), src, tile_expert, tile_end[-1]


def _expert_matmul(x, w, e, cfg):
    """x @ W[e]^T against a stacked (E, d, n) leaf the in-place kernel
    cannot take, `e` traced or a Python integer: the expert is sliced out
    (_take_expert) for the leaf's own matmul."""
    return matmul(x, _take_expert(w, e), **cfg)


def _take_expert(w, e):
    """Select expert e from a stacked (E, ...) weight: an expert-sized copy
    in HBM before the matmul may read it, so only what the in-place kernel
    cannot take comes here (_moe_ffn) — tp wrappers (for TpColWeight
    the expert axis sits behind the tp stack axis), unquantised stacks,
    the XLA dequant path and segments of more than pallas_q40.MAX_T rows."""
    from ..parallel.tp_q80 import TpColWeight, TpRowWeight, take_expert_col

    if isinstance(w, TpColWeight):
        return take_expert_col(w, e)
    if isinstance(w, TpRowWeight):
        return TpRowWeight(_take_expert(w.w, e))
    if isinstance(w, QuantizedTensor):
        return QuantizedTensor(
            lax.dynamic_index_in_dim(w.packed, e, axis=0, keepdims=False),
            lax.dynamic_index_in_dim(w.scales, e, axis=0, keepdims=False),
        )
    return lax.dynamic_index_in_dim(w, e, axis=0, keepdims=False)


def _layer(x, lw, spec: ModelSpec, k_cache, v_cache, q_pos, cfg, sp_mesh=None,
           sp_cache_mesh=None, per_row_pos=False, write_gate=None,
           n_valid=None, moe_counts=None, slots=None):
    if spec.is_mla:
        assert slots is None, "a slot map over the latent cache"
        # pre-norm serial block over latent attention; the FFN is dense in
        # the leading layers (w1 or fused w13 present) and experts after
        assert sp_mesh is None and sp_cache_mesh is None
        attn_out, k_cache = _mla_attention_block(
            x, lw, spec, k_cache, q_pos, cfg, per_row_pos=per_row_pos,
            write_gate=write_gate)
        with jax.named_scope("block_tail"):
            x = x + attn_out.astype(x.dtype)
        ffn = _normed_ffn(x, lw, spec, cfg, n_valid, moe_counts)
        with jax.named_scope("block_tail"):
            return x + ffn.astype(x.dtype), k_cache, None
    attn_out, k_cache, v_cache = _attention_block(
        x, lw, spec, k_cache, v_cache, q_pos, cfg, sp_mesh=sp_mesh,
        sp_cache_mesh=sp_cache_mesh, per_row_pos=per_row_pos,
        write_gate=write_gate, slots=slots)

    if spec.post_norm:
        return (_post_norm_tail(x, attn_out, lw, spec, cfg), k_cache,
                v_cache)
    if spec.arch == ArchType.GROK1:
        # post-attention norm BEFORE residual add (ref: grok1-tasks.cpp:16-41)
        with jax.named_scope("block_tail"):
            x = x + rmsnorm(attn_out, lw["rms_ffn"]).astype(x.dtype)
        with jax.named_scope("ffn"):
            xb = rmsnorm(x, lw["rms_moe"])      # ref: grok1-tasks.cpp:43-54
        moe_out = _moe_ffn(xb, lw, spec, cfg, n_valid, moe_counts)
        with jax.named_scope("block_tail"):
            # ref: grok1-tasks.cpp:244-256
            moe_out = rmsnorm(moe_out, lw["rms_ffn2"])
            return x + moe_out.astype(x.dtype), k_cache, v_cache
    # LLAMA (dense; ref: llama2-tasks.cpp:125-131), MIXTRAL (experts; ref:
    # mixtral-tasks.cpp:24), GRANITE_HYBRID (experts, a shared one, the
    # residual multiplier): one pre-norm serial block, told apart by what
    # the layer holds and the spec says
    return (_pre_norm_tail(x, attn_out, lw, spec, cfg, n_valid, moe_counts),
            k_cache, v_cache)


def forward(
    params: dict,
    spec: ModelSpec,
    tokens: jnp.ndarray,   # (B, T) int32
    pos0: jnp.ndarray,     # int32 first absolute position of the segment —
                           # scalar (shared) or (B,) per-sequence (batched
                           # generation with ragged prompt lengths)
    cache: KVCache,
    *,
    activation_q80: bool = False,
    compute_dtype=jnp.float32,
    logits_for_all: bool = False,
    use_pallas: bool = False,
    sp_mesh=None,
    tp_mesh=None,
    tp_reduce: str = "exact",
    pallas_interpret: bool = False,
    sp_cache_mesh=None,
    pp_mesh=None,
    pp_gpipe: bool = True,
    logit_index=None,
    vocab_mesh=None,
    vocab_axes: tuple = ("tp",),
    expert_counts: bool = False,
    slots: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache]:
    """Run T tokens through the model; returns (logits, updated cache).

    logits: (B, vocab) for the last token (or position `logit_index` if
    given — scalar or (B,) per-sequence, used when the segment is
    right-padded), or (B, T, vocab) if logits_for_all.
    sp_mesh: a Mesh whose sp axis shards this segment's sequence — enables the
    ring-attention prefill path (segment must start at pos 0).
    tp_mesh: a Mesh for the explicit shard_map TP paths (weights marked as
    TpRowWeight/TpColWeight; Pallas kernels per shard, col partial sums
    reduced per tp_reduce — see parallel/tp_q80.py).
    sp_cache_mesh: a Mesh whose sp axis shards the KV cache's sequence dim
    (cache_pspec(sp=True)) — cache writes keep that sharding and attention
    reads it chunk-wise (parallel/ring_attention.py:sp_cache_attention).
    pp_mesh: a Mesh whose pp axis places the layers in stages — params
    "layers" must be stage-stacked (parallel/pp.py:stack_stages) and the
    cache stage-stacked (KVCache.create(pp=...)).
    vocab_mesh: a Mesh whose `vocab_axes` row-split the embedding table's
    vocab dim (ops/sharded_vocab.py) — the lookup becomes a masked local
    gather + all-reduce, bit-identical to the replicated gather (zeros +
    one real contribution add exactly). The head (wcls) is row-split by
    its PartitionSpec independently of this knob.
    expert_counts: also return, third, int32 (3,): the distinct held experts
    some real token chose, the live (token, expert) pairs and the row tiles
    the grouped call lays them out in, summed over the MoE layers (_moe_ffn;
    the served step programs' window counters); (2,), without the tiles,
    from a program whose token rows fit one row tile.
    slots: (B,) int32, the cache slot row b reads and writes at pos0[b]
    (per-row positions, where takes_slot_map allows it). Rows of one slot
    at consecutive segments prefill that slot several segments a program:
    a row attends what the rows before it wrote, and in a state layer
    starts from the final state and tail of the row it continues
    (SegmentRows.chained); every other op is independent across rows, so
    the slot's logits are those of the same segments one a program, bit
    for bit. None: row b is slot b.
    """
    assert slots is None or takes_slot_map(
        spec, any(m is not None for m in (pp_mesh, tp_mesh, sp_mesh,
                                          sp_cache_mesh))), (
        "a slot map over a mesh, the latent cache or a mixer that does not "
        "chain")
    cfg = dict(activation_q80=activation_q80, compute_dtype=compute_dtype,
               use_pallas=use_pallas, tp_mesh=tp_mesh, tp_reduce=tp_reduce,
               pallas_interpret=pallas_interpret)
    b, t = tokens.shape

    with jax.named_scope("embed"):
        if vocab_mesh is not None:
            from ..ops.sharded_vocab import embed_tokens_sharded

            x = embed_tokens_sharded(params["tok_emb"], tokens, vocab_mesh,
                                     tuple(vocab_axes), compute_dtype)
        else:
            # ref: tasks.cpp:202-203
            x = params["tok_emb"][tokens].astype(compute_dtype)
        if spec.arch == ArchType.GROK1:
            x = x * GROK_INPUT_SCALE
        if spec.embedding_scale != 1.0:
            x = x * jnp.asarray(spec.embedding_scale, x.dtype)

    s_all: list = []
    conv_all: list = []
    moe_counts: list | None = [] if expert_counts else None
    per_row_pos = getattr(pos0, "ndim", 0) == 1
    with jax.named_scope("embed"):   # the segment's positions, with its rows
        if per_row_pos:
            q_pos = pos0[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        else:
            q_pos = pos0 + jnp.arange(t, dtype=jnp.int32)[None, :]
            q_pos = jnp.broadcast_to(q_pos, (b, t))

    if pp_mesh is not None:
        # layers placed in stages over pp (parallel/pp.py): long segments
        # (prefill chunks) take the GPipe sequence-microbatch schedule —
        # flop-bound, wall ~ 1/pp of the all-stages scheme; decode/verify
        # segments (weight-read-bound) keep all-stages
        from ..parallel.pp import (gpipe_microbatches, pp_layers,
                                   pp_layers_gpipe)

        n_mb = (gpipe_microbatches(t, pp_mesh.shape["pp"])
                if pp_gpipe else 1)
        if n_mb > 1:
            x, k_all, v_all = pp_layers_gpipe(
                x, params["layers"], spec, cache, q_pos, cfg, pp_mesh,
                n_mb, per_row_pos=per_row_pos)
        else:
            x, k_all, v_all = pp_layers(x, params["layers"], spec, cache,
                                        q_pos, cfg, pp_mesh,
                                        per_row_pos=per_row_pos)
        k_all, v_all = list(k_all), list(v_all)
    else:
        # statically unrolled layer loop (see module docstring for why not
        # scan); a layer takes the leaves of its KIND
        k_all = []
        v_all = []
        kinds, at = spec.layer_kinds, spec.cache_index
        with jax.named_scope("embed"):
            rows = (_segment_rows(spec, cache, pos0, b, t, logit_index,
                                  logits_for_all, slots)
                    if spec.has_state or spec.is_moe else None)
        for l in range(spec.n_layers):
            if kinds[l].has_state:
                x, s_new, c_new = _state_layer(
                    x, params["layers"][l], spec, kinds[l], cache.s[at[l]],
                    cache.conv[at[l]], rows, cfg, moe_counts)
                s_all.append(s_new)
                conv_all.append(c_new)
                continue
            x, k_new, v_new = _layer(x, params["layers"][l], spec,
                                     cache.k[at[l]],
                                     cache.v[at[l]] if cache.v else None,
                                     q_pos, cfg,
                                     sp_mesh=sp_mesh,
                                     sp_cache_mesh=sp_cache_mesh,
                                     per_row_pos=per_row_pos,
                                     n_valid=(None if rows is None
                                              else rows.n_valid),
                                     moe_counts=moe_counts, slots=slots)
            k_all.append(k_new)
            if v_new is not None:
                v_all.append(v_new)

    with jax.named_scope("head"):
        # ref: llama2-tasks.cpp:222-234
        x = rmsnorm(x, params["rms_final"], spec.norm_eps)
        if not logits_for_all:
            if logit_index is None:
                x = x[:, -1, :]
            else:
                x = jnp.take_along_axis(
                    x, jnp.broadcast_to(logit_index.reshape(-1, 1, 1),
                                        (x.shape[0], 1, x.shape[-1])),
                    axis=1)[:, 0]
        logits = matmul(x, params["wcls"], **cfg).astype(jnp.float32)
        if spec.arch == ArchType.GROK1:
            logits = logits * GROK_LOGIT_SCALE  # ref: grok1-tasks.cpp:269-272
        if spec.logit_scale != 1.0:
            logits = logits * spec.logit_scale
    cache = KVCache(tuple(k_all), tuple(v_all), tuple(s_all), tuple(conv_all))
    if expert_counts:
        # (a pp region's layers, traced elsewhere, are not counted)
        with jax.named_scope("moe_routed"):
            return logits, cache, jnp.asarray(
                [sum(c) for c in zip(*moe_counts)], jnp.int32)
    return logits, cache


def _segment_rows(spec: ModelSpec, cache: KVCache, pos0, b: int, t: int,
                  logit_index, logits_for_all: bool,
                  slots=None) -> SegmentRows:
    """SegmentRows of one forward, from what every caller already passes:
    the rows' first positions (a row at the context's end is gated, as
    for the cache writes), in a right-padded segment logit_index, and the
    slot map where the model has a state for it to reach."""
    gate_at = cache.k[0].shape[2] if cache.k else spec.seq_len
    pos_rows = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (b,))
    n_tok = jnp.full((b,), t, jnp.int32)
    if logit_index is not None and not logits_for_all:
        n_tok = jnp.broadcast_to(
            jnp.asarray(logit_index, jnp.int32) + 1, (b,))
    n_valid = jnp.where(pos_rows < gate_at, n_tok, 0)
    fresh = (pos_rows == 0) & (n_valid > 0)
    if slots is None or not spec.has_state:
        return SegmentRows(n_valid, fresh)
    from ..ops.pallas_ssd import chained_rows, last_rows

    chained = chained_rows(slots, n_valid)
    return SegmentRows(n_valid, fresh, slots, chained,
                       last_rows(chained, n_valid))
