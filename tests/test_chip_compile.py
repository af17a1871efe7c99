"""Compile the main path's kernels and step programs at REAL widths for a
described (not attached) TPU v5e:2x2 — what the chip's compiler refuses,
it refuses here, at no chip time (on-chip-measurement guide, section 2;
the whole-model, full-depth version is tools/rehearse_chip_compile.py).

Interpret-mode tests cannot see these failures: before this file existed,
every tp=4 row shard whose row count no tile divides (2752, 8000, 32064
rows) was one whole-weight block that overflowed scoped VMEM on silicon
while every interpret test passed.

ONE file, topology described inside a module-scoped fixture — only the
xdist worker that is handed this file loads libtpu, and every worker
collects the same tests. Nothing here runs on a device, so nothing here
is a result or a time.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one (every rerun would warn and
    # recompile): keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4(topo):
    from distributed_llama_tpu.parallel.mesh import make_mesh

    return make_mesh(tp=4, devices=topo.devices)


@pytest.fixture(scope="module")
def served_moe_step(topo):
    """(spec, args, lowered, compiled) of an MoE configuration's step program
    AS SERVED (B=8, the Q80 activation round trip on; `mixtral-8x7b-12l`
    at its 12 layers and S=4096, `sarvam-105b-ep8` at all 32 and S=8192),
    compiled once for the tests that read it."""
    import rehearse_chip_compile as r

    served = {"mixtral_8x7b_12l": (dataclasses.replace(r.MIXTRAL_8X7B,
                                                       n_layers=12), 4096),
              "sarvam_105b_ep8": (r.SARVAM_105B_EP8, 8192)}
    made = {}

    def step(model, t):
        if (model, t) not in made:
            spec, seq_len = served[model]
            fn, args = r.abstract_step(spec, topo.devices, batch=8, t=t,
                                       seq_len=seq_len, q80=True)
            lowered = fn.lower(*args)
            made[model, t] = (spec, args, lowered, lowered.compile())
        return made[model, t]

    return step


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: _struct(s.shape, s.dtype, sharding), tree)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _cache_of(args):
    """The KVCache among a step program's abstract arguments (the chunk
    with a slot map takes the map after it)."""
    return next(a for a in args if hasattr(a, "conv"))


# Llama-2-7B weight shapes (d rows, n contraction): w1 = w3, w2, wq = wo,
# wcls; t = 1 is decode, t = 256 (= MAX_T) the widest kernel prefill
@pytest.mark.parametrize("d,n,t", [
    (11008, 4096, 1), (11008, 4096, 256),      # w1 / w3
    (4096, 11008, 1), (4096, 11008, 256),      # w2
    (4096, 4096, 1),                           # wq / wo
    (32000, 4096, 1), (32000, 4096, 256),      # wcls
])
def test_q40_matmul_compiles_at_7b_widths(one_chip, d, n, t):
    from rehearse_chip_compile import q40_struct

    from distributed_llama_tpu.ops.pallas_q40 import q40_matmul

    w = _placed(q40_struct(d, n), one_chip)
    x = _struct((t, n), BF16, one_chip)
    c = jax.jit(lambda x, w: q40_matmul(x, w, out_dtype=BF16)).lower(
        x, w).compile()
    assert _has_kernel(c)


# one expert for every row of x (a scalar `e`): 1, 8 and 256 (= MAX_T) rows
@pytest.mark.parametrize("t", [1, 8, 256])
@pytest.mark.parametrize("d,n", [(14336, 4096), (4096, 14336)])
def test_q40_expert_matmul_compiles_at_mixtral_widths(one_chip, d, n, t):
    from rehearse_chip_compile import q40_struct

    from distributed_llama_tpu.ops.pallas_q40 import q40_expert_matmul

    w = _placed(q40_struct(8, d, n), one_chip)   # moe_up/gate | moe_down
    x = _struct((t, n), BF16, one_chip)
    e = _struct((), jnp.int32, one_chip)
    c = jax.jit(lambda x, w, e: q40_expert_matmul(
        x, w, e, out_dtype=BF16)).lower(x, w, e).compile()
    assert _has_kernel(c)


# the served step programs' grouped calls: one pallas_call a projection
# over the row tiles of the step's (token, expert) pairs
@pytest.mark.parametrize("rows", [8, 256], ids=["decode8", "chunk256"])
@pytest.mark.parametrize("model,d,n", [
    ("mixtral_8x7b", 14336, 4096), ("mixtral_8x7b", 4096, 14336),
    ("sarvam_105b_ep8", 2048, 4096), ("sarvam_105b_ep8", 4096, 2048),
    ("kimi_linear_48b_ep4", 1024, 2304), ("kimi_linear_48b_ep4", 2304, 1024),
    ("granite_4_h_small_ep2", 768, 4096),
    ("granite_4_h_small_ep2", 4096, 768)])
def test_grouped_expert_tiles_compile(one_chip, model, d, n, rows):
    """`q40_expert_matmul` over the row tiles `_pair_layout` gives four MoE
    configurations' two step programs (Mixtral: 8 tiles of 8 rows and 16
    of 64; sarvam-105b-ep8: 16 of 8 and a wave of 32 of 16;
    kimi-linear-48b-a3b-ep4: 64 of 8 and a wave of 128 of 8;
    granite-4.0-h-small-ep2: 36 of 8 and a wave of 56 of 64), each tile's
    expert and the used count prefetched, under the operand feed the
    program's token rows decide. kimi's chunk calls (8-row tiles) run
    STATIONARY (`_unpacks_once`): their dequantised block, 5.2 MB of
    scratch at its gate's (1024, 1152) tile, compiles within the scoped
    VMEM `_q40_call` asks for. The gate / up shape (a `dim`-wide input)
    is called as `_grouped_experts` calls it since PR 50: the program's
    TOKEN rows and the row index `src` prefetched, the two whole float32
    panels (2 x 2 MB at 256 rows of 4096) resident beside the tile the
    kernel gathers into scratch; the down shape with its rows laid out."""
    import rehearse_chip_compile as r
    from rehearse_chip_compile import q40_struct

    from distributed_llama_tpu.models.transformer import _pair_layout
    from distributed_llama_tpu.ops.pallas_q40 import (_unpacks_once,
                                                      q40_expert_matmul)

    spec = getattr(r, model.upper())
    assert {d, n} == {spec.dim, spec.hidden_dim}
    tile, _, wave = _pair_layout(spec, rows)
    assert _unpacks_once(tile, rows) == (
        rows == 256 and model == "kimi_linear_48b_ep4")
    w = _placed(q40_struct(spec.n_experts, d, n), one_chip)
    gathers = n == spec.dim
    x = _struct((rows if gathers else wave * tile, n), BF16, one_chip)
    e = _struct((wave,), jnp.int32, one_chip)
    used = _struct((), jnp.int32, one_chip)
    src = _struct((wave * tile,), jnp.int32, one_chip)
    c = jax.jit(lambda x, w, e, used, src: q40_expert_matmul(
        x, w, e, used, out_dtype=BF16, token_rows=rows,
        src=src if gathers else None)).lower(x, w, e, used, src).compile()
    assert _has_kernel(c)


def _copy_results(compiled) -> list[str]:
    """The result types of a compiled module's `copy` instructions."""
    import re

    return sorted(re.findall(r"= (\w+\[[\d,]*\])\S* copy(?:-start)?\(",
                             compiled.as_text()))


def _with_repeat_forced(monkeypatch, make):
    """make() as the tree makes it and with `pltpu.repeat` forced on every
    shape (the kernels' traces are cached by shape: dropped on both sides)."""
    from distributed_llama_tpu.ops import pallas_q40 as q

    made = []
    for forced in (False, True):
        if forced:
            monkeypatch.setattr(q, "_spreads_on_mxu", lambda nb: False)
        q.q40_matmul.clear_cache()
        q.q40_expert_matmul.clear_cache()
        # ONE call site: a kernel's text holds the stack it was traced under
        made.append(make())
    monkeypatch.undo()
    q.q40_matmul.clear_cache()
    q.q40_expert_matmul.clear_cache()
    return made


# the narrow down projections of one chip's programs:
# granite-4.0-h-small-ep2's 36 held experts (24 scale blocks a row: spread
# on the MXU; 36 tiles of 8 rows in the decode step, a wave of 56 of 64 in
# a chunk) and its shared expert (48 blocks, 8 and 256 rows);
# sarvam-105b-ep8's 16 held experts and its shared expert (64 blocks):
# `pltpu.repeat` wins or ties those (PERF.md section 6, PR 40)
@pytest.mark.parametrize("experts,n,tile,n_tiles,rows", [
    (36, 768, 8, 36, 8), (36, 768, 64, 56, 256),
    (None, 1536, 8, 1, 8), (None, 1536, 256, 1, 256),
    (16, 2048, 8, 16, 8), (16, 2048, 16, 32, 256),
    (None, 2048, 8, 1, 8), (None, 2048, 256, 1, 256)])
def test_narrow_down_projections_compile_with_their_spread(
        one_chip, monkeypatch, experts, n, tile, n_tiles, rows):
    """Both kernels at 4096 rows over a 768-, 1536- and 2048-wide
    contraction. Where the shape takes the MXU spread (768) the call
    compiles within the scoped VMEM `_q40_call` asks for, with the spread
    matrix as one more operand, and puts no copy in front of the kernel
    that the call with `pltpu.repeat` does not have; the others are the
    parent's text."""
    from rehearse_chip_compile import q40_struct

    from distributed_llama_tpu.ops import pallas_q40 as q

    x = _struct((n_tiles * tile, n), BF16, one_chip)
    if experts is None:
        w = _placed(q40_struct(4096, n), one_chip)
        args = (x, w)

        def fn(x, w):
            return q.q40_matmul(x, w, out_dtype=BF16)
    else:
        w = _placed(q40_struct(experts, 4096, n), one_chip)
        args = (x, w, _struct((n_tiles,), jnp.int32, one_chip),
                _struct((), jnp.int32, one_chip))

        def fn(x, w, e, used):
            return q.q40_expert_matmul(x, w, e, used, out_dtype=BF16,
                                       token_rows=rows)

    # a new function each time: jit caches a function's trace
    own, forced = _with_repeat_forced(
        monkeypatch, lambda: jax.jit(lambda *a: fn(*a)).lower(*args))
    assert (own.as_text() != forced.as_text()) == (n == 768)
    assert q._spreads_on_mxu(n // 32) == (n == 768)
    own = own.compile()
    assert _has_kernel(own)
    if n == 768:
        forced = forced.compile()
        assert _copy_results(own) == _copy_results(forced)
        assert (own.memory_analysis().temp_size_in_bytes
                <= forced.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("t", [1, 32], ids=["decode", "chunk32"])
@pytest.mark.parametrize("model", ["mistral_7b", "mixtral_8x7b_12l",
                                   "olmo_hybrid_7b", "sarvam_105b_ep8",
                                   "granite_4_h_small_ep2"])
def test_only_granites_step_programs_take_the_mxu_spread(
        topo, monkeypatch, model, t):
    """`mistral-7b`'s, `mixtral-8x7b-12l`'s, `olmo-hybrid-7b`'s and
    `sarvam-105b-ep8`'s two step programs AS SERVED hold no contraction
    under 32 scale blocks a row (theirs are 64, 120, 128, 344, 448 and
    512): they lower to the same text with the MXU spread and with
    `pltpu.repeat` forced everywhere. `granite-4.0-h-small-ep2`'s do not:
    its 768-wide expert down projection (24 blocks) is the one shape of
    the benchmark's programs that takes the MXU spread."""
    import rehearse_chip_compile as r

    spec, seq_len = {
        "mistral_7b": (dataclasses.replace(r.MISTRAL_7B, n_layers=2), 4096),
        "mixtral_8x7b_12l": (dataclasses.replace(r.MIXTRAL_8X7B,
                                                 n_layers=12), 4096),
        "olmo_hybrid_7b": (r.hybrid_layers(r.OLMO_HYBRID_7B, 1), 8192),
        "sarvam_105b_ep8": (dataclasses.replace(r.SARVAM_105B_EP8,
                                                n_layers=4), 8192),
        "granite_4_h_small_ep2": (dataclasses.replace(
            r.GRANITE_4_H_SMALL_EP2, n_layers=2, mixers=(3, 0)), 8192),
    }[model]

    def lower():
        fn, args = r.abstract_step(spec, topo.devices, batch=8, t=t,
                                   seq_len=seq_len, q80=True)
        return fn.lower(*args).as_text()

    own, forced = _with_repeat_forced(monkeypatch, lower)
    assert "q40_matmul" in own
    assert (own == forced) == (model != "granite_4_h_small_ep2")


@pytest.mark.parametrize("t", [1, 32], ids=["decode", "chunk32"])
@pytest.mark.parametrize("model", ["mixtral_8x7b_12l", "sarvam_105b_ep8",
                                   "granite_4_h_small_ep2",
                                   "kimi_linear_48b_ep4"])
def test_only_kimis_chunk_unpacks_an_expert_once(
        topo, monkeypatch, model, t):
    """The four MoE configurations' step programs AS SERVED, with the order
    of the grouped expert call as `_unpacks_once` decides it and with row
    tiles outermost forced, which is the parent's kernel: Mixtral's,
    granite's (64-row tiles) and sarvam's (16) chunk programs and all four
    decode programs lower to the SAME TEXT either way;
    `kimi-linear-48b-a3b-ep4`'s chunk program (8-row tiles) does not, and
    it compiles for the described v5e with the dequantised block in scratch
    (`test_served_kimi_linear_step_programs_hold_their_kernels`).
    `tools/compare_step_texts.py` is the same comparison against a parent
    checkout's compiled text."""
    import rehearse_chip_compile as r

    from distributed_llama_tpu.ops import pallas_q40 as q

    spec, seq_len = {
        "mixtral_8x7b_12l": (dataclasses.replace(r.MIXTRAL_8X7B,
                                                 n_layers=2), 4096),
        "sarvam_105b_ep8": (dataclasses.replace(r.SARVAM_105B_EP8,
                                                n_layers=3), 8192),
        "granite_4_h_small_ep2": (dataclasses.replace(
            r.GRANITE_4_H_SMALL_EP2, n_layers=2, mixers=(3, 0)), 8192),
        "kimi_linear_48b_ep4": (dataclasses.replace(
            r.KIMI_LINEAR_48B_EP4, n_layers=3, mixers=(2, 2, 1)), 8192),
    }[model]

    def lower():
        q.q40_expert_matmul.clear_cache()  # a trace is cached by shape
        fn, args = r.abstract_step(spec, topo.devices, batch=8, t=t,
                                   seq_len=seq_len, q80=True)
        text = fn.lower(*args).as_text()
        q.q40_expert_matmul.clear_cache()
        return text

    own = lower()
    monkeypatch.setattr(q, "_unpacks_once", lambda *a: False)
    forced = lower()
    assert "q40_expert_matmul" in own
    assert (own != forced) == (t == 32 and model == "kimi_linear_48b_ep4")


@pytest.mark.parametrize("b,t,h,kvh,s,cache_dtype,kh", [
    (8, 1, 32, 32, 1024, BF16, 32),   # batched decode
    (1, 256, 32, 32, 1024, BF16, 8),  # 256-token prefill chunk
    (8, 1, 32, 32, 1024, jnp.float8_e4m3fn, 8),  # fp8 cache
    # the tiles of KV heads the cells run (PR 54): Mistral's, Mixtral's and
    # granite's step programs, their f8 decode, olmo-hybrid's 30 heads,
    # jamba's one, a tp = 4 shard's two, and the offline 256-token prefill
    # under GQA, whose score tile leaves room for two heads
    (8, 1, 32, 8, 4096, BF16, 8),
    (8, 32, 32, 8, 4096, BF16, 8),
    (8, 1, 32, 8, 4096, jnp.float8_e4m3fn, 8),
    (8, 1, 30, 30, 8192, BF16, 30),
    (8, 32, 30, 30, 8192, BF16, 30),
    (8, 1, 30, 30, 8192, jnp.float8_e4m3fn, 10),
    (16, 1, 32, 1, 8192, BF16, 1),
    (16, 16, 32, 1, 8192, BF16, 1),
    (8, 1, 8, 2, 4096, BF16, 2),
    (1, 256, 32, 8, 4096, BF16, 2),
])
def test_flash_attention_compiles(one_chip, b, t, h, kvh, s, cache_dtype, kh):
    """The kernel at the tile `head_tile` cuts for the shapes, under the
    slot map where the served step programs pass one."""
    from distributed_llama_tpu.ops.pallas_attention import (flash_attention,
                                                            flash_grid)

    assert flash_grid(b, t, h, kvh, s, 128, cache_dtype, BF16) == (
        b, kvh // kh, s // 512)
    q = _struct((b, t, h, 128), BF16, one_chip)
    kv = _struct((b, kvh, s, 128), cache_dtype, one_chip)
    pos = _struct((b, t), jnp.int32, one_chip)
    slots = _struct((b,), jnp.int32, one_chip)
    c = jax.jit(lambda q, k, v, pos, slots: flash_attention(
        q, k, v, pos, slots=slots if s > 1024 else None)).lower(
            q, kv, kv, pos, slots).compile()
    assert _has_kernel(c)


# the row shards the chip's compiler refused before _tile_d bounded its
# tile: Llama-2-7B w1/w3 (11008 -> 2752 per shard), a 32000-vocab head
# (-> 8000) and Llama-3's 128256-vocab head (-> 32064)
@pytest.mark.parametrize("d", [11008, 32000, 128256])
def test_tp4_row_shard_compiles(tp4, d):
    from rehearse_chip_compile import q40_struct

    from distributed_llama_tpu.parallel.tp_q80 import (TpRowWeight,
                                                       tp_row_matmul,
                                                       tp_row_pspec)

    assert (d // 4) % 128  # ragged: no lane-multiple tile divides the shard
    w = TpRowWeight(q40_struct(d, 4096))
    w = jax.tree_util.tree_map(
        lambda s, ps: _struct(s.shape, s.dtype, NamedSharding(tp4, ps)),
        w, tp_row_pspec(w))
    x = _struct((8, 4096), BF16, NamedSharding(tp4, P()))
    c = jax.jit(lambda x, w: tp_row_matmul(
        x, w, tp4, compute_dtype=BF16)).lower(x, w).compile()
    assert _has_kernel(c)


def test_tp4_col_matmul_compiles_with_q80_reduce(tp4):
    from rehearse_chip_compile import q40_struct

    from distributed_llama_tpu.parallel.tp_q80 import (repack_col_tp,
                                                       tp_col_matmul,
                                                       tp_col_pspec)

    w = jax.eval_shape(lambda: repack_col_tp(jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), q40_struct(4096, 11008)), 4))
    w = jax.tree_util.tree_map(
        lambda s, ps: _struct(s.shape, s.dtype, NamedSharding(tp4, ps)),
        w, tp_col_pspec(w))                      # w2, col-split
    x = _struct((8, 1, 11008), BF16, NamedSharding(tp4, P(None, None, "tp")))
    c = jax.jit(lambda x, w: tp_col_matmul(
        x, w, tp4, compute_dtype=BF16, reduce="q80",
        use_pallas=True)).lower(x, w).compile()
    text = c.as_text()
    assert _has_kernel(c)
    assert "all-gather" in text and "all-to-all" in text  # the int8 exchange


@pytest.mark.parametrize("model", ["llama2_7b", "mixtral_8x7b"])
def test_whole_decode_step_compiles_at_published_widths(topo, model):
    """One-chip `slot_decode_step` (B=8) at published widths, depth cut to
    2 layers. Mixtral at B=8 is the all-experts branch of `_moe_ffn` —
    what the first benchmark cells will serve."""
    import rehearse_chip_compile as r

    spec = dataclasses.replace(
        {"llama2_7b": r.LLAMA2_7B, "mixtral_8x7b": r.MIXTRAL_8X7B}[model],
        n_layers=2)
    fn, args = r.abstract_step(spec, topo.devices, batch=8, t=1,
                               seq_len=1024)
    assert _has_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("t", [1, 32], ids=["decode", "chunk32"])
@pytest.mark.parametrize("model", ["mistral_7b", "mixtral_8x7b"])
def test_step_programs_keep_no_cache_sized_copy(topo, model, t):
    """The benchmark's two step programs (B=8, S=4096; depth cut to 2) write
    K/V through the in-place `kv_cache_write` kernel and hold NO `copy` of a
    whole cache leaf. With the drop-mode scatter (or a loop of
    dynamic_update_slices) XLA re-laid every layer's K and V around the
    update, four 64 MiB copies a layer — 62 % of a decode step on the chip
    (PERF.md section 6, PR 27). This is the guard against their return."""
    import rehearse_chip_compile as r

    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    spec = dataclasses.replace(
        {"mistral_7b": r.MISTRAL_7B, "mixtral_8x7b": r.MIXTRAL_8X7B}[model],
        n_layers=2)
    fn, args = r.abstract_step(spec, topo.devices, batch=8, t=t,
                               seq_len=4096)
    lowered = fn.lower(*args)
    assert kernel_call_sites(lowered.as_text()).get("kv_cache_write", 0) >= 1
    copies = r.cache_shaped_copies(lowered.compile().as_text(),
                                   _cache_of(args).k[0].shape)
    assert not copies, copies


@pytest.mark.parametrize("model,t", [("granite_4_h_small_ep2", 1),
                                     ("mistral_7b", 32)],
                         ids=["granite-decode", "mistral-chunk32"])
def test_step_programs_return_the_logits_and_the_sampling_summary(topo, model,
                                                                  t):
    """Both step programs of an engine without a mesh (PR 53; the chunk of
    `mistral-7b` cut to one layer at 32,000 words, the decode step of
    `granite-4.0-h-small-ep2` cut to one SSM layer at 50,176: the TPU's
    compiler takes ~15 s a sort, so one program a vocabulary; B=8) compile
    for the described v5e with the sampling
    summary as their last lines: the (B, vocab) float32 logits stay the
    FIRST output, the packed (B, 1 + 2 x 512) int32 summary is the LAST,
    and the compiled text holds no `copy` of a vocabulary-sized array that
    the same program without the summary lacks (the candidates are found
    by two sorts of a few thousand values a row, under a conditional the
    step may skip: nothing of the logits is re-laid for them)."""
    import re

    import rehearse_chip_compile as r

    spec = (dataclasses.replace(r.MISTRAL_7B, n_layers=1)
            if model == "mistral_7b"
            else r.hybrid_layers(r.GRANITE_4_H_SMALL_EP2, 1, 1))
    texts = {}
    for summary in (True, False):
        fn, args = r.abstract_step(spec, topo.devices, batch=8, t=t,
                                   seq_len=4096, q80=True, summary=summary)
        lowered = fn.lower(*args)
        outs = jax.tree_util.tree_leaves(lowered.out_info)
        assert (outs[0].shape, outs[0].dtype) == ((8, spec.vocab_size),
                                                  jnp.float32)
        if summary:
            assert (outs[-1].shape, outs[-1].dtype) == ((8, 1025), jnp.int32)
            assert args[-1].shape == (8 + 2,)
        texts[summary] = lowered.compile().as_text()
    wide = re.compile(r"= \w+\[(?:\d+,)*%d\]\S* copy\(" % spec.vocab_size)
    copies = {k: len(wide.findall(text)) for k, text in texts.items()}
    assert copies[True] <= copies[False], copies
    assert "sort(" in texts[True] and "conditional(" in texts[True]


@pytest.mark.parametrize("model", ["mistral_7b", "mixtral_8x7b_12l",
                                   "granite_4_h_small_ep2"])
def test_the_chunk_with_the_slot_map_holds_its_kernels_and_no_cache_copy(
        topo, served_moe_step, model):
    """The chunk program of the three configurations whose engines chain a
    slot's segments (`mistral-7b`: two layers; `mixtral-8x7b-12l` AS
    SERVED; `granite-4.0-h-small-ep2`: one whole period of its layers, nine
    SSM and one attention, each with its 36 held experts; B=8, S=4096 or
    8192, the Q80 round trip on) takes the slot map as a sixth argument
    (before the sampling summary's one, which every program without a mesh
    takes), as
    `abstract_step` decides with the engine's rule: the chip's compiler
    accepts `kv_cache_write` and `flash_attention` with the second
    prefetched scalar and `ssd_chunk` with its grid head blocks outermost
    and the state block addressed by slot, every kernel the configuration
    lists is in the program, and it holds NO `copy` of a cache leaf (PRs 27
    and 30 each found one round an update of a few rows) nor of a state
    leaf, as it stands (8, 128, 64, 128) or as the kernel takes it (8,
    8192, 128): 4 MB a slot a layer that nothing may gather or re-lay. The
    map-less chunk, which olmo's and sarvam's engines keep, compiles
    beside it."""
    import rehearse_chip_compile as r

    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    leaves = []
    if model == "mistral_7b":
        spec = dataclasses.replace(r.MISTRAL_7B, n_layers=2)
        fn, args = r.abstract_step(spec, topo.devices, batch=8, t=32,
                                   seq_len=4096, q80=True)
        lowered = fn.lower(*args)
        compiled = lowered.compile()
        kernels = {"q40_matmul", "flash_attention", "kv_cache_write"}
        bare, bare_args = r.abstract_step(spec, topo.devices, batch=8, t=32,
                                          seq_len=4096, q80=True,
                                          slot_map=False)
        assert len(bare_args) == 6      # no map; the summary's operand
        assert _has_kernel(bare.lower(*bare_args).compile())
    elif model == "granite_4_h_small_ep2":
        spec = r.hybrid_layers(r.GRANITE_4_H_SMALL_EP2, 1, 10)
        fn, args = r.abstract_step(spec, topo.devices, batch=8, t=32,
                                   seq_len=8192, q80=True)
        lowered = fn.lower(*args)
        compiled = lowered.compile()
        kernels = {"ssd_chunk", "q40_matmul", "q40_expert_matmul",
                   "flash_attention", "kv_cache_write"}
        assert len(_cache_of(args).s) == 9
        leaves = [(8, 128, 64, 128), (8, 8192, 128)]
    else:
        spec, args, lowered, compiled = served_moe_step(model, 32)
        kernels = {"q40_matmul", "q40_expert_matmul", "flash_attention",
                   "kv_cache_write"}
    # the map, then the summary's operand (PR 53)
    assert len(args) == 7 and args[5].shape == (8,)
    sites = kernel_call_sites(lowered.as_text())
    assert kernels <= set(sites), sites
    for shape in [_cache_of(args).k[0].shape] + leaves:
        assert not r.cache_shaped_copies(compiled.as_text(), shape)
    # who gets a map is the kinds' and the mesh's: not a delta-rule state,
    # not the latent cache, not sharded rows
    def one(s):
        return dataclasses.replace(s, n_layers=1, mixers=s.mixers[:1])

    for other, tp, n_args in ((r.OLMO_HYBRID_7B, 1, 6),
                              (r.SARVAM_105B_EP8, 1, 6),
                              (r.GRANITE_4_H_SMALL_EP2, 1, 7),
                              (r.MISTRAL_7B, 4, 5)):
        assert len(r.abstract_step(one(other), topo.devices, tp=tp, batch=8,
                                   t=32, seq_len=4096)[1]) == n_args


def test_served_mixtral_prefill_chunk_fits_scoped_vmem(served_moe_step):
    """`mixtral-8x7b-12l`'s prefill program AS SERVED (12 layers, the Q80
    activation round trip on). With the cache copies gone XLA placed one
    expert matmul's 256-row activation panels in the kernel's own scoped
    VMEM, 19.2 MB against the 16 MB default, and the compile failed on the
    chip, at this depth only; both Q40 entry points now ask for their
    panels (ops/pallas_q40._SCOPED_VMEM_DEFAULT)."""
    assert _has_kernel(served_moe_step("mixtral_8x7b_12l", 32)[-1])


@pytest.mark.parametrize("t", [1, 32], ids=["decode", "chunk32"])
@pytest.mark.parametrize("model", ["mixtral_8x7b_12l", "sarvam_105b_ep8"])
def test_moe_step_programs_read_experts_in_place(served_moe_step, model, t):
    """Both MoE configurations' two step programs AS SERVED run every
    expert's gate, up and down through `q40_expert_matmul` on the stacked
    leaf, and hold NO copy, slice or fusion whose result is one expert's
    packed matrix or a packed stack. With `_take_expert` + `q40_matmul`
    every step program read and wrote all of Mixtral's 10.1 GB of expert
    weights once more than its matmuls did — 36 % of the device's time in
    `mixtral-8x7b-12l.chat-steady`, more than the decode step's matmuls
    (PERF.md section 6, PR 31). The twin of
    `test_step_programs_keep_no_cache_sized_copy`."""
    import rehearse_chip_compile as r

    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    spec, _, lowered, compiled = served_moe_step(model, t)
    sites = kernel_call_sites(lowered.as_text())
    # call SITES (jit dedups equal shapes): gate = up, and down
    assert sites.get("q40_expert_matmul", 0) >= 2, sites
    sliced = r.expert_sized_results(compiled.as_text(), spec)
    assert not sliced, sliced[:4]


@pytest.mark.parametrize("t", [1, 32], ids=["decode", "chunk32"])
def test_served_sarvam_mla_step_programs_hold_their_kernels(served_moe_step,
                                                            t):
    """`sarvam-105b-ep8`'s two step programs AS SERVED (all 32 layers, B=8,
    S=8192, the Q80 round trip on): each attends through `mla_attention` —
    a 32-token chunk of 64 heads is 2048 query rows, which flash_attention's
    one panel a head would send, silently, to the dense path — writes its
    one latent leaf a layer through `kv_cache_write`, and holds no copy of
    a cache leaf."""
    import rehearse_chip_compile as r

    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    _, args, lowered, compiled = served_moe_step("sarvam_105b_ep8", t)
    cache = _cache_of(args)
    assert cache.v == () and cache.k[0].shape == (8, 1, 8192, 576)
    sites = kernel_call_sites(lowered.as_text())
    assert sites.get("mla_attention", 0) >= 1, sites
    assert sites.get("kv_cache_write", 0) >= 1, sites
    assert sites.get("q40_matmul", 0) >= 5, sites
    assert "flash_attention" not in sites
    assert not r.cache_shaped_copies(compiled.as_text(), cache.k[0].shape)


@pytest.mark.parametrize("t", [1, 32], ids=["decode", "chunk32"])
def test_served_olmo_hybrid_step_programs_hold_their_kernels(topo, t):
    """`olmo-hybrid-7b`'s two step programs at published widths (one period
    of its pattern: three DELTA layers and one ATTENTION layer; B=8, S=8192,
    the Q80 round trip on, the 100352-row head): the delta rule runs in its
    kernel of that program (96- and 192-wide heads, a (6, 96, 192) state
    block), the 8192-row leaves exist for the ATTENTION layer only, and the
    program holds no copy of a state or a cache leaf."""
    import rehearse_chip_compile as r

    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    spec = r.hybrid_layers(r.OLMO_HYBRID_7B, 1)
    fn, args = r.abstract_step(spec, topo.devices, batch=8, t=t,
                               seq_len=8192, q80=True)
    cache = _cache_of(args)
    assert (len(cache.k), len(cache.v), len(cache.s), len(cache.conv)) == (
        1, 1, 3, 3)
    assert cache.k[0].shape == (8, 30, 8192, 128)
    assert cache.s[0].shape == (8, 30, 96, 192)
    assert cache.conv[0].shape == (8, 3, 11520)
    lowered = fn.lower(*args)
    sites = kernel_call_sites(lowered.as_text())
    mine, other = (("delta_rule_decode", "delta_rule_chunk") if t == 1
                   else ("delta_rule_chunk", "delta_rule_decode"))
    assert sites.get(mine, 0) >= 1 and other not in sites, sites
    assert sites.get("flash_attention", 0) >= 1, sites
    assert sites.get("kv_cache_write", 0) >= 1, sites
    assert sites.get("q40_matmul", 0) >= 5, sites
    text = lowered.compile().as_text()
    for leaf in (cache.k[0], cache.s[0]):
        assert not r.cache_shaped_copies(text, leaf.shape)


@pytest.mark.parametrize("t", [1, 32])
def test_ssd_kernels_compile_at_published_widths(one_chip, t):
    """ssd_decode (t = 1) and ssd_chunk at granite-4.0-h-small's sizes: 128
    heads of 64 over a state of 128, one group, 8 rows; the state (8, 128,
    64, 128) float32 donated and aliased."""
    from distributed_llama_tpu.ops.pallas_ssd import ssd_scan

    f32 = jnp.float32
    args = [_struct(s, d, one_chip) for s, d in (
        ((8, t, 128, 64), f32), ((8, t, 128), f32), ((128,), f32),
        ((8, t, 1, 128), f32), ((8, t, 1, 128), f32),
        ((8, 128, 64, 128), f32), ((8,), jnp.int32), ((8,), jnp.bool_))]
    lowered = jax.jit(lambda *a: ssd_scan(*a, use_pallas=True),
                      donate_argnums=5).lower(*args)
    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    mine = "ssd_decode" if t == 1 else "ssd_chunk"
    assert kernel_call_sites(lowered.as_text()) == {mine: 1}
    assert _has_kernel(lowered.compile())


@pytest.mark.parametrize("t", [1, 32])
def test_granite_hybrid_steps_compile_at_published_widths(topo, t):
    """`granite-4.0-h-small-ep2`'s two step programs at published widths
    (one SSM and one ATTENTION layer, each with its 36 held experts of 72
    and the shared expert; B=8, S=8192, the Q80 round trip on, the
    50176-row head): the scan runs in its kernel of that program, the
    16384-row gate | x leaf and the 768-wide experts tile, and the program
    holds no copy of a state or a cache leaf."""
    import rehearse_chip_compile as r

    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    spec = dataclasses.replace(r.GRANITE_4_H_SMALL_EP2, n_layers=2,
                               mixers=(3, 0))
    fn, args = r.abstract_step(spec, topo.devices, batch=8, t=t,
                               seq_len=8192, q80=True)
    cache = _cache_of(args)
    assert (len(cache.k), len(cache.v), len(cache.s), len(cache.conv)) == (
        1, 1, 1, 1)
    assert cache.k[0].shape == (8, 8, 8192, 128)
    assert cache.s[0].shape == (8, 128, 64, 128)
    assert cache.conv[0].shape == (8, 3, 8448)
    lowered = fn.lower(*args)
    sites = kernel_call_sites(lowered.as_text())
    mine, other = (("ssd_decode", "ssd_chunk") if t == 1
                   else ("ssd_chunk", "ssd_decode"))
    assert sites.get(mine, 0) >= 1 and other not in sites, sites
    for k in ("flash_attention", "kv_cache_write", "q40_expert_matmul",
              "q40_matmul"):
        assert sites.get(k, 0) >= 1, sites
    text = lowered.compile().as_text()
    for leaf in (cache.k[0], cache.s[0]):
        assert not r.cache_shaped_copies(text, leaf.shape)


@pytest.mark.parametrize("t", [1, 32], ids=["decode", "chunk32"])
@pytest.mark.parametrize("model", ["mistral_7b", "sarvam_105b_ep8"])
def test_the_device_scopes_change_no_compiled_program(topo, monkeypatch,
                                                      model, t):
    """A `jax.named_scope` is op metadata: the step program the chip's
    compiler makes with the scopes of models/scopes.DEVICE_SCOPES is, but
    for its `metadata={...}` and the source locations inside a Pallas
    kernel's serialized body, the text it makes with every scope taken out
    (two layers of Mistral-7B, and of sarvam's share one dense and two
    experts-held layers with their wave loop; the Q80 round trip on)."""
    import contextlib
    import re

    import rehearse_chip_compile as r

    from distributed_llama_tpu.models.scopes import DEVICE_SCOPES

    spec = {"mistral_7b": dataclasses.replace(r.MISTRAL_7B, n_layers=2),
            "sarvam_105b_ep8": dataclasses.replace(r.SARVAM_105B_EP8,
                                                   n_layers=3)}[model]

    def compiled_text() -> str:
        fn, args = r.abstract_step(spec, topo.devices, batch=8, t=t,
                                   seq_len=4096, q80=True)
        return fn.lower(*args).compile().as_text()

    def bare(text: str) -> str:
        text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
        text = re.sub(r'"body":"[^"]*"', '"body":""', text)
        return text[text.index("\n%"):]     # past the tables of locations

    with_scopes = compiled_text()
    named = set(re.findall(r'op_name="jit\([^"]*?/(\w+)/', with_scopes))
    assert named & set(DEVICE_SCOPES) >= {"attn_proj", "attn_core", "head"}
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: (contextlib.nullcontext() if name in DEVICE_SCOPES
                      else real(name)))
    without = compiled_text()
    assert not set(re.findall(r'op_name="[^"]*?/(\w+)/', without)) & set(
        DEVICE_SCOPES)
    assert bare(with_scopes) == bare(without)


@pytest.mark.parametrize("b,t", [(8, 1), (8, 32)])
def test_mla_attention_compiles(one_chip, b, t):
    from distributed_llama_tpu.ops.pallas_attention import mla_attention

    from distributed_llama_tpu.ops.pallas_kv_write import \
        kv_cache_write_seq_minor

    q = _struct((b, t, 64, 576), BF16, one_chip)
    cache_t = _struct((b, 1, 576, 8192), BF16, one_chip)   # sequence minor
    pos = _struct((b, t), jnp.int32, one_chip)
    c = jax.jit(lambda q, c, p: mla_attention(
        q, c, p, v_width=512, scale=0.1)).lower(q, cache_t, pos).compile()
    assert _has_kernel(c)
    new = _struct((b, t, 1, 576), BF16, one_chip)
    c = jax.jit(kv_cache_write_seq_minor, donate_argnums=0).lower(
        cache_t, new, _struct((b,), jnp.int32, one_chip)).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("t", [1, 32, 256])
@pytest.mark.parametrize("cache_dtype", [BF16, jnp.float32,
                                         jnp.float8_e4m3fn])
def test_kv_cache_write_compiles(one_chip, t, cache_dtype):
    """Every row tile (f32 8, bf16 16, fp8 32) and window width the serving
    programs use; Llama-2-7B's 32 kv heads are the widest block."""
    from distributed_llama_tpu.ops.pallas_kv_write import kv_cache_write

    cache = _struct((8, 32, 4096, 128), cache_dtype, one_chip)
    new = _struct((8, t, 32, 128), cache_dtype, one_chip)
    pos = _struct((8,), jnp.int32, one_chip)
    c = jax.jit(kv_cache_write, donate_argnums=(0, 1)).lower(
        cache, cache, new, new, pos).compile()
    assert _has_kernel(c)


def test_whole_tp4_q80_steps_compile(topo):
    """The program `dllama api --tp 4 --buffer-float-type q80
    --serve-batch 8` mints — decode and the 32-wide slot prefill — over the
    four described chips, 7B widths cut to 2 layers."""
    import rehearse_chip_compile as r

    spec = dataclasses.replace(r.LLAMA2_7B, n_layers=2)
    for t in (1, 32):
        fn, args = r.abstract_step(spec, topo.devices, tp=4, batch=8, t=t,
                                   seq_len=1024, q80=True)
        rep = r.compile_report(fn, args)
        # call SITES in the lowered module (jit dedups equal shapes):
        # wq=wk=wv, wo, w1=w3, w2, wcls
        assert rep["kernels"].get("q40_matmul", 0) >= 5, rep
        assert rep["kernels"].get("flash_attention", 0) >= 1, rep
        assert rep["kernels"].get("kv_cache_write", 0) >= 1, rep
        assert rep["collectives"]["all-to-all"] > 0, rep


@pytest.mark.parametrize("t", [1, 32])
def test_kda_kernels_compile_at_published_widths(one_chip, t):
    """kda_decode (t = 1) and kda_chunk at Kimi-Linear-48B-A3B's sizes: 32
    heads of 128 x 128, a decay a key channel, 8 rows; the state (8, 32,
    128, 128) float32 donated and aliased."""
    from distributed_llama_tpu.ops.pallas_kda import kda_rule
    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    f32 = jnp.float32
    args = [_struct(s, d, one_chip) for s, d in (
        ((8, t, 32, 128), f32), ((8, t, 32, 128), f32),
        ((8, t, 32, 128), f32), ((8, t, 32, 128), f32), ((8, t, 32), f32),
        ((8, 32, 128, 128), f32), ((8,), jnp.int32), ((8,), jnp.bool_))]
    lowered = jax.jit(lambda *a: kda_rule(*a, use_pallas=True),
                      donate_argnums=5).lower(*args)
    mine = "kda_decode" if t == 1 else "kda_chunk"
    assert kernel_call_sites(lowered.as_text()) == {mine: 1}
    assert _has_kernel(lowered.compile())


@pytest.mark.parametrize("t", [1, 32], ids=["decode", "chunk32"])
def test_served_kimi_linear_step_programs_hold_their_kernels(topo, t):
    """`kimi-linear-48b-a3b-ep4`'s two step programs at published widths
    (the dense first layer, a KDA layer and a latent layer with their 64
    held experts of 256 and the shared expert; B=8, S=8192, the Q80 round
    trip on, the 40960-row head): the rule runs in its kernel of that
    program, the latent layer attends through `mla_attention` and writes its
    one 576-wide leaf through `kv_cache_write`, the 1024-wide experts tile,
    and the program holds no copy of a state or a cache leaf."""
    import rehearse_chip_compile as r

    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    spec = dataclasses.replace(r.KIMI_LINEAR_48B_EP4, n_layers=3,
                               mixers=(2, 2, 1))
    fn, args = r.abstract_step(spec, topo.devices, batch=8, t=t,
                               seq_len=8192, q80=True)
    cache = _cache_of(args)
    assert (len(cache.k), len(cache.v), len(cache.s), len(cache.conv)) == (
        1, 0, 2, 2)
    assert cache.k[0].shape == (8, 1, 8192, 576)
    assert cache.s[0].shape == (8, 32, 128, 128)
    assert cache.conv[0].shape == (8, 3, 12288)
    lowered = fn.lower(*args)
    sites = kernel_call_sites(lowered.as_text())
    mine, other = (("kda_decode", "kda_chunk") if t == 1
                   else ("kda_chunk", "kda_decode"))
    assert sites.get(mine, 0) >= 1 and other not in sites, sites
    for k in ("mla_attention", "kv_cache_write", "q40_expert_matmul",
              "q40_matmul"):
        assert sites.get(k, 0) >= 1, sites
    assert "flash_attention" not in sites and "delta_rule_chunk" not in sites
    text = lowered.compile().as_text()
    for leaf in (cache.k[0], cache.s[0]):
        assert not r.cache_shaped_copies(text, leaf.shape)


@pytest.mark.parametrize("t", [1, 16], ids=["decode", "chunk16"])
def test_served_jamba_step_programs_hold_their_kernels(topo, t):
    """`jamba2-3b`'s two step programs at published widths (two selective
    scan layers and an attention layer of 20 query heads on ONE KV head;
    B=16, S=8,192 as the cell serves it, the Q80 round trip on, the 65,536-row
    head): the scan runs in its kernel of that program over a (16, 5120)
    state a slot, the attention layer attends through `flash_attention`
    (320 query rows a panel in a chunk) and writes its one-head rows through
    `kv_cache_write`, the 10,240-row input projection and the 5120-wide
    output projection tile, and the program holds no copy of a state or a
    cache leaf."""
    import rehearse_chip_compile as r

    from distributed_llama_tpu.runtime.profiler import kernel_call_sites

    spec = dataclasses.replace(r.JAMBA2_3B, n_layers=3, mixers=(3, 3, 0))
    fn, args = r.abstract_step(spec, topo.devices, batch=16, t=t,
                               seq_len=8192, q80=True)
    # the chunk's rows follow a map; both take the summary's operand
    assert len(args) == (5 if t == 1 else 7)
    cache = _cache_of(args)
    assert (len(cache.k), len(cache.v), len(cache.s), len(cache.conv)) == (
        1, 1, 2, 2)
    assert cache.k[0].shape == cache.v[0].shape == (16, 1, 8192, 128)
    assert cache.s[0].shape == (16, 16, 5120)
    assert cache.conv[0].shape == (16, 3, 5120)
    lowered = fn.lower(*args)
    sites = kernel_call_sites(lowered.as_text())
    mine, other = (("selective_scan_decode", "selective_scan_chunk")
                   if t == 1 else
                   ("selective_scan_chunk", "selective_scan_decode"))
    assert sites.get(mine, 0) >= 1 and other not in sites, sites
    for k in ("flash_attention", "kv_cache_write", "q40_matmul"):
        assert sites.get(k, 0) >= 1, sites
    assert "ssd_chunk" not in sites and "ssd_decode" not in sites
    text = lowered.compile().as_text()
    for leaf in (cache.k[0], cache.s[0]):
        assert not r.cache_shaped_copies(text, leaf.shape)
