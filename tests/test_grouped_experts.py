"""The grouped expert path through the served step programs (Engine,
Scheduler): what a whole prompt reads and writes, the four window counters
against a count over the benchmark reference's routing, and that a model
without experts compiles what it compiled before the path existed.

The layer itself (`_moe_ffn` against the all-experts loop, the tile layout,
the kernel's skipped tiles) is tests/test_pallas_q40.py's.
"""

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import weights  # noqa: E402  (the benchmark's draw)
from reference import mixtral as ref  # noqa: E402
from test_pallas_q40 import _all_experts_loop  # noqa: E402

from distributed_llama_tpu.io.model_file import read_model  # noqa: E402
from distributed_llama_tpu.models.params import (load_params,  # noqa: E402
                                                 random_tensors)
from distributed_llama_tpu.models.spec import ArchType  # noqa: E402
from distributed_llama_tpu.models.transformer import _pair_layout  # noqa: E402
from distributed_llama_tpu.ops import pallas_q40  # noqa: E402
from distributed_llama_tpu.runtime.engine import Engine  # noqa: E402
from distributed_llama_tpu.runtime.scheduler import Scheduler  # noqa: E402
from distributed_llama_tpu.runtime.stats import WINDOW_COUNTERS  # noqa: E402
from distributed_llama_tpu.sampler import Sampler  # noqa: E402
from distributed_llama_tpu.testing import (tiny_hybrid_spec,  # noqa: E402
                                           tiny_mla_spec, tiny_spec)

F32 = jnp.float32
B, CHUNK, SEQ = 8, 8, 96


@pytest.fixture(scope="module")
def tiny_moe(tmp_path_factory):
    """A MIXTRAL file of 2 layers, 8 experts top-2, drawn as the benchmark
    draws `mixtral-8x7b-12l` (zero-mean nibbles: tokens spread over the
    experts), its loaded leaves and two prompts."""
    spec = tiny_spec(arch=ArchType.MIXTRAL, n_experts=8, n_active_experts=2,
                     dim=128, hidden_dim=256, n_layers=2, n_heads=4,
                     n_kv_heads=2, seq_len=SEQ, rope_theta=1e6)
    path = str(tmp_path_factory.mktemp("moe") / "model.m")
    weights.write_model(path, spec, 7, {"zero_mean": True})
    spec, tensors = read_model(path)
    params = load_params(spec, tensors, mode="q40", dtype=F32)
    toks = np.random.default_rng(3).integers(3, spec.vocab_size, 64)
    return path, spec, params, [int(t) for t in toks]


def engine(spec, params, dtype=F32):
    return Engine(spec, params, batch=B, compute_dtype=dtype,
                  cache_dtype=dtype, use_pallas=True, pallas_interpret=True)


def serve(eng, prompts: dict, n_decode: int, pad_token: int):
    """Chunked slot prefill of {row: tokens} (rows of unequal length, so
    that some are gated while others end on a right-padded tail chunk),
    then n_decode greedy slot decode steps; pad positions and gated rows
    hold `pad_token`. Returns the logits of every live row of every call
    and the (row, position) pairs that were written."""
    seq, logits, live = eng.seq_len, [], set()
    longest = max(len(p) for p in prompts.values())
    last = {}
    for off in range(0, longest, CHUNK):
        tok = np.full((B, CHUNK), pad_token, np.int32)
        pos = np.full((B,), seq, np.int32)
        lidx = np.zeros((B,), np.int32)
        for row, p in prompts.items():
            part = p[off:off + CHUNK]
            if part:
                tok[row, :len(part)] = part
                pos[row], lidx[row] = off, len(part) - 1
                live.update((row, off + j) for j in range(len(part)))
        lg = np.asarray(eng.fetch_logits(
            eng.slot_prefill_chunk(tok, pos, lidx)))
        for row, p in prompts.items():
            if p[off:off + CHUNK]:
                logits.append(lg[row])
                last[row] = lg[row]
    at = {row: len(p) for row, p in prompts.items()}
    for _ in range(n_decode):
        tok = np.full((B, 1), pad_token, np.int32)
        pos = np.full((B,), seq, np.int32)
        for row in prompts:
            tok[row, 0], pos[row] = int(np.argmax(last[row])), at[row]
            live.add((row, at[row]))
            at[row] += 1
        lg = np.asarray(eng.fetch_logits(eng.slot_decode_step(tok, pos)))
        for row in prompts:
            logits.append(lg[row])
            last[row] = lg[row]
    return np.stack(logits), sorted(live)


def cache_rows(eng, live):
    rows, at = (np.asarray(x) for x in zip(*live))
    return [np.asarray(leaf)[rows, :, at]
            for leaf in (*eng.cache.k, *eng.cache.v)]


def test_a_whole_prompt_reads_and_writes_what_the_all_experts_loop_does(
        tiny_moe, monkeypatch):
    """Three rows of 21, 13 and 3 tokens through chunks of 8 (gated rows,
    right-padded tails) and three decode steps, five rows idle throughout.
    What a pad position or a gated row holds (its routed output is zero in
    the grouped programs, every held expert's in the loop's) reaches no
    live row: the cache rows at live positions and the live rows' logits
    are BIT-equal under another pad token. Against the all-experts loop's
    programs they agree to float32 rounding and in every greedy token: two
    COMPILED programs of the same arithmetic differ in the last bit where
    the CPU compiler's fusions contract a multiply into an add, so bit
    equality of what the two say is held operation by operation, in
    tests/test_pallas_q40.py."""
    _, spec, params, toks = tiny_moe
    prompts = {0: toks[:21], 2: toks[21:34], 5: toks[34:37]}

    eng = engine(spec, params)
    got, live = serve(eng, prompts, 3, pad_token=0)
    got_cache = cache_rows(eng, live)
    assert eng.take_expert_counts()       # the grouped programs counted

    other = engine(spec, params)
    again, _ = serve(other, prompts, 3, pad_token=int(toks[40]))
    np.testing.assert_array_equal(got, again)
    for a, b in zip(got_cache, cache_rows(other, live)):
        np.testing.assert_array_equal(a, b)

    _all_experts_loop(monkeypatch)
    loop = engine(spec, params)
    want, _ = serve(loop, prompts, 3, pad_token=0)
    assert np.abs(want).max() > 0.5
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    for a, b in zip(got_cache, cache_rows(loop, live)):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5)


def test_the_six_counters_count_the_references_routing(tiny_moe, tmp_path):
    """Two requests through the Scheduler (chunks of 8, so tails are padded
    and rows gated; then both decode, one longer than the other): between a
    capture's two ends `/stats` `capture` carries `expert_reads_*`,
    `expert_pairs_*` and `expert_tiles_*`, and they equal a NumPy count
    over the reference's own `top_i` (benchmark/reference/mixtral.py) of
    the tokens each dispatched program really held: a read is an expert
    some real token of a program chose in a layer, a pair a (real token,
    chosen expert), the tiles `sum(ceil(group / row tile))` over the
    experts of a layer."""
    from distributed_llama_tpu.runtime.profiler import PROFILER

    path, spec, params, toks = tiny_moe
    eng = engine(spec, params)
    dispatched = []  # (program, {row: positions}) of every step program

    def logged(program, fn):
        def call(tokens, pos, *lidx, **sample):
            width = np.asarray(lidx[0]) + 1 if lidx else np.ones(B, int)
            dispatched.append((program, {
                r: range(int(pos[r]), int(pos[r]) + int(width[r]))
                for r in range(B) if pos[r] < eng.seq_len}))
            return fn(tokens, pos, *lidx, **sample)
        return call

    eng.slot_prefill_chunk = logged("prefill", eng.slot_prefill_chunk)
    eng.slot_decode_step = logged("decode", eng.slot_decode_step)
    sched = Scheduler(eng, chunk=CHUNK)
    greedy = lambda: Sampler(spec.vocab_size, temperature=0.0, topp=0.9,  # noqa: E731
                             seed=1)
    calls = []

    def counters():
        if calls:  # between the capture's two ends: the work
            reqs = [sched.submit(toks[:21], 6, greedy()),
                    sched.submit(toks[30:43], 3, greedy())]
            for _ in range(400):
                if all(r.finished.is_set() for r in reqs):
                    break
                sched.step()
            calls.extend(reqs)
        calls.append(None)
        now = sched.stats.summary()   # as apps/api_server's /debug/profile
        return {k: now[k] for k in ("steps", *WINDOW_COUNTERS) if k in now}

    try:
        PROFILER.capture(str(tmp_path), 1, counters)
        ends = PROFILER.last_counters
    finally:
        PROFILER.reset()
    a, b = (list(r.tokens(timeout=5.0)) for r in calls[1:3])
    assert len(a) == 6 and len(b) == 3

    # the reference's routing of each request's whole sequence, by slot:
    # the scheduler gives the first request slot 0 and the second slot 1
    seqs = {0: toks[:21] + a, 1: toks[30:43] + b}
    top_i = {}
    for row, seq in seqs.items():
        routing = []
        ref.forward(path, np.asarray(seq[:-1], np.int32), routing=routing)
        assert min(r["margin"].min() for r in routing) > 1e-5
        top_i[row] = [r["top_i"] for r in routing]      # a layer: (T, 2)
    want = dict.fromkeys(("expert_reads_prefill", "expert_pairs_prefill",
                          "expert_tiles_prefill", "expert_reads_decode",
                          "expert_pairs_decode", "expert_tiles_decode"), 0)
    # the row tile of each program's grouped call: 64 x 2 / 8 = 16 rows in
    # a chunk of 8 x 8, the sublane tile's 8 in a decode step
    tile = {"prefill": _pair_layout(spec, B * CHUNK)[0],
            "decode": _pair_layout(spec, B)[0]}
    assert tile == {"prefill": 16, "decode": 8}
    for program, held in dispatched:
        assert set(held) <= set(seqs)
        for layer in range(spec.n_layers):
            chosen = np.concatenate([top_i[r][layer][list(at)]
                                     for r, at in held.items()])
            sizes = np.bincount(chosen.reshape(-1), minlength=spec.n_experts)
            want[f"expert_reads_{program}"] += len(np.unique(chosen))
            want[f"expert_pairs_{program}"] += chosen.size
            want[f"expert_tiles_{program}"] += int(
                (-(-sizes // tile[program])).sum())
    assert (want["expert_reads_prefill"] <= want["expert_tiles_prefill"]
            < want["expert_pairs_prefill"])
    assert want["expert_tiles_decode"] == want["expert_reads_decode"]
    assert want["expert_pairs_prefill"] == (21 + 13) * 2 * spec.n_layers
    assert 0 < want["expert_reads_prefill"] < want["expert_pairs_prefill"]
    assert 0 < want["expert_reads_decode"] <= want["expert_pairs_decode"]
    got = {k: ends["stop"][k] - ends["start"][k] for k in want}
    assert got == want
    assert all(ends["start"][k] == 0 for k in want)


def test_the_counters_keep_up_through_chunks_that_fetch_nothing(tiny_moe):
    """Two prompts of 45 tokens through chunks of 8, side by side (one
    alone would take a chunk program's other rows too, and be done in two):
    five mid-prompt chunks fetch no logits and no decode step runs between
    them.
    The counters are added from the programs that HAVE RUN, without a
    fetch and without blocking, so they trail `prefill_steps` by the
    program in flight and not by the stretch (a closed loop's eight
    documents prefill for dozens of iterations on end: a capture's two ends
    would else difference steps and experts of different programs)."""
    _, spec, params, toks = tiny_moe
    eng = engine(spec, params)
    sched = Scheduler(eng, chunk=CHUNK)
    reqs = [sched.submit(toks[at:at + 45], 2,
                         Sampler(spec.vocab_size, temperature=0.0, topp=0.9,
                                 seed=1)) for at in (0, 19)]
    a_chunk = 2 * CHUNK * spec.n_active_experts * spec.n_layers
    for i in range(1, 6):
        sched.step()
        assert sched.stats.prefill_steps == i and sched.stats.decode_steps == 0
        assert (i - 1) * a_chunk <= sched.stats.expert_pairs_prefill <= i * a_chunk
        # chunk i has run, its counts (an output of their own) with it: on
        # a loaded machine the cache can be ready a moment before them
        jax.block_until_ready((eng.cache,
                               [c for _, c in eng._expert_counts]))
    for _ in range(50):
        if all(r.finished.is_set() for r in reqs):
            break
        sched.step()
    assert all(r.finished.is_set() for r in reqs)
    assert eng.take_expert_counts() == []
    assert sched.stats.prefill_segments == sched.stats.prefill_rows == 12
    assert sched.stats.expert_pairs_prefill == 2 * 45 * 2 * spec.n_layers
    assert sched.stats.expert_pairs_decode == 2 * 1 * 2 * spec.n_layers


def _skewed_share(rng, width, t, d, h):
    """One MoE block of a SARVAM_MLA-shaped held share (sigmoid scores, a
    bias that picks, top-8, 16 experts held from `width` routed, a shared
    expert) at test size, with a SKEWED router: the bias sends most tokens
    to three of the held experts, as the benchmark's sarvam and kimi files
    do, so that a group is several row tiles."""
    from test_pallas_q40 import _qt, _stack

    from distributed_llama_tpu.models.spec import HiddenAct, ModelSpec

    spec = ModelSpec(arch=ArchType.SARVAM_MLA, dim=d, hidden_dim=h,
                     n_layers=1, n_heads=4, n_kv_heads=4, vocab_size=64,
                     seq_len=64, n_experts=16, n_active_experts=8,
                     n_routed_experts=width, expert_offset=width // 4,
                     routed_scaling=2.5, n_shared_experts=1, kv_lora_rank=32,
                     qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                     hidden_act=HiddenAct.SILU)
    bias = 0.5 * rng.standard_normal(width, dtype=np.float32)
    bias[spec.expert_offset + np.asarray([1, 7, 10])] += 3.0
    lw = {"moe_up": _stack(rng, 16, h, d)[1],
          "moe_gate": _stack(rng, 16, h, d)[1],
          "moe_down": _stack(rng, 16, d, h)[1],
          "moe_router": jnp.asarray(rng.standard_normal(
              (width, d), dtype=np.float32) / np.sqrt(d)),
          "moe_bias": jnp.asarray(bias),
          "sh_w1": _qt(rng, h, d), "sh_w2": _qt(rng, d, h),
          "sh_w3": _qt(rng, h, d)}
    xb = jnp.asarray(rng.standard_normal((B, t, d), dtype=np.float32),
                     jnp.bfloat16)
    return spec, lw, xb


# (router width, tokens a row, dim, hidden): a chunk's row tile is 8 where
# 8 x 8 tokens choose 8 of 64 (kimi-linear's 256 x 8 / 256) and 16 where
# 8 x 32 choose 8 of 128 (sarvam's): the first sends all three projections
# down the stationary order, the second keeps row tiles outermost, as the
# two configurations' own chunks do
SKEWED = {"kimi_cut": (64, 8, 256, 512), "sarvam_cut": (128, 32, 512, 512)}


@pytest.mark.parametrize("case", sorted(SKEWED))
def test_a_skewed_router_is_unpacked_once_an_expert_to_the_same_bits(
        rng, monkeypatch, case):
    """`_moe_ffn` under a router that sends most of a chunk's tokens to
    three of the 16 held experts: groups of many row tiles, so the grouped
    call runs stationary at the 8-row tile (every projection:
    `_unpacks_once` says so and is what decides) and row tiles outermost at
    the 16-row one, with gated rows and a right-padded tail. Every LIVE
    token's output is BIT-equal to the all-experts loop over slices
    (`sliced_experts`), operation by operation as in
    tests/test_pallas_q40.py; and the layer's third count is the used row
    tiles, `sum(ceil(group / tile))` over the held experts."""
    import distributed_llama_tpu.models.transformer as tr
    from test_pallas_q40 import _CFG

    width, t, d, h = SKEWED[case]
    spec, lw, xb = _skewed_share(rng, width, t, d, h)
    tile = _pair_layout(spec, B * t)[0]
    assert tile == {"kimi_cut": 8, "sarvam_cut": 16}[case]
    n_valid = jnp.asarray([t, 0, t - 3, t, 0, t, 1, t], jnp.int32)
    real = np.arange(t)[None, :] < np.asarray(n_valid)[:, None]

    orders, groups = [], []
    decide, lay_out = pallas_q40._unpacks_once, tr._pair_tiles
    monkeypatch.setattr(pallas_q40, "_unpacks_once", lambda *a: (
        orders.append(decide(*a)) or orders[-1]))

    def pair_tiles(held, live, member, sizes, tile, n_tiles):
        out = lay_out(held, live, member, sizes, tile, n_tiles)
        groups.append((np.asarray(sizes), int(out[-1])))
        return out

    monkeypatch.setattr(tr, "_pair_tiles", pair_tiles)

    def run(nv):
        counts = []
        return tr._moe_ffn(xb, lw, spec, _CFG, nv, counts), counts[0]

    pallas_q40.q40_expert_matmul.clear_cache()
    with jax.disable_jit():
        got, (reads, pairs, tiles) = run(n_valid)
    pallas_q40.q40_expert_matmul.clear_cache()
    assert orders and set(orders) == {case == "kimi_cut"}
    (sizes, used), = groups
    assert int(tiles) == used == int((-(-sizes // tile)).sum())
    assert int(reads) == (sizes > 0).sum() and int(pairs) == sizes.sum()
    assert np.sort(sizes)[-3] > 2 * tile and int(tiles) > 2 * int(reads)

    _all_experts_loop(monkeypatch)
    with jax.disable_jit():
        loop = np.asarray(run(None)[0], np.float32)
    got = np.asarray(got, np.float32)
    assert np.abs(loop[real]).max() > 0
    np.testing.assert_array_equal(got[real], loop[real])


# sha256 (16 hex digits) of the text the slot step programs of the tiny
# specs lower to, read on the tree BEFORE the grouped path and its counters
# existed (commit 5e2fe7c, this container's jax): a model without experts
# must compile exactly what it compiled then. A PR that changes the dense
# path on purpose re-pins them, from the failure's message. Kernels on, the
# tiny contractions (2-8 scale blocks a row) spread their scales on the MXU
# since PR 40: the pins under "repeat" are the PARENT's, read with
# `pltpu.repeat` forced (nothing else in the text moved), those under True
# are PR 40's own, of the MXU spread.
# PR 53 re-pinned all 24: both slot step programs of an engine without a
# mesh end with the sampling summary of their own logits (one traced
# operand more, one packed int32 leaf out, the candidates' two sorts under a
# conditional; `ops/sharded_vocab.sample_summary`), and the chunk programs
# are lowered WITH their slot map, as the engine calls them. That nothing
# else of a program moved is `tools/compare_step_texts.py`'s to show (the
# compiled text with and without the summary differs in the tail alone) and
# `tests/test_chip_compile.py::
# test_step_programs_return_the_logits_and_the_sampling_summary`'s.
# PR 54 re-pinned the twelve WITH kernels of the three specs that keep a
# K/V cache: `flash_attention` holds a tile of KV heads a grid step (a 3-D
# grid, 4-D blocks read from the cache as it stands, one dot batched over
# the tile's heads). The twelve without kernels and SARVAM_MLA's four with
# (`mla_attention`, untouched) kept theirs.
PARENT_TEXT = {
    ("LLAMA", False, "decode"): "511015bfcdfc9f6e",
    ("LLAMA", False, "prefill"): "a90a541ae8f11b93",
    ("LLAMA", "repeat", "decode"): "08e21648b21770a2",
    ("LLAMA", True, "decode"): "836d57aa9d8538d0",
    ("LLAMA", "repeat", "prefill"): "9fe71c82ad57ad7e",
    ("LLAMA", True, "prefill"): "523d39e320d29519",
    ("OLMO_HYBRID", False, "decode"): "185c19cf7c5b20d5",
    ("OLMO_HYBRID", False, "prefill"): "37f474c4294dd864",
    ("OLMO_HYBRID", "repeat", "decode"): "b2ab83842ed5123e",
    ("OLMO_HYBRID", True, "decode"): "9a7415f923dac8ac",
    ("OLMO_HYBRID", "repeat", "prefill"): "1af28fb5278998bc",
    ("OLMO_HYBRID", True, "prefill"): "1ed3a8079dace6f3",
    # the two architectures WITH experts that the benchmark runs. Their
    # DECODE programs keep the text of commit 26394a7 (before
    # GRANITE_HYBRID's block kinds and multipliers); their CHUNK programs
    # kept it until PR 49, whose third counter (the grouped call's used row
    # tiles, `expert_tiles_prefill`: an int32 (3,) beside the logits where
    # it was (2,); a program whose rows fit one row tile sends none) leaves
    # them: re-pinned there. That PR 49's KERNEL change enters no program of
    # these shapes is shown by the parent's tree with that PR's kernel file
    # alone, which passes the parent's pins, all 24 of this file's:
    #   git archive <PR 48's commit> | tar -x -C /root/scratch/kernel_only
    #   cp distributed_llama_tpu/ops/pallas_q40.py \
    #      /root/scratch/kernel_only/distributed_llama_tpu/ops/
    #   cd /root/scratch/kernel_only && JAX_PLATFORMS=cpu python -m pytest \
    #      tests/test_grouped_experts.py -q -k lowers   # 24 passed
    # PR 50 re-pinned the eight WITH kernels, both programs: gate and up
    # hand `q40_expert_matmul` the token rows and the wave's slice of `src`
    # (a third prefetched scalar), the XLA gather `x[src]` and the float32
    # split over the pair buffer's rows left, and the kernel's body (in the
    # text here: interpret mode) copies a used tile's rows out of the
    # token rows' panels. The four without kernels kept theirs, and
    # test_gate_and_up_read_token_rows_not_a_pair_buffer reads what entered.
    ("MIXTRAL", False, "decode"): "b9c624763508c2d7",
    ("MIXTRAL", False, "prefill"): "8f3ecd436bd00510",
    ("MIXTRAL", "repeat", "decode"): "12a6060f3af983d9",
    ("MIXTRAL", True, "decode"): "f1d2bb07ca486908",
    ("MIXTRAL", "repeat", "prefill"): "3fb71afe3dd48ca6",
    ("MIXTRAL", True, "prefill"): "34c59d0ca837f9fe",
    ("SARVAM_MLA", False, "decode"): "d15a2f64eb679fc0",
    ("SARVAM_MLA", False, "prefill"): "b2401b4131bb8cb4",
    ("SARVAM_MLA", "repeat", "decode"): "987de3976c6a341f",
    ("SARVAM_MLA", True, "decode"): "8b3caf303d830a37",
    ("SARVAM_MLA", "repeat", "prefill"): "f2cc5b60cbf1e91b",
    ("SARVAM_MLA", True, "prefill"): "94b886bde05b6567",
}
TINY_SPECS = {
    "LLAMA": tiny_spec, "OLMO_HYBRID": tiny_hybrid_spec,
    "MIXTRAL": lambda: tiny_spec(arch=ArchType.MIXTRAL, n_experts=4,
                                 n_active_experts=2),
    "SARVAM_MLA": tiny_mla_spec}


@pytest.fixture(scope="module")
def lowered_steps():
    made = {}

    def drop_traces():  # the kernels' traces are cached by shape alone
        pallas_q40.q40_matmul.clear_cache()
        pallas_q40.q40_expert_matmul.clear_cache()

    def steps(arch: str, kernels) -> dict:
        """`kernels`: False, True, or "repeat": the kernels with
        `pltpu.repeat` forced on every Q40 shape, as the parent had them."""
        if (arch, kernels) not in made:
            with pytest.MonkeyPatch.context() as mp:
                if kernels == "repeat":
                    mp.setattr(pallas_q40, "_spreads_on_mxu",
                               lambda nb: False)
                drop_traces()
                made[arch, kernels] = lower(arch, bool(kernels))
                drop_traces()
        return made[arch, kernels]

    def lower(arch: str, kernels: bool) -> dict:
        spec = TINY_SPECS[arch]()
        params = load_params(
            spec, random_tensors(spec, seed=1, scale=0.05), mode="q40",
            dtype=F32)
        eng = Engine(spec, params, batch=B, compute_dtype=F32,
                     cache_dtype=F32, use_pallas=kernels,
                     pallas_interpret=kernels)
        i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
        pos = np.full((B,), eng.seq_len, np.int32)
        one, chunk = np.zeros((B, 1)), np.zeros((B, CHUNK))
        eng.slot_decode_step(one, pos)             # mint both programs
        eng.slot_prefill_chunk(chunk, pos, np.zeros(B))
        assert bool(eng.take_expert_counts()) == spec.is_moe
        # as the engine calls them: the chunk's slot map where it takes
        # one, the summary's operand (PR 53)
        served = (*((eng._identity_map,) if eng._chunk_slot_map else ()),
                  *eng._sample_operands(None, None))
        return {
            "decode": eng._steps["slot_decode"].lower(
                eng.params, i32(one), i32(pos), eng.cache, *served[-1:]),
            "prefill": eng._steps["slot_prefill", CHUNK].lower(
                eng.params, i32(chunk), i32(pos), i32(np.zeros(B)),
                eng.cache, *served)}

    return steps


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("kernels", [False, True, "repeat"],
                         ids=["xla", "kernels", "kernels-repeat"])
@pytest.mark.parametrize("arch", ["LLAMA", "OLMO_HYBRID"])
def test_a_model_without_experts_lowers_to_the_parents_step_programs(
        lowered_steps, arch, kernels, program):
    """The two slot step programs of LLAMA and OLMO_HYBRID engines lower to
    the SAME TEXT as before the grouped path but for the sampling summary at
    their end (PR 53): no counter leaves them (their outputs are the logits,
    the cache's leaves and the summary), no pair is sorted or counted in
    them."""
    low = lowered_steps(arch, kernels)[program]
    n_cache = 4 if arch == "LLAMA" else 16
    # the logits, the cache's leaves and the sampling summary (PR 53)
    assert len(jax.tree_util.tree_leaves(low.out_info)) == 2 + n_cache
    got = hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
    assert got == PARENT_TEXT[arch, kernels, program], (
        arch, kernels, program, got)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("kernels", [False, True, "repeat"],
                         ids=["xla", "kernels", "kernels-repeat"])
@pytest.mark.parametrize("arch", ["MIXTRAL", "SARVAM_MLA"])
def test_a_model_with_experts_lowers_to_the_parents_step_programs(
        lowered_steps, arch, kernels, program):
    """MIXTRAL and SARVAM_MLA engines, the benchmark's other two
    architectures, lower to the SAME TEXT as before the block of a layer
    was chosen by what the spec says (norm placement, FFN kind, the four
    multipliers): at multipliers of 1 nothing enters their programs. (Three
    things have since, on purpose: PR 49's third counter in the chunk
    programs, PR 50's gathered gate and up in both programs with kernels,
    PR 53's sampling summary at the end of both, PARENT_TEXT.)"""
    text = lowered_steps(arch, kernels)[program].as_text()
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == PARENT_TEXT[arch, kernels, program], (
        arch, kernels, program, got)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("arch", ["MIXTRAL", "SARVAM_MLA"])
def test_gate_and_up_read_token_rows_not_a_pair_buffer(lowered_steps, arch,
                                                       program):
    """In the lowered step programs of an engine with experts and kernels
    no XLA op lays the token rows out in the pair buffer (PR 50): no gather
    yields `wave x tile` rows of `d` values, no float32 split into the
    packed lane order runs over that many `d`-wide rows. Gate and up call
    ONE function on the layer's token rows and the wave's slice of `src`
    (the row index rides in with the prefetched scalars), whose body splits
    `rows x d` once: the two calls of a layer name the same rows, so the
    compiler keeps one split a layer. The down projection's input is born
    in the buffer and keeps its split over the buffer's rows."""
    spec = TINY_SPECS[arch]()
    rows = B * (1 if program == "decode" else CHUNK)
    tile, _, wave = _pair_layout(spec, rows)
    pairs, d, h = wave * tile, spec.dim, spec.hidden_dim
    assert pairs > rows
    text = lowered_steps(arch, True)[program].as_text()

    gathered = re.findall(r'"stablehlo\.gather".*-> tensor<([0-9x]+)xf32>',
                          text)
    assert gathered and f"{pairs}x{d}" not in gathered
    split = r"stablehlo\.reshape.*-> tensor<%dx%dx2x16xf32>"
    assert not re.findall(split % (pairs, d // 32), text)

    takes = (rf"\(tensor<{rows}x{d}xf32>, [^)]*tensor<{wave}xi32>, "
             rf"tensor<i32>, tensor<{pairs}xi32>\) -> tensor<{pairs}x{h}xf32>")
    calls = re.findall(rf"call @(q40_expert_matmul\w*)\((%\w+),.*: {takes}",
                       text)
    moe_layers = text.count("call @q40_expert_matmul") // 3
    assert moe_layers >= 2 and len(calls) == 2 * moe_layers
    assert len({name for name, _ in calls}) == 1
    # gate and up of a layer: the same function of the same token rows
    assert all(a == b for a, b in zip(calls[::2], calls[1::2]))
    body = text.split(f"func.func private @{calls[0][0]}(")[1].split(
        "func.func")[0]
    assert len(re.findall(split % (rows, d // 32), body)) == 1
    # what this leaves: the down projection's split, a buffer row at a time
    assert len(re.findall(split % (pairs, h // 32), text)) == 1
