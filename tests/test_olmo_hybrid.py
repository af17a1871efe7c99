"""OLMO_HYBRID (gated-delta-rule layers with a per-slot recurrent state and
a short convolution beside full-attention layers, norms on the sublayers'
outputs) against the plain reference `benchmark/reference/olmo_hybrid.py`,
at tiny size on the CPU, and the three rules a state needs that a cache of
rows never did.

Logits, not tokens: with random weights the largest logit changes on
rounding. float32 compute and cache, so the program's chunked rule and the
reference's token-by-token scan differ by summation order only.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
sys.path.insert(0, os.path.join(REPO, "tools"))

from reference import olmo_hybrid as ref  # noqa: E402

from distributed_llama_tpu.io.model_file import (model_tensor_plan,  # noqa: E402
                                                 read_model, read_spec,
                                                 write_model)
from distributed_llama_tpu.models import LayerKind  # noqa: E402
from distributed_llama_tpu.models.params import load_params  # noqa: E402
from distributed_llama_tpu.ops.pallas_delta_rule import delta_rule  # noqa: E402
from distributed_llama_tpu.runtime.engine import Engine  # noqa: E402
from distributed_llama_tpu.runtime.scheduler import Scheduler  # noqa: E402
from distributed_llama_tpu.sampler import Sampler  # noqa: E402
from distributed_llama_tpu.testing import (tiny_hybrid_spec,  # noqa: E402
                                           tiny_spec, write_fixture)

SEQ = 128
F32 = jnp.float32


def write_hybrid(path: str, spec, seed: int) -> str:
    """An OLMO_HYBRID `.m` whose weights keep every mechanism alive: std
    1/sqrt(fan-in) projections, norms near 1, decays from a few tokens to
    hundreds, convolution taps of the default size."""
    rng = np.random.default_rng(seed)
    tensors = {}
    for name, shape, _ in model_tensor_plan(spec):
        x = rng.standard_normal(shape).astype(np.float32)
        if "rms" in name:
            x = 1.0 + 0.1 * x
        elif name.endswith("a_log"):
            x = np.log(rng.uniform(0.05, 8.0, shape)).astype(np.float32)
        elif name.endswith("dt_bias"):
            x = rng.uniform(-3.0, 0.0, shape).astype(np.float32)
        elif name.endswith("conv_w"):
            x = rng.uniform(-0.5, 0.5, shape).astype(np.float32)
        else:
            x = x / np.sqrt(shape[-1])
        tensors[name] = x
    write_model(path, spec, tensors)
    return path


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    d = tmp_path_factory.mktemp("hybrid")
    path = write_hybrid(str(d / "model.m"), tiny_hybrid_spec(seq_len=SEQ), 5)
    spec, tensors = read_model(path)
    params = load_params(spec, tensors, mode="q40", dtype=F32)
    tokens = np.random.default_rng(1).integers(3, 288, 70).astype(np.int32)
    return path, spec, params, tokens, ref.forward(path, tokens)


def engine(spec, params, batch=3, kernels=False):
    return Engine(spec, params, batch=batch, compute_dtype=F32,
                  cache_dtype=F32, use_pallas=kernels,
                  pallas_interpret=kernels)


def rel_l2(a, b) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64) - b)
                 / np.linalg.norm(b))


def chunk_call(eng, rows: dict, chunk: int):
    """One slot_prefill_chunk: rows {row: (tokens, offset)}, the others
    gated."""
    b, seq = eng.batch, eng.seq_len
    tok = np.zeros((b, chunk), np.int32)
    pos = np.full((b,), seq, np.int32)
    lidx = np.zeros((b,), np.int32)
    for row, (toks, off) in rows.items():
        tok[row, :len(toks)] = toks
        pos[row], lidx[row] = off, len(toks) - 1
    return np.asarray(eng.fetch_logits(eng.slot_prefill_chunk(tok, pos, lidx)))


def decode_call(eng, rows: dict):
    """One slot_decode_step: rows {row: (token, position)}."""
    b, seq = eng.batch, eng.seq_len
    tok = np.zeros((b, 1), np.int32)
    pos = np.full((b,), seq, np.int32)
    for row, (t, at) in rows.items():
        tok[row, 0], pos[row] = t, at
    return np.asarray(eng.fetch_logits(eng.slot_decode_step(tok, pos)))


def slot_run(eng, tokens, n_prompt, chunk, row):
    """Chunked slot prefill, then slot decode, through the cache and the
    state; {position: logits} of the last prompt token and every decoded
    one."""
    got = {}
    for off in range(0, n_prompt, chunk):
        lg = chunk_call(eng, {row: (tokens[off:min(off + chunk, n_prompt)],
                                    off)}, chunk)
    got[n_prompt - 1] = lg[row]
    for i in range(n_prompt, len(tokens)):
        got[i] = decode_call(eng, {row: (tokens[i], i)})[row]
    return got


def state_of(eng, row):
    return [np.asarray(x[row]) for x in (*eng.cache.s, *eng.cache.conv)]


@pytest.mark.parametrize("kernels,limit", [(False, 1e-4), (True, 2e-4)],
                         ids=["xla", "pallas-interpret"])
def test_slot_prefill_then_decode_agree_with_reference(tiny, kernels, limit):
    """Chunks of 8 up to position 60, then 10 decode steps from the carried
    state, one slot of three, the others gated; against the reference's full
    forward (token-by-token recurrence). The interpreted kernels (Q40 matmul,
    attention, cache write, delta rule together) get twice the room; the
    delta rule's own kernel is held to its twin below."""
    _, spec, params, tokens, want = tiny
    eng = engine(spec, params, kernels=kernels)
    got = slot_run(eng, tokens, 60, 8, row=1)
    assert sorted(got) == list(range(59, 70))
    for at, lg in got.items():
        assert rel_l2(lg, want[at]) < limit, at


def test_cache_holds_one_leaf_set_a_layer_kind(tiny):
    """Rows for the 2 attention layers, state and tail for the 6 delta
    layers, and NO context-sized leaf for a delta layer."""
    _, spec, params, _, _ = tiny
    eng = engine(spec, params)
    c = eng.cache
    assert (len(c.k), len(c.v), len(c.s), len(c.conv)) == (2, 2, 6, 6)
    assert c.k[0].shape == (3, 4, SEQ, 16)
    assert c.s[0].shape == (3, 2, 32, 64) and c.s[0].dtype == F32
    assert c.conv[0].shape == (3, 3, 2 * (32 + 32 + 64))
    assert all(SEQ not in x.shape for x in (*c.s, *c.conv))
    assert spec.cache_index == (0, 1, 2, 0, 3, 4, 5, 1)
    assert spec.cache_values_per_token == 2 * 4 * (16 + 16)
    assert spec.state_bytes_per_slot(4) == 6 * (2 * 32 * 64 * 4 + 3 * 256 * 4)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunk_widths_agree(tiny, chunk):
    """50 prompt tokens in chunks of 8, 16 or 32 (each with a padded tail),
    then 3 decode steps: the reference's logits whatever the width."""
    _, spec, params, tokens, want = tiny
    got = slot_run(engine(spec, params), tokens[:53], 50, chunk, row=0)
    for at, lg in got.items():
        assert rel_l2(lg, want[at]) < 1e-4, (chunk, at)


def test_a_whole_segment_runs_as_chunks_of_32(tiny):
    """The shared-position path (`Engine.prefill`, what `inference` and
    `chat` use): 67 prompt tokens in one segment are two chunks of 32 and
    one of 3 inside the XLA twin; then `step` decodes from the state."""
    _, spec, params, tokens, want = tiny
    eng = engine(spec, params, batch=1)
    lg = eng.prefill([int(t) for t in tokens[:67]])
    assert rel_l2(np.asarray(lg)[0], want[66]) < 1e-4
    lg = eng.step(np.asarray([[tokens[67]]], np.int32), 67)
    assert rel_l2(np.asarray(lg)[0], want[67]) < 1e-4


def test_a_reused_slot_starts_from_zeros(tiny):
    """Rule 1: a chunk that starts at position 0 starts from a zero state
    and a zero tail, whatever the slot's last request left (no reset
    program on the host). Fails if `fresh` is dropped: the second request
    would read the first one's state."""
    _, spec, params, tokens, want = tiny
    eng = engine(spec, params)
    slot_run(eng, tokens[::-1].copy()[:40], 30, 8, row=1)   # another request
    assert any(np.abs(x).max() > 0 for x in state_of(eng, 1))
    got = slot_run(eng, tokens[:44], 40, 8, row=1)
    for at, lg in got.items():
        assert rel_l2(lg, want[at]) < 1e-4, at


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_a_gated_row_keeps_its_state_to_the_bit(tiny, kernels):
    """Rule 2: a row passed at pos == seq_len takes no part: its state and
    tail are bit-equal after a chunk program and after a decode program that
    other rows ran (also with NO live row at all, as in warm-up)."""
    _, spec, params, tokens, _ = tiny
    eng = engine(spec, params, kernels=kernels)
    slot_run(eng, tokens[:22], 20, 8, row=1)
    before = state_of(eng, 1)
    assert any(np.abs(x).max() > 0 for x in before)
    chunk_call(eng, {0: (tokens[:8], 0), 2: (tokens[8:13], 0)}, 8)
    decode_call(eng, {0: (tokens[8], 8)})
    chunk_call(eng, {}, 8)
    decode_call(eng, {})
    for a, b in zip(before, state_of(eng, 1)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_pad_tokens_of_a_tail_chunk_do_not_advance_the_state(tiny, kernels):
    """Rule 3: 20 real tokens in a chunk of 32 leave the state, the tail and
    the logits that the same 20 alone leave (tokens past logit_index neither
    decay nor write, and the tail is the last three REAL rows)."""
    _, spec, params, tokens, _ = tiny
    padded = engine(spec, params, kernels=kernels)
    alone = engine(spec, params)
    lg_p = chunk_call(padded, {1: (tokens[:20], 0)}, 32)[1]
    for off in (0, 8, 16):
        lg_a = chunk_call(alone, {1: (tokens[off:min(off + 8, 20)], off)},
                          8)[1]
    assert rel_l2(lg_p, lg_a) < 1e-4
    for a, b in zip(state_of(padded, 1), state_of(alone, 1)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)
    # and decode goes on from it as from the unpadded one
    d_p = decode_call(padded, {1: (tokens[20], 20)})[1]
    d_a = decode_call(alone, {1: (tokens[20], 20)})[1]
    assert rel_l2(d_p, d_a) < 1e-4


def _rule_inputs(rng, b, t, h=6, dk=32, dv=64):
    q, k = (rng.standard_normal((b, t, h, dk)).astype(np.float32)
            for _ in "qk")
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q *= dk ** -0.5 / np.linalg.norm(q, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, h, dv)).astype(np.float32)
    g = -rng.uniform(0.001, 1.5, (b, t, h)).astype(np.float32)
    beta = rng.uniform(0.0, 2.0, (b, t, h)).astype(np.float32)
    state = rng.standard_normal((b, h, dk, dv)).astype(np.float32)
    return [jnp.asarray(x) for x in (q, k, v, g, beta, state)]


def _token_by_token(q, k, v, g, beta, state, n_valid, fresh):
    """The rule one token after another, in float64."""
    q, k, v, g, beta, state = (np.asarray(x, np.float64)
                               for x in (q, k, v, g, beta, state))
    b, t, h, _ = q.shape
    o = np.zeros(v.shape)
    s = state.copy()
    for i in range(b):
        if fresh[i] and n_valid[i]:
            s[i] = 0.0
        for j in range(int(n_valid[i])):
            for hh in range(h):
                sk = s[i, hh] * np.exp(g[i, j, hh])          # (dk, dv)
                u = beta[i, j, hh] * (v[i, j, hh] - k[i, j, hh] @ sk)
                s[i, hh] = sk + np.outer(k[i, j, hh], u)
                o[i, j, hh] = q[i, j, hh] @ s[i, hh]
    return o, s


@pytest.mark.parametrize("t", [1, 8, 32])
@pytest.mark.parametrize("n_valid,fresh", [
    ([32, 0, 20, 32], [0, 0, 1, 1]),      # live, gated, tail + fresh, fresh
    ([0, 32, 0, 5], [0, 0, 0, 0]),        # gated rows first and between
    ([0, 0, 0, 0], [0, 0, 0, 0]),         # no live row at all (warm-up)
], ids=["mixed", "gated-first", "all-gated"])
def test_delta_rule_kernel_equals_its_twin_and_the_recurrence(t, n_valid,
                                                              fresh):
    """The Pallas kernel in interpret mode, its XLA twin (the same chunked
    algebra) and the token-by-token recurrence in float64 agree on the
    outputs of the tokens that count and on the new state; a gated row's
    state is bit-equal to what came in."""
    rng = np.random.default_rng(t)
    args = _rule_inputs(rng, 4, t)
    nv = np.minimum(np.asarray(n_valid, np.int32), t)
    fr = np.asarray(fresh, bool)
    want_o, want_s = _token_by_token(*args, nv, fr)
    for kernel in (False, True):
        o, s = delta_rule(*args, jnp.asarray(nv), jnp.asarray(fr),
                          use_pallas=kernel, interpret=kernel)
        o, s = np.asarray(o), np.asarray(s)
        for i in range(4):
            np.testing.assert_allclose(o[i, :nv[i]], want_o[i, :nv[i]],
                                       rtol=1e-4, atol=1e-5)
            if nv[i] == 0:
                assert np.array_equal(s[i], np.asarray(args[5])[i])
                assert not o[i].any()
        np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-5)


def test_scheduler_runs_prefilling_decoding_and_idle_rows_together(tiny):
    """The served path, three slots: one request decodes while a later one
    prefills and the third slot idles, in the same iterations; both emit the
    greedy tokens each emits alone, and the new counter and gauge read what
    the scheduler did."""
    _, spec, params, tokens, _ = tiny
    greedy = lambda: Sampler(spec.vocab_size, temperature=0.0, topp=0.9,  # noqa: E731
                             seed=1)
    first, second = [int(x) for x in tokens[:21]], [int(x) for x in
                                                    tokens[30:49]]

    def alone(prompt, n):
        eng = engine(spec, params, batch=1)
        return eng.generate(prompt, n, greedy()).tokens

    eng = engine(spec, params, batch=3)
    sched = Scheduler(eng, chunk=8)
    assert sched.stats.cache_bytes_per_token == 2 * 4 * 32 * 4
    assert sched.stats.state_bytes_per_slot == spec.state_bytes_per_slot(4)
    a = sched.submit(first, 12, greedy())
    for _ in range(4):                 # 3 chunks of `first`, then it decodes
        sched.step()
    b = sched.submit(second, 6, greedy())
    mixed = 0
    for _ in range(400):
        if a.finished.is_set() and b.finished.is_set():
            break
        before = (sched.stats.prefill_steps, sched.stats.decode_steps)
        sched.step()
        mixed += (sched.stats.prefill_steps > before[0]
                  and sched.stats.decode_steps > before[1])
    assert mixed >= 2                  # iterations that ran both programs
    assert list(a.tokens(timeout=5.0)) == alone(first, 12)
    assert list(b.tokens(timeout=5.0)) == alone(second, 6)
    s = sched.stats
    assert s.prefill_rows == 3 + 3     # one row in each of 3 + 3 chunks
    assert s.prefill_steps == 6 and s.prefill_tokens == 21 + 19
    assert s.summary()["prefill_rows"] == 6
    # a third request reuses slot 0 after `first` left its state there
    c = sched.submit(second, 6, greedy())
    for _ in range(400):
        if c.finished.is_set():
            break
        sched.step()
    assert list(c.tokens(timeout=5.0)) == alone(second, 6)


def test_header_and_tensor_plan_round_trip(tiny, tmp_path):
    path, spec, _, _, _ = tiny
    want = tiny_hybrid_spec(seq_len=SEQ)
    for f in dataclasses.fields(want):
        assert getattr(spec, f.name) == pytest.approx(
            getattr(want, f.name), rel=1e-6), f.name
    assert spec.layer_kinds == ((LayerKind.DELTA,) * 3
                                + (LayerKind.ATTENTION,)) * 2
    names = [n for n, _, _ in model_tensor_plan(spec)]
    assert names[1:8] == [f"layers.0.{w}" for w in
                          ("wq", "wk", "wv", "wg", "wa", "wb", "wo")]
    assert "layers.0.rms_q" not in names and "layers.3.rms_q" in names
    assert "layers.3.conv_w" not in names and "layers.4.a_log" in names
    # the reference's own reader walks the same file to its last byte
    mf = ref.HybridFile(path)
    assert mf.end == os.path.getsize(path)
    assert [n for n, _, _ in mf._plan()] == names
    assert mf.h["rms_eps"] == pytest.approx(1e-6) and mf.kind(3) == 0
    # a LLAMA header gains no key: the file is the parent's, byte for byte
    llama, _ = write_fixture(str(tmp_path), spec=tiny_spec())
    with open(llama, "rb") as f:
        f.seek(4)
        assert int.from_bytes(f.read(4), "little") == 8 + 14 * 8
    assert read_spec(llama).mixers == () and not read_spec(llama).has_state


def test_streamed_loader_builds_the_same_leaves(tiny):
    """models/loader (what the CLI uses) and load_params agree leaf for
    leaf, the fused projections and the stacked decay / beta rows too."""
    from distributed_llama_tpu.models.loader import load_params_streamed
    from distributed_llama_tpu.models.params import fuse_layer_weights

    path, spec, params, _, _ = tiny
    streamed, _ = load_params_streamed(spec, path, mode="q40", dtype=F32)
    _, tensors = read_model(path)
    plain = fuse_layer_weights(load_params(spec, tensors, mode="q40",
                                           dtype=F32))
    import jax

    a, ta = jax.tree_util.tree_flatten(streamed)
    b, tb = jax.tree_util.tree_flatten(plain)
    assert ta == tb
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert set(streamed["layers"][0]) == {
        "wqkv", "wg", "w_ab", "wo", "conv_w", "a_log", "dt_bias", "rms_o",
        "w13", "w2", "rms_att", "rms_ffn"}
    assert streamed["layers"][0]["w_ab"].shape == (4, 64)


def test_real_size_products_are_the_issues():
    """Olmo-Hybrid-7B's two products: cache bytes a token over the 8 layers
    that have a cache, state bytes a slot over the 24 that have a state."""
    spec = tiny_hybrid_spec(
        dim=3840, n_heads=30, n_kv_heads=30, n_layers=32,
        mixers=tuple(([2] * 3 + [0]) * 8), lin_heads=30, lin_k_head_dim=96,
        lin_v_head_dim=192)
    assert spec.cache_values_per_token * 2 == 122_880
    assert spec.state_bytes_per_slot(2) == 54_743_040
    assert (spec.n_cache_layers, spec.n_state_layers) == (8, 24)


def test_synthetic_weights_draw_the_published_initialisation(tmp_path):
    from distributed_llama_tpu.testing import write_synthetic_model

    spec = tiny_hybrid_spec(lin_heads=8, dim=256, hidden_dim=256,
                            n_heads=4, n_kv_heads=4)
    path = str(tmp_path / "m.m")
    write_synthetic_model(path, spec, 7)
    _, tensors = read_model(path)
    a = np.exp(tensors["layers.0.a_log"].to_f32())
    dt = np.log1p(np.exp(tensors["layers.0.dt_bias"].to_f32()))
    assert 0 < a.min() and a.max() <= 16
    assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-6
    wq = tensors["layers.0.wq"].to_f32()
    assert abs(wq.mean()) < 0.05 * wq.std()          # zero-mean nibbles
    assert tensors["layers.0.wa"].to_f32().std() < 0.15 * wq.std()
    assert np.abs(tensors["layers.0.conv_w"].to_f32()).max() <= 0.5
    # the draw a logits check can see precision under (testing.py): a unit
    # embedding, output norms at a tenth, peaked full-attention scores
    mean = {n: float(tensors[n].to_f32().mean()) for n in (
        "layers.0.rms_att", "layers.3.rms_ffn", "layers.3.rms_q",
        "layers.3.rms_k", "layers.0.rms_o", "rms_final")}
    assert [round(v, 1) for v in mean.values()] == [0.1, 0.1, 4.5, 4.5,
                                                    1.0, 1.0]
    assert 0.9 < tensors["tok_emb"].to_f32().std() < 1.1


@pytest.mark.parametrize("flags,says", [
    (["--prefix-cache"], "--prefix-cache"),
    (["--kv-transfer"], "--kv-transfer"),
    (["--draft", "self:2"], "--draft"),
    (["--lookup-decode", "4"], "--draft / --lookup-decode"),
    (["--tp", "2"], "--tp / --pp / --sp / --ep"),
    (["--pp", "2"], "--tp / --pp / --sp / --ep"),
    (["--session", "s.npz"], "--session"),
])
def test_what_assumes_rows_is_refused_at_start_up(tiny, flags, says, capsys):
    """One clear message each, from the header, before anything is loaded."""
    from distributed_llama_tpu.apps.dllama import main

    path = tiny[0]
    tok = os.path.join(os.path.dirname(path), "tok.t")
    with pytest.raises(SystemExit) as e:
        main(["inference", "--model", path, "--tokenizer", tok,
              "--prompt", "x", "--steps", "1"] + flags)
    assert "OLMO_HYBRID keeps a recurrent state" in str(e.value)
    assert says in str(e.value)


def test_library_callers_are_refused_too(tiny):
    from distributed_llama_tpu.runtime.prefix_cache import PrefixCache

    _, spec, params, tokens, _ = tiny
    eng = engine(spec, params, batch=2)
    with pytest.raises(ValueError, match="--prefix-cache"):
        PrefixCache(eng, num_blocks=4, block_len=4)
    with pytest.raises(ValueError, match="--draft"):
        Scheduler(eng, chunk=4, draft_factory=lambda e: None, draft_len=2)
    with pytest.raises(ValueError, match="--draft"):
        eng.slot_verify_step(np.zeros((2, 3), np.int32),
                             np.zeros((2,), np.int32), spec.vocab_size)
    with pytest.raises(ValueError, match="--session"):
        eng.save_session("/dev/null")
    assert tiny_spec().refusal("prefix_cache") is None


@pytest.mark.parametrize("name,least", [
    ("served", None), ("rows_fp8", None), ("state_bf16", 3e-4),
    ("state_zeroed_between_chunks", 0.1), ("pad_tokens_advance", 0.1),
    ("beta_without_2", 0.1)])
def test_the_checks_controls_break_what_they_name(tiny, name, least):
    """tools/olmo_hybrid_controls.py, the chip-side controls of the logits
    check: each swaps ONE thing of the program and puts it back. At tiny
    size in float32 the served path agrees with the reference to 1e-4 and
    every control that can run on a CPU does not (an fp8 dot cannot: its
    control is a flag of the CLI)."""
    import olmo_hybrid_controls as tool

    import distributed_llama_tpu.models.transformer as tr
    import distributed_llama_tpu.ops.pallas_delta_rule as dr

    _, spec, params, tokens, want = tiny
    before = (dr.delta_rule, tr._segment_rows)
    flags, change, param_change, patch = tool.controls({})[name]
    if name == "rows_fp8":
        assert flags == ["--cache-dtype", "f8"] and not change
        return
    assert not flags and param_change is None
    with patch():
        got = slot_run(engine(dataclasses.replace(spec, **change), params),
                       tokens, 60, 8, row=1)
    assert (dr.delta_rule, tr._segment_rows) == before
    worst = max(rel_l2(lg, want[at]) for at, lg in got.items())
    assert worst < 1e-4 if least is None else worst > least, worst
