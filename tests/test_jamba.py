"""JAMBA (selective-scan layers, Mamba-1, beside full-attention layers
without positions that share ONE KV head; a dense SwiGLU in every layer)
against the plain reference `benchmark/reference/jamba.py`, at tiny size on
the CPU with the state, the step's rank and the convolution at their
PUBLISHED sizes (16, 160, 4 taps) and MORE slots than 8.

Logits, not tokens: with random weights the largest logit changes on
rounding. float32 compute and cache, so the program's chunked scan differs
from the reference's token-by-token one by summation order only.
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

from reference import jamba as ref  # noqa: E402

from distributed_llama_tpu.io.model_file import (model_tensor_plan,  # noqa: E402
                                                 read_model, read_spec,
                                                 write_model)
from distributed_llama_tpu.models import ArchType, LayerKind  # noqa: E402
from distributed_llama_tpu.models.params import load_params  # noqa: E402
from distributed_llama_tpu.models.transformer import takes_slot_map  # noqa: E402
from distributed_llama_tpu.runtime.engine import Engine  # noqa: E402
from distributed_llama_tpu.runtime.scheduler import Scheduler  # noqa: E402
from distributed_llama_tpu.sampler import Sampler  # noqa: E402
from distributed_llama_tpu.testing import tiny_jamba_spec, tiny_spec  # noqa: E402
from test_olmo_hybrid import (chunk_call, decode_call, rel_l2,  # noqa: E402
                              slot_run, state_of)

SEQ = 160
F32 = jnp.float32
S, A = LayerKind.SSM, LayerKind.ATTENTION
KINDS = (S, S, A, S) * 2


@pytest.fixture(scope="module", autouse=True)
def _free_compiled_programs():
    """As tests/test_kimi_linear.py's: compiled programs outlive their
    tests here (tests/conftest.py), so the module gives its own back."""
    yield
    import jax

    jax.clear_caches()


def write_jamba(path: str, spec, seed: int) -> str:
    """A JAMBA `.m` whose weights keep every mechanism alive: std
    1/sqrt(fan-in) projections, inner norms and a skip weight AWAY from 1,
    channels that forget over a few tokens beside channels that hold for
    hundreds, a step's bias that matters, convolution taps of the default
    size."""
    rng = np.random.default_rng(seed)
    tensors = {}
    for name, shape, _ in model_tensor_plan(spec):
        x = rng.standard_normal(shape).astype(np.float32)
        if name.endswith(("rms_dt", "rms_b", "rms_c")):
            x = rng.uniform(0.5, 2.0, shape).astype(np.float32)
        elif "rms" in name:
            x = 1.0 + 0.1 * x
        elif name.endswith("a_log"):
            x = np.log(rng.uniform(0.05, 16.0, shape)).astype(np.float32)
        elif name.endswith("dt_bias"):
            x = rng.uniform(-5.0, 0.5, shape).astype(np.float32)
        elif name.endswith("conv_w"):
            x = rng.uniform(-0.5, 0.5, shape).astype(np.float32)
        elif name.endswith("ssm_d"):
            x = 1.0 + 0.3 * x
        elif name.endswith("conv_b"):
            x = 0.2 * x
        else:
            x = x / np.sqrt(shape[-1])
        tensors[name] = x
    write_model(path, spec, tensors)
    return path


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    from distributed_llama_tpu.io import TokenizerData, write_tokenizer_file
    from distributed_llama_tpu.testing import byte_fallback_vocab

    d = tmp_path_factory.mktemp("jamba")
    path = write_jamba(str(d / "model.m"), tiny_jamba_spec(seq_len=SEQ), 5)
    write_tokenizer_file(str(d / "tok.t"), TokenizerData(
        vocab=byte_fallback_vocab(288), scores=[0.0] * 288, bos_id=1,
        eos_id=2))
    spec, tensors = read_model(path)
    params = load_params(spec, tensors, mode="q40", dtype=F32)
    tokens = np.random.default_rng(1).integers(3, 288, 70).astype(np.int32)
    return path, spec, params, tokens, ref.forward(path, tokens)


def engine(spec, params, batch=3, kernels=False, cache_dtype=F32):
    return Engine(spec, params, batch=batch, compute_dtype=F32,
                  cache_dtype=cache_dtype, use_pallas=kernels,
                  pallas_interpret=kernels)


@pytest.mark.parametrize("kernels,limit", [(False, 1e-4), (True, 2e-4)],
                         ids=["xla", "pallas-interpret"])
def test_slot_prefill_then_decode_agree_with_reference(tiny, kernels, limit):
    """Chunks of 16 up to position 60 (3 whole chunks and a tail of 12),
    then 10 decode steps from the carried state and the one-head rows, one
    slot of three, the others gated; against the reference's full forward
    (token-by-token recurrence, full causal attention, both kinds of
    layer)."""
    _, spec, params, tokens, want = tiny
    eng = engine(spec, params, kernels=kernels)
    got = slot_run(eng, tokens, 60, 16, row=1)
    assert sorted(got) == list(range(59, 70))
    for at, lg in got.items():
        assert rel_l2(lg, want[at]) < limit, at


def test_twelve_slots_at_once_and_a_reused_slot(tiny):
    """MORE slots than 8: slots 0, 5 and 11 of twelve prefill and decode in
    the same programs at different offsets, and a slot that held another
    request starts from zeros (`fresh`): each reads the reference's logits
    for its own tokens."""
    path, spec, params, tokens, want = tiny
    other = tokens[::-1].copy()
    third = np.roll(tokens, 7)
    want_other = ref.forward(path, other[:40])
    want_third = ref.forward(path, third[:40])
    eng = engine(spec, params, batch=12)
    slot_run(eng, other[:30], 30, 8, row=0)        # leaves a state in slot 0
    assert any(np.abs(x).max() > 0 for x in state_of(eng, 0))
    for off in range(0, 32, 8):                    # slot 0 again, 5 and 11
        lg = chunk_call(eng, {0: (tokens[off:off + 8], off),
                              5: (third[off:off + 8], off),
                              11: (other[off:off + 8], off)}, 8)
    assert rel_l2(lg[0], want[31]) < 1e-4
    assert rel_l2(lg[5], want_third[31]) < 1e-4
    assert rel_l2(lg[11], want_other[31]) < 1e-4
    lg = decode_call(eng, {0: (tokens[32], 32), 5: (third[32], 32),
                           11: (other[32], 32)})
    assert rel_l2(lg[0], want[32]) < 1e-4
    assert rel_l2(lg[5], want_third[32]) < 1e-4
    assert rel_l2(lg[11], want_other[32]) < 1e-4


def test_cache_holds_one_head_rows_beside_a_scan_state(tiny):
    """K and V rows of ONE head for the 2 attention layers, state and tail
    for the 6 scan layers with the state index outermost ((N, d_inner):
    the channels fill the lanes), NO context-sized leaf for a scan layer;
    both gauges; and the chunk program's rows follow a slot map."""
    _, spec, params, _, _ = tiny
    eng = engine(spec, params)
    c = eng.cache
    assert (len(c.k), len(c.v), len(c.s), len(c.conv)) == (2, 2, 6, 6)
    assert c.k[0].shape == c.v[0].shape == (3, 1, SEQ, 16)
    assert c.s[0].shape == (3, 16, 128) and c.s[0].dtype == F32
    assert c.conv[0].shape == (3, 3, 128)
    assert all(SEQ not in x.shape for x in (*c.s, *c.conv))
    assert spec.layer_kinds == KINDS
    assert spec.cache_index == (0, 1, 0, 2, 3, 4, 1, 5)
    assert spec.cache_values_per_token == 2 * 2 * 16
    assert spec.state_bytes_per_slot(4) == 6 * (16 * 128 * 4 + 3 * 128 * 4)
    assert spec.state_leaves(LayerKind.SSM) == ((16, 128), (3, 128))
    assert spec.ssm_selective and spec.ssm_conv_dim == spec.ssm_inner == 128
    sched = Scheduler(eng, chunk=8)
    assert sched.stats.cache_bytes_per_token == 2 * 2 * 16 * 4
    assert sched.stats.state_bytes_per_slot == spec.state_bytes_per_slot(4)
    assert sched.stats.summary()["state_bytes_per_slot"] > 0
    # the scan chains as ssd_chunk does, and the K/V cache takes a map
    assert takes_slot_map(spec, False)
    assert eng.prefill_rows_per_slot == 3


def test_real_size_products_are_the_issues():
    """AI21-Jamba2-3B's two products: 2 attention layers x 1 KV head x 128
    x 2 leaves x 2 B = 1,024 B a token; 26 scan layers x (5120 x 16 x 4 B
    + 3 x 5,120 x 2 B) = 9,318,400 B a slot."""
    spec = tiny_jamba_spec(
        dim=2560, hidden_dim=8192, n_heads=20, n_layers=28, vocab_size=65536,
        seq_len=262144,
        mixers=tuple(0 if l % 14 == 7 else 3 for l in range(28)),
        ssm_heads=5120)
    spec.validate()
    assert (spec.n_cache_layers, spec.n_state_layers) == (2, 26)
    assert spec.head_size == 128 and spec.kv_dim == 128
    assert spec.cache_values_per_token * 2 == 1_024
    assert spec.state_bytes_per_slot(2) == 9_318_400
    assert spec.state_leaves(LayerKind.SSM) == ((16, 5120), (3, 5120))
    from distributed_llama_tpu.ops.pallas_attention import flash_supported

    assert flash_supported(16, 20, 1)          # 320 query rows a panel


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_a_gated_row_keeps_its_state_and_rows_to_the_bit(tiny, kernels):
    """A row passed at pos == seq_len takes no part: its state, tail and
    rows are bit-equal after a chunk and a decode program that other rows
    ran (also with NO live row at all, as in warm-up)."""
    _, spec, params, tokens, _ = tiny
    eng = engine(spec, params, kernels=kernels)
    slot_run(eng, tokens[:22], 20, 8, row=1)
    before = state_of(eng, 1) + [np.asarray(x[1]) for x in eng.cache.k]
    assert all(np.abs(x).max() > 0 for x in before)
    chunk_call(eng, {0: (tokens[:8], 0), 2: (tokens[8:13], 0)}, 8)
    decode_call(eng, {0: (tokens[8], 8)})
    chunk_call(eng, {}, 8)
    decode_call(eng, {})
    after = state_of(eng, 1) + [np.asarray(x[1]) for x in eng.cache.k]
    for a, b in zip(before, after):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_pad_tokens_of_a_tail_chunk_do_not_advance_the_state(tiny, kernels):
    """20 real tokens in a chunk of 32 leave the state, the tail and the
    logits that the same 20 alone leave, and decode goes on from it."""
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
    d_p = decode_call(padded, {1: (tokens[20], 20)})[1]
    d_a = decode_call(alone, {1: (tokens[20], 20)})[1]
    assert rel_l2(d_p, d_a) < 1e-4


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_a_prompt_chained_over_the_rows_reads_one_segment_a_program(
        tiny, kernels):
    """Rows 0-2 of one chunk program carry three consecutive segments of
    slot 1 (8 + 8 + a tail of 4, as the scheduler chains a prompt that
    prefills alone): logits, state, tail and rows are those of the same
    segments one a program."""
    _, spec, params, tokens, want = tiny
    chained = engine(spec, params, kernels=kernels)
    one = engine(spec, params, kernels=kernels)
    b, c, seq = 3, 8, chained.seq_len
    tok = np.zeros((b, c), np.int32)
    tok[0], tok[1], tok[2, :4] = tokens[:8], tokens[8:16], tokens[16:20]
    lg = np.asarray(chained.fetch_logits(chained.slot_prefill_chunk(
        tok, np.asarray([0, 8, 16], np.int32),
        np.asarray([7, 7, 3], np.int32),
        slots=np.asarray([1, 1, 1], np.int32))))
    for off in (0, 8, 16):
        lg_one = chunk_call(one, {1: (tokens[off:min(off + 8, 20)], off)}, 8)
    assert rel_l2(lg[2], want[19]) < 2e-4
    assert rel_l2(lg[2], lg_one[1]) < 1e-5
    for a, b_ in zip(state_of(chained, 1), state_of(one, 1)):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-6)
    for a, b_ in zip(state_of(chained, 0), state_of(one, 0)):
        assert np.array_equal(a, b_)               # slot 0 stays zeros
    assert seq == SEQ


def test_scheduler_serves_prefilling_and_decoding_rows_together(tiny):
    """The served path, three slots: one request decodes while a later one
    prefills, in the same iterations; both emit the greedy tokens each emits
    alone, and a third request reuses a slot."""
    _, spec, params, tokens, _ = tiny
    greedy = lambda: Sampler(spec.vocab_size, temperature=0.0, topp=0.9,  # noqa: E731
                             seed=1)
    first, second = [int(x) for x in tokens[:21]], [int(x) for x in
                                                    tokens[30:49]]

    def alone(prompt, n):
        return engine(spec, params, batch=1).generate(prompt, n,
                                                      greedy()).tokens

    eng = engine(spec, params, batch=3)
    sched = Scheduler(eng, chunk=8)
    a = sched.submit(first, 12, greedy())
    for _ in range(2):
        sched.step()
    b = sched.submit(second, 6, greedy())
    for _ in range(400):
        if a.finished.is_set() and b.finished.is_set():
            break
        sched.step()
    assert list(a.tokens(timeout=5.0)) == alone(first, 12)
    assert list(b.tokens(timeout=5.0)) == alone(second, 6)
    s = sched.stats
    assert s.prefill_tokens == 21 + 19 and s.decode_rows > 0
    assert s.attn_pairs_decode > 0 and s.attn_pairs_prefill > 0
    c = sched.submit(second, 6, greedy())
    for _ in range(400):
        if c.finished.is_set():
            break
        sched.step()
    assert list(c.tokens(timeout=5.0)) == alone(second, 6)


def test_header_and_tensor_plan_round_trip(tiny):
    path, spec, _, _, _ = tiny
    want = tiny_jamba_spec(seq_len=SEQ)
    for f in dataclasses.fields(want):
        assert getattr(spec, f.name) == pytest.approx(
            getattr(want, f.name), rel=1e-6), f.name
    assert spec.arch == ArchType.JAMBA and not spec.is_mla
    assert spec.ssm_dt_rank == 160 and spec.rope_theta == 0
    names = [n for n, _, _ in model_tensor_plan(spec)]
    assert names[1:14] == [f"layers.0.{w}" for w in (
        "wz", "wx", "wxp", "wdt", "wo", "conv_w", "conv_b", "a_log",
        "dt_bias", "ssm_d", "rms_dt", "rms_b", "rms_c")]
    assert names[14:19] == [f"layers.0.{w}" for w in (
        "w1", "w2", "w3", "rms_att", "rms_ffn")]
    assert "layers.2.wq" in names and "layers.2.conv_w" not in names
    assert "layers.0.rms_o" not in names           # NO norm before wo
    shapes = {n: s for n, s, _ in model_tensor_plan(spec)}
    assert shapes["layers.0.wxp"] == (160 + 16 + 16, 128)
    assert shapes["layers.0.wdt"] == (128, 160)
    assert shapes["layers.0.a_log"] == (16, 128)
    assert shapes["layers.0.conv_w"] == (4, 128)   # x ALONE
    assert shapes["layers.2.wk"] == (16, 64)       # ONE KV head
    # the reference's own reader walks the same file to its last byte
    mf = ref.JambaFile(path)
    assert mf.end == os.path.getsize(path)
    assert [n for n, _, _ in mf._plan()] == names
    assert mf.h["rms_eps"] == pytest.approx(1e-6) and mf.kind(2) == 0
    # an older architecture's header gains no key
    assert read_spec(path).ssm_dt_rank == 160
    assert tiny_spec().ssm_dt_rank == 0 and not tiny_spec().ssm_selective


def test_streamed_loader_builds_the_same_leaves(tiny):
    """models/loader (what the CLI uses) and load_params agree leaf for
    leaf: the fused gate | x of a scan layer, its two thin projections a
    dense leaf each, the attention layer's fused q | k | v."""
    import jax

    from distributed_llama_tpu.models.loader import load_params_streamed
    from distributed_llama_tpu.models.params import fuse_layer_weights

    path, spec, _, _, _ = tiny
    streamed, _ = load_params_streamed(spec, path, mode="q40", dtype=F32)
    _, tensors = read_model(path)
    plain = fuse_layer_weights(load_params(spec, tensors, mode="q40",
                                           dtype=F32))
    a, ta = jax.tree_util.tree_flatten(streamed)
    b, tb = jax.tree_util.tree_flatten(plain)
    assert ta == tb
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert set(streamed["layers"][0]) == {
        "wzx", "wxp", "wdt", "wo", "conv_w", "conv_b", "a_log", "dt_bias",
        "ssm_d", "rms_dt", "rms_b", "rms_c", "w13", "w2", "rms_att",
        "rms_ffn"}
    assert streamed["layers"][0]["wxp"].shape == (192, 128)
    assert streamed["layers"][0]["wdt"].shape == (128, 160)
    assert set(streamed["layers"][2]) == {"wqkv", "wo", "w13", "w2",
                                          "rms_att", "rms_ffn"}


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
    """Every STATE_REFUSALS entry holds for it: one clear message each,
    from the header, before anything is loaded."""
    from distributed_llama_tpu.apps.dllama import main

    path = tiny[0]
    tok = os.path.join(os.path.dirname(path), "tok.t")
    with pytest.raises(SystemExit) as e:
        main(["inference", "--model", path, "--tokenizer", tok,
              "--prompt", "x", "--steps", "1"] + flags)
    assert "JAMBA keeps a recurrent state" in str(e.value)
    assert says in str(e.value)


def test_library_callers_are_refused_too(tiny):
    """PrefixCache, a draft, a verify step, a session file."""
    from distributed_llama_tpu.runtime.prefix_cache import PrefixCache

    _, spec, params, _, _ = tiny
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


@pytest.mark.parametrize("name,least", [
    ("served", None), ("state_bf16", 3e-4), ("inner_norms_dropped", 0.05),
    ("dt_bias_dropped", 0.05), ("state_zeroed_between_chunks", 0.05),
    ("pad_tokens_advance", 0.01)])
def test_the_checks_controls_break_what_they_name(tiny, name, least):
    """tools/jamba_controls.py, the chip-side controls of the logits check:
    each swaps ONE thing of the program and puts it back. At tiny size in
    float32 the served path agrees with the reference to 1e-4 and a control
    does not (the fp8 rows have a test of their own below)."""
    import jamba_controls as tool

    _, spec, params, tokens, want = tiny
    flags, spec_change, param_change, patch = tool.controls({})[name]
    assert not flags and not spec_change
    changed = param_change(params) if param_change else params
    with patch():
        got = slot_run(engine(spec, changed), tokens[:44], 36, 16, row=1)
    worst = max(rel_l2(lg, want[at]) for at, lg in got.items())
    if least is None:
        assert worst < 1e-4
    else:
        assert worst > least, (name, worst)
    # and the swap was put back
    got = slot_run(engine(spec, params), tokens[:44], 36, 16, row=1)
    assert max(rel_l2(lg, want[at]) for at, lg in got.items()) < 1e-4


def test_fp8_rows_fail_the_float32_tolerance(tiny):
    """The lower-precision control of the rows: the 2 attention layers'
    one-head K / V rows in fp8 move the logits far past what float32 rows
    read."""
    _, spec, params, tokens, want = tiny
    got = slot_run(engine(spec, params, cache_dtype=jnp.float8_e4m3fn),
                   tokens[:44], 40, 8, row=1)
    worst = max(rel_l2(lg, want[at]) for at, lg in got.items())
    assert worst > 3e-3, worst


@pytest.mark.parametrize("part", ["rms_dt", "rms_b", "rms_c", "dt_bias",
                                  "conv_b", "ssm_d"])
def test_each_part_of_the_mixer_matters(tiny, part):
    """No part of the mathematics is left out because the result stays
    inside a tolerance: each inner norm's weights at ones, the step's bias,
    the convolution's bias or the skip weight at zero moves the logits by
    a hundred times the served path's distance from the reference."""
    _, spec, params, tokens, want = tiny
    fill = jnp.ones_like if part.startswith("rms") else jnp.zeros_like
    layers = [dict(lw, **{part: fill(lw[part])}) if part in lw else lw
              for lw in params["layers"]]
    got = slot_run(engine(spec, dict(params, layers=layers)), tokens[:44],
                   36, 16, row=1)
    worst = max(rel_l2(lg, want[at]) for at, lg in got.items())
    assert worst > 0.01, (part, worst)


def test_synthetic_weights_draw_the_published_initialisation(tmp_path):
    """testing.write_synthetic_model draws this architecture's file byte for
    byte as benchmark/weights.py does under no recipe, and both draw a_log
    (A in (0, 16) a (state index, channel) pair), dt_bias (dt log-uniform
    in [0.001, 0.1] a channel), conv_w and the three inner norms (about 1)
    as published."""
    import weights

    from distributed_llama_tpu.testing import write_synthetic_model

    spec = tiny_jamba_spec(dim=256, hidden_dim=64, ssm_heads=512)
    mine, theirs = str(tmp_path / "a.m"), str(tmp_path / "b.m")
    write_synthetic_model(mine, spec, 7)
    weights.write_model(theirs, spec, 7, None)
    with open(mine, "rb") as f, open(theirs, "rb") as g:
        assert f.read() == g.read()
    _, tensors = read_model(mine)
    a = np.exp(tensors["layers.0.a_log"].to_f32())
    dt = np.log1p(np.exp(tensors["layers.0.dt_bias"].to_f32()))
    assert a.shape == (16, 512) and 0 < a.min() and a.max() <= 16
    assert dt.shape == (512,)
    assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-6
    assert np.abs(tensors["layers.0.conv_w"].to_f32()).max() <= 0.5
    for w in ("rms_dt", "rms_b", "rms_c"):
        assert abs(tensors[f"layers.0.{w}"].to_f32().mean() - 1.0) < 0.05
    assert np.abs(tensors["layers.0.conv_b"].to_f32()).max() < 0.2


@pytest.mark.parametrize("model_type", ["jamba"])
def test_the_hf_converter_refuses_the_model_type(model_type):
    """converters/hf.py refuses every model_type it cannot convert, by
    name, before it reads a size: `jamba` among them (its file is drawn
    from a seed; no conversion is asked for)."""
    from distributed_llama_tpu.converters import hf
    from distributed_llama_tpu.quants.types import FloatType

    with pytest.raises(ValueError, match=f"unsupported model_type "
                                         f"'{model_type}'"):
        hf.spec_from_config({"model_type": model_type}, FloatType.Q40)
