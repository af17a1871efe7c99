"""KIMI_LINEAR (Kimi Delta Attention layers, whose decay is a vector a head,
beside latent-attention layers without positions; a leading dense layer,
then sigmoid-bias-routed experts with a shared expert in EVERY layer, an
experts-held share) against the plain reference
`benchmark/reference/kimi_linear.py`, at tiny size on the CPU: the first
model whose cache holds a latent leaf BESIDE a state leaf.

Logits, not tokens: with random weights the largest logit changes on
rounding. float32 compute and cache, so the program's chunked rule and
absorbed attention differ from the reference's token-by-token scan and
expanded attention by summation order only.
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

from reference import kimi_linear as ref  # noqa: E402

from distributed_llama_tpu.io.model_file import (model_tensor_plan,  # noqa: E402
                                                 read_model, read_spec,
                                                 write_model)
from distributed_llama_tpu.models import ArchType, LayerKind  # noqa: E402
from distributed_llama_tpu.models.params import load_params  # noqa: E402
from distributed_llama_tpu.models.transformer import (_moe_ffn,  # noqa: E402
                                                      takes_slot_map)
from distributed_llama_tpu.runtime.engine import Engine  # noqa: E402
from distributed_llama_tpu.runtime.scheduler import Scheduler  # noqa: E402
from distributed_llama_tpu.sampler import Sampler  # noqa: E402
from distributed_llama_tpu.testing import tiny_kimi_spec, tiny_spec  # noqa: E402
from test_olmo_hybrid import (chunk_call, decode_call, rel_l2,  # noqa: E402
                              slot_run, state_of)

SEQ = 128
F32 = jnp.float32
KINDS = ((LayerKind.DELTA,) * 3 + (LayerKind.LATENT,)
         + (LayerKind.DELTA,) * 2 + (LayerKind.LATENT,))


@pytest.fixture(scope="module", autouse=True)
def _free_compiled_programs():
    """tests/conftest.py turns the cyclic collector off for the whole run,
    so an engine's compiled programs outlive its test. This module mints
    some dozens of them a worker; three whole runs with them left alive
    each lost a worker to a segmentation fault inside XLA's CPU compiler
    or its cache read, late in the run and in a test of another file each
    time (the parent's tree lost none). Dropping jit's caches when the
    module is done gives the executables back."""
    yield
    import jax

    jax.clear_caches()


def write_kimi(path: str, spec, seed: int) -> str:
    """A KIMI_LINEAR `.m` whose weights keep every mechanism alive: std
    1/sqrt(fan-in) projections, norms near 1, channels that forget over a
    few tokens beside channels that hold for hundreds, convolution taps of
    the default size, a router bias that matters."""
    rng = np.random.default_rng(seed)
    tensors = {}
    for name, shape, _ in model_tensor_plan(spec):
        x = rng.standard_normal(shape).astype(np.float32)
        if "rms" in name:
            x = 1.0 + 0.1 * x
        elif name.endswith("a_log"):
            x = np.log(rng.uniform(0.05, 8.0, shape)).astype(np.float32)
        elif name.endswith("dt_bias"):
            x = rng.uniform(-3.0, 0.5, shape).astype(np.float32)
        elif name.endswith("conv_w"):
            x = rng.uniform(-0.5, 0.5, shape).astype(np.float32)
        elif name.endswith("moe_bias"):
            x = 0.1 * x
        else:
            x = x / np.sqrt(shape[-1])
        tensors[name] = x
    write_model(path, spec, tensors)
    return path


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    from distributed_llama_tpu.io import TokenizerData, write_tokenizer_file
    from distributed_llama_tpu.testing import byte_fallback_vocab

    d = tmp_path_factory.mktemp("kimi")
    path = write_kimi(str(d / "model.m"), tiny_kimi_spec(seq_len=SEQ), 5)
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
    """Chunks of 8 up to position 60 (7 whole chunks and a tail of 4), then
    10 decode steps from the carried state and the latent rows, one slot of
    three, the others gated; against the reference's full forward
    (token-by-token recurrence, expanded attention, every kind of layer and
    the dense first one)."""
    _, spec, params, tokens, want = tiny
    eng = engine(spec, params, kernels=kernels)
    got = slot_run(eng, tokens, 60, 8, row=1)
    assert sorted(got) == list(range(59, 70))
    for at, lg in got.items():
        assert rel_l2(lg, want[at]) < limit, at


def test_two_slots_at_once_and_a_reused_slot(tiny):
    """Two slots prefill and decode in the same programs at different
    offsets, and a slot that held another request starts from zeros
    (`fresh`): each reads the reference's logits for its own tokens."""
    path, spec, params, tokens, want = tiny
    other = tokens[::-1].copy()
    want_other = ref.forward(path, other[:40])
    eng = engine(spec, params)
    slot_run(eng, other[:30], 30, 8, row=0)        # leaves a state in slot 0
    assert any(np.abs(x).max() > 0 for x in state_of(eng, 0))
    for off in range(0, 32, 8):                    # slot 0 again, and slot 2
        lg = chunk_call(eng, {0: (tokens[off:off + 8], off),
                              2: (other[off:off + 8], off)}, 8)
    assert rel_l2(lg[0], want[31]) < 1e-4
    assert rel_l2(lg[2], want_other[31]) < 1e-4
    lg = decode_call(eng, {0: (tokens[32], 32), 2: (other[32], 32)})
    assert rel_l2(lg[0], want[32]) < 1e-4
    assert rel_l2(lg[2], want_other[32]) < 1e-4


def test_cache_holds_a_latent_leaf_beside_a_state_leaf(tiny):
    """One latent rows leaf for each of the 2 LATENT layers (no V leaf),
    state and tail for the 5 KDA layers, and NO context-sized leaf for a
    KDA layer; both gauges non-zero."""
    _, spec, params, _, _ = tiny
    eng = engine(spec, params)
    c = eng.cache
    assert (len(c.k), len(c.v), len(c.s), len(c.conv)) == (2, 0, 5, 5)
    assert c.k[0].shape == (3, 1, SEQ, 32 + 8)
    assert c.s[0].shape == (3, 2, 32, 32) and c.s[0].dtype == F32
    assert c.conv[0].shape == (3, 3, 2 * (32 + 32 + 32))
    assert all(SEQ not in x.shape for x in (*c.s, *c.conv))
    assert spec.layer_kinds == KINDS
    assert spec.cache_index == (0, 1, 2, 0, 3, 4, 1)
    assert spec.cache_values_per_token == 2 * 40
    assert spec.state_bytes_per_slot(4) == 5 * (2 * 32 * 32 * 4 + 3 * 192 * 4)
    assert spec.state_leaves(LayerKind.DELTA) == ((2, 32, 32), (3, 192))
    sched = Scheduler(eng, chunk=8)
    assert sched.stats.cache_bytes_per_token == 2 * 40 * 4
    assert sched.stats.state_bytes_per_slot == spec.state_bytes_per_slot(4)
    assert sched.stats.summary()["state_bytes_per_slot"] > 0
    # neither the latent cache nor the delta rule chains yet
    assert not takes_slot_map(spec, False)
    assert eng.prefill_rows_per_slot == 1


def test_real_size_products_are_the_issues():
    """Kimi-Linear-48B-A3B's two products: 7 latent layers x 576 values x 2
    B a token, 20 KDA layers x (32 x 128 x 128 x 4 B + 3 x 12,288 x 2 B) a
    slot."""
    spec = tiny_kimi_spec(
        dim=2304, n_heads=32, n_layers=27, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        mixers=tuple(([2] * 3 + [1]) * 6 + [2, 2, 1]), lin_heads=32,
        lin_k_head_dim=128, lin_v_head_dim=128, lin_decay_dim=128)
    spec.validate()
    assert (spec.n_cache_layers, spec.n_state_layers) == (7, 20)
    assert spec.cache_values_per_token * 2 == 8_064
    assert spec.state_bytes_per_slot(2) == 43_417_600
    assert spec.state_leaves(LayerKind.DELTA) == ((32, 128, 128),
                                                  (3, 12_288))


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_a_gated_row_keeps_its_state_and_rows_to_the_bit(tiny, kernels):
    """A row passed at pos == seq_len takes no part: its state, tail and
    latent rows are bit-equal after a chunk and a decode program that other
    rows ran (also with NO live row at all, as in warm-up)."""
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


def test_scheduler_serves_prefilling_and_decoding_rows_together(tiny):
    """The served path, three slots: one request decodes while a later one
    prefills, in the same iterations; both emit the greedy tokens each emits
    alone, the MoE window counters count in the 6 expert layers, and a third
    request reuses a slot."""
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
    for _ in range(4):
        sched.step()
    b = sched.submit(second, 6, greedy())
    for _ in range(400):
        if a.finished.is_set() and b.finished.is_set():
            break
        sched.step()
    assert list(a.tokens(timeout=5.0)) == alone(first, 12)
    assert list(b.tokens(timeout=5.0)) == alone(second, 6)
    s = sched.stats
    assert s.prefill_steps == 6 and s.prefill_tokens == 21 + 19
    c = sched.submit(second, 6, greedy())
    for _ in range(400):
        if c.finished.is_set():
            break
        sched.step()
    assert list(c.tokens(timeout=5.0)) == alone(second, 6)


def test_header_and_tensor_plan_round_trip(tiny):
    path, spec, _, _, _ = tiny
    want = tiny_kimi_spec(seq_len=SEQ)
    for f in dataclasses.fields(want):
        assert getattr(spec, f.name) == pytest.approx(
            getattr(want, f.name), rel=1e-6), f.name
    assert spec.arch == ArchType.KIMI_LINEAR and spec.is_mla
    assert spec.lin_vector_decay and spec.lin_decay_dim == 32
    names = [n for n, _, _ in model_tensor_plan(spec)]
    assert names[1:10] == [f"layers.0.{w}" for w in (
        "wq", "wk", "wv", "wf_a", "wf_b", "wbeta", "wg_a", "wg_b", "wo")]
    assert "layers.0.w1" in names and "layers.0.moe_router" not in names
    assert "layers.1.moe_bias" in names and "layers.1.sh_w1" in names
    assert "layers.3.wkvb" in names and "layers.3.rms_kv" in names
    assert "layers.3.conv_w" not in names and "layers.0.rms_kv" not in names
    shapes = {n: s for n, s, _ in model_tensor_plan(spec)}
    assert shapes["layers.0.dt_bias"] == (2 * 32,)
    assert shapes["layers.0.a_log"] == (2,)
    assert shapes["layers.0.conv_w"] == (4, 2 * 96)
    # the reference's own reader walks the same file to its last byte
    mf = ref.KimiFile(path)
    assert mf.end == os.path.getsize(path)
    assert [n for n, _, _ in mf._plan()] == names
    assert mf.h["rms_eps"] == pytest.approx(1e-5) and mf.kind(3) == 1
    # an older architecture's header gains no key
    assert read_spec(path).lin_decay_dim == 32
    assert tiny_spec().lin_decay_dim == 1


def test_streamed_loader_builds_the_same_leaves(tiny):
    """models/loader (what the CLI uses) and load_params agree leaf for
    leaf: the fused q | k | v of a KDA layer, its three thin projections as
    one dense leaf, a latent layer's wq left alone."""
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
        "wqkv", "w_fgb", "wf_b", "wg_b", "wo", "conv_w", "a_log", "dt_bias",
        "rms_o", "w13", "w2", "rms_att", "rms_ffn"}
    assert streamed["layers"][0]["w_fgb"].shape == (32 + 2 + 32, 64)
    assert set(streamed["layers"][3]) == {
        "wq", "wkva", "w_uk", "w_uv", "wo", "rms_kv", "moe_router",
        "moe_bias", "moe_up", "moe_gate", "moe_down", "sh_w1", "sh_w2",
        "sh_w3", "rms_att", "rms_ffn"}


def _take_experts(w, start, n):
    from distributed_llama_tpu.quants.jax_codec import QuantizedTensor

    if isinstance(w, QuantizedTensor):
        return QuantizedTensor(w.packed[start:start + n],
                               w.scales[start:start + n])
    return w[start:start + n]


@pytest.mark.parametrize("layer", [1, 3], ids=["kda-layer", "latent-layer"])
def test_shares_add_up_to_the_uncut_layer(tmp_path, layer):
    """Four chips each hold 2 of 8 routed experts: their routed parts, plus
    the shared expert counted once, are what the reference computes for the
    UNCUT layer (all 8 held), in a state layer and in a latent layer alike;
    and the reference reading the file as share 1's computes share 1's
    part."""
    whole = tiny_kimi_spec(n_experts=8, n_routed_experts=8, seq_len=SEQ)
    path = write_kimi(str(tmp_path / "whole.m"), whole, 9)
    spec, tensors = read_model(path)
    lw = load_params(spec, tensors, mode="q40", dtype=F32)["layers"][layer]
    xb = jnp.asarray(np.random.default_rng(3).standard_normal(
        (1, 24, spec.dim)).astype(np.float32))
    cfg = dict(compute_dtype=F32)
    mf = ref.KimiFile(path)
    full = np.asarray(ref.highest(ref.moe)(mf, layer, xb[0]))
    # no expert held: what every chip computes alike, the shared expert
    shared = np.asarray(_moe_ffn(
        xb, lw, dataclasses.replace(spec, n_experts=0), cfg))[0]
    parts = []
    for share in range(4):
        sub = dataclasses.replace(spec, n_experts=2, expert_offset=2 * share)
        sw = dict(lw)
        for k in ("moe_up", "moe_gate", "moe_down"):
            sw[k] = _take_experts(lw[k], 2 * share, 2)
        parts.append(np.asarray(_moe_ffn(xb, sw, sub, cfg))[0] - shared)
    assert rel_l2(sum(parts) + shared, full) < 1e-5
    assert all(np.abs(p).max() > 0 for p in parts)
    # the reference's own share: experts 2 and 3 of the whole file's, read
    # as a file that holds two experts from offset 2
    sub = dataclasses.replace(spec, n_experts=2, expert_offset=2)
    dense = {}
    for name, _, _ in model_tensor_plan(sub):
        src = name.split(".")
        if ".experts." in name:
            src[3] = str(int(src[3]) + 2)
        dense[name] = tensors[".".join(src)].to_f32()
    sub_path = str(tmp_path / "share1.m")
    write_model(sub_path, sub, dense)
    want = ref.highest(ref.moe)(ref.KimiFile(sub_path), layer, xb[0])
    assert rel_l2(parts[1] + shared, np.asarray(want)) < 1e-5


def test_a_token_with_no_held_expert_gets_the_shared_expert_alone(tiny):
    """With 4 of 8 held and top 4, some tokens choose no held expert: their
    routed part is exactly zero in the program and in the reference."""
    path, spec, params, _, _ = tiny
    lw = params["layers"][1]
    xb = jnp.asarray(np.random.default_rng(8).standard_normal(
        (1, 64, spec.dim)).astype(np.float32))
    cfg = dict(compute_dtype=F32)
    counts: list = []
    out = np.asarray(_moe_ffn(xb, lw, spec, cfg, counts=counts))[0]
    shared = np.asarray(_moe_ffn(
        xb, lw, dataclasses.replace(spec, n_experts=0), cfg))[0]
    mf = ref.KimiFile(path)
    routing: list = []
    want = np.asarray(ref.highest(ref.moe)(mf, 1, xb[0], routing))
    assert rel_l2(out, want) < 1e-5
    held = (routing[0] < spec.n_experts).sum(-1)
    assert int(counts[0][1]) == int(held.sum())
    none = held == 0
    if none.any():
        np.testing.assert_allclose(out[none], shared[none], rtol=1e-6,
                                   atol=1e-7)


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
    assert "KIMI_LINEAR keeps a recurrent state" in str(e.value)
    assert says in str(e.value)


def test_library_callers_are_refused_too(tiny):
    """PrefixCache, a draft, a verify step, a session file; and the latent
    cache's own refusal under a tp mesh."""
    import jax

    from distributed_llama_tpu.parallel.mesh import make_mesh
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
    with pytest.raises(AssertionError, match="KIMI_LINEAR keeps a latent"):
        Engine(spec, params, make_mesh(tp=2, devices=jax.devices()[:2]),
               batch=2, compute_dtype=F32, cache_dtype=F32)


@pytest.mark.parametrize("name,least", [
    ("served", None), ("state_bf16", 3e-4), ("router_next_best", 0.05),
    ("experts_rolled", 0.05)])
def test_the_checks_controls_break_what_they_name(tiny, name, least):
    """tools/kimi_linear_controls.py, the chip-side controls of the logits
    check: each swaps ONE thing of the program and puts it back. At tiny
    size in float32 the served path agrees with the reference to 1e-4 and a
    control does not (the fp8 latent cache has a test of its own below)."""
    import kimi_linear_controls as tool

    _, spec, params, tokens, want = tiny
    flags, spec_change, param_change, patch = tool.controls(
        spec.router_width)[name]
    assert not flags and not spec_change
    changed = param_change(params) if param_change else params
    with patch():
        got = slot_run(engine(spec, changed), tokens[:44], 40, 8, row=1)
    worst = max(rel_l2(lg, want[at]) for at, lg in got.items())
    if least is None:
        assert worst < 1e-4
    else:
        assert worst > least, (name, worst)
    # and the swap was put back
    got = slot_run(engine(spec, params), tokens[:44], 40, 8, row=1)
    assert max(rel_l2(lg, want[at]) for at, lg in got.items()) < 1e-4


def test_an_fp8_latent_cache_fails_the_float32_tolerance(tiny):
    """The lower-precision control of the rows: the 2 latent layers' cache
    in fp8 moves the logits far past what float32 rows read."""
    _, spec, params, tokens, want = tiny
    got = slot_run(engine(spec, params, cache_dtype=jnp.float8_e4m3fn),
                   tokens[:44], 40, 8, row=1)
    worst = max(rel_l2(lg, want[at]) for at, lg in got.items())
    assert worst > 3e-3, worst


def test_synthetic_weights_draw_the_published_initialisation(tmp_path):
    """testing.write_synthetic_model draws this architecture's file byte for
    byte as benchmark/weights.py does under no recipe, and both draw a_log,
    dt_bias (a channel), conv_w and the router's bias as published."""
    import weights

    from distributed_llama_tpu.testing import write_synthetic_model

    spec = tiny_kimi_spec(dim=256, hidden_dim=64)
    mine, theirs = str(tmp_path / "a.m"), str(tmp_path / "b.m")
    write_synthetic_model(mine, spec, 7)
    weights.write_model(theirs, spec, 7, None)
    with open(mine, "rb") as f, open(theirs, "rb") as g:
        assert f.read() == g.read()
    _, tensors = read_model(mine)
    a = np.exp(tensors["layers.0.a_log"].to_f32())
    dt = np.log1p(np.exp(tensors["layers.0.dt_bias"].to_f32()))
    assert 0 < a.min() and a.max() <= 16 and dt.shape == (64,)
    assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-6
    wq = tensors["layers.0.wq"].to_f32()
    assert abs(wq.mean()) < 0.05 * wq.std()          # zero-mean nibbles
    assert np.abs(tensors["layers.0.conv_w"].to_f32()).max() <= 0.5
    assert 0.3 < tensors["layers.1.moe_bias"].to_f32().std() < 0.8
    assert abs(tensors["layers.3.rms_kv"].to_f32().mean() - 1.0) < 0.05


@pytest.mark.parametrize("model_type", ["kimi_linear", "sarvam_mla",
                                        "olmo_hybrid", "granitemoehybrid"])
def test_the_hf_converter_refuses_the_model_type(model_type):
    """converters/hf.py refuses every model_type it cannot convert, by
    name, before it reads a size: `kimi_linear` among them (its file is
    drawn from a seed; no conversion is asked for)."""
    from distributed_llama_tpu.converters import hf
    from distributed_llama_tpu.quants.types import FloatType

    with pytest.raises(ValueError, match=f"unsupported model_type "
                                         f"'{model_type}'"):
        hf.spec_from_config({"model_type": model_type}, FloatType.Q40)
