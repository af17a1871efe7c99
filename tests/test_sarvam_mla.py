"""SARVAM_MLA (latent attention, a leading dense layer, sigmoid-bias-routed
experts with a shared expert, an experts-held share) against the plain
reference `benchmark/reference/sarvam_mla.py`, at tiny size on the CPU.

Logits, not tokens: with random weights the largest logit changes on
rounding. float32 compute and cache, so the program's absorbed attention
and the reference's expanded form may differ by summation order only.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from reference import sarvam_mla as ref  # noqa: E402

from distributed_llama_tpu.io.model_file import (model_tensor_plan,  # noqa: E402
                                                 read_model, read_spec)
from distributed_llama_tpu.models import ArchType  # noqa: E402
from distributed_llama_tpu.models.params import load_params  # noqa: E402
from distributed_llama_tpu.models.transformer import (KVCache,  # noqa: E402
                                                      _moe_ffn, forward)
from distributed_llama_tpu.runtime.engine import Engine  # noqa: E402
from distributed_llama_tpu.runtime.prefix_cache import PrefixCache  # noqa: E402
from distributed_llama_tpu.runtime.scheduler import Scheduler  # noqa: E402
from distributed_llama_tpu.sampler import Sampler  # noqa: E402
from distributed_llama_tpu.testing import (tiny_mla_spec,  # noqa: E402
                                           tiny_spec, write_fixture)

SEQ = 128
F32 = jnp.float32


def write_mla(path: str, spec, seed: int) -> str:
    """A SARVAM_MLA `.m` whose weights keep activations at O(1) through
    every layer (std 1/sqrt(fan-in), norms near 1, biases that matter), so
    that attention, rope and the router's choice all move the logits;
    `write_fixture`'s 0.05-scale weights leave them nearly linear."""
    from distributed_llama_tpu.io.model_file import write_model

    rng = np.random.default_rng(seed)
    tensors = {}
    for name, shape, _ in model_tensor_plan(spec):
        x = rng.standard_normal(shape).astype(np.float32)
        if "rms" in name:
            x = 1.0 + 0.1 * x
        elif name.endswith("moe_bias"):
            x = 0.1 * x
        else:
            x = x / np.sqrt(shape[-1])
        tensors[name] = x
    write_model(path, spec, tensors)
    return path


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    d = tmp_path_factory.mktemp("mla")
    path = write_mla(str(d / "model.m"), tiny_mla_spec(seq_len=SEQ), 5)
    spec, tensors = read_model(path)
    params = load_params(spec, tensors, mode="q40", dtype=F32)
    tokens = np.random.default_rng(1).integers(3, 288, 70).astype(np.int32)
    return path, spec, params, tokens


def rel_l2(a, b) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64) - b)
                 / np.linalg.norm(b))


def _slot_run(eng, tokens, n_prompt, chunk, row):
    """Chunked slot prefill, then slot decode, through the latent cache;
    {position: logits} of the last prompt token and every decoded one."""
    b, seq = eng.batch, eng.seq_len
    got = {}
    for off in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - off)
        tok = np.zeros((b, chunk), np.int32)
        pos = np.full((b,), seq, np.int32)
        lidx = np.zeros((b,), np.int32)
        tok[row, :n] = tokens[off:off + n]
        pos[row], lidx[row] = off, n - 1
        logits = eng.slot_prefill_chunk(tok, pos, lidx)
    got[n_prompt - 1] = np.asarray(eng.fetch_logits(logits))[row]
    for i in range(n_prompt, len(tokens)):
        tok = np.zeros((b, 1), np.int32)
        pos = np.full((b,), seq, np.int32)
        tok[row, 0], pos[row] = tokens[i], i
        got[i] = np.asarray(eng.fetch_logits(
            eng.slot_decode_step(tok, pos)))[row]
    return got


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
def test_slot_prefill_then_decode_agree_with_reference(tiny, kernels):
    """Chunks of 8 up to position 60 (past yarn's original context of 32),
    then 10 decode steps, one slot of three, the others gated."""
    path, spec, params, tokens = tiny
    eng = Engine(spec, params, batch=3, compute_dtype=F32, cache_dtype=F32,
                 use_pallas=kernels, pallas_interpret=kernels)
    assert len(eng.cache.k) == spec.n_layers and eng.cache.v == ()
    assert eng.cache.k[0].shape == (3, 1, SEQ, 32 + 8)
    want = ref.forward(path, tokens)
    got = _slot_run(eng, tokens, 60, 8, row=1)
    assert sorted(got) == list(range(59, 70))
    for at, lg in got.items():
        assert rel_l2(lg, want[at]) < 1e-4, at


def test_absorbed_attention_equals_expanded(tiny):
    """One attention block: the program's absorbed form (queries folded
    through W_uk, the latent attended, W_uv unfolded) against the
    reference's per-head keys and values, both float32."""
    from distributed_llama_tpu.models.transformer import _mla_attention_block

    path, spec, params, _ = tiny
    mf = ref.MlaFile(path)
    t = 40
    x = np.random.default_rng(2).standard_normal((t, spec.dim)) \
        .astype(np.float32)
    cos, sin = ref.yarn_tables(mf.h, t)
    want = ref.highest(ref.attention)(mf, 1, jnp.asarray(x), cos, sin)
    cache = jnp.zeros((1, 1, SEQ, spec.cache_head_size), F32)
    cfg = dict(compute_dtype=F32)
    got, cache = _mla_attention_block(
        jnp.asarray(x)[None], params["layers"][1], spec, cache,
        jnp.arange(t, dtype=jnp.int32)[None], cfg, per_row_pos=True)
    assert rel_l2(got[0], np.asarray(want)) < 1e-5
    assert float(jnp.abs(cache[0, 0, t:]).max()) == 0.0


def test_dropping_the_rope_term_moves_the_logits(tiny):
    """The control the chip check repeats: without the rope term of the
    score the reference's own logits move far past any tolerance."""
    path, _, _, tokens = tiny
    assert rel_l2(ref.forward(path, tokens, rope=False)[-1],
                  ref.forward(path, tokens)[-1]) > 0.05


def _layer_input(spec, t=24, seed=3):
    return jnp.asarray(np.random.default_rng(seed)
                       .standard_normal((1, t, spec.dim)).astype(np.float32))


def test_shares_add_up_to_the_uncut_layer(tmp_path):
    """Four chips each hold 2 of 8 routed experts: their routed parts, plus
    the shared expert counted once, are what one chip holding all 8
    computes; and each share is the reference's for that share."""
    whole = tiny_mla_spec(n_experts=8, n_routed_experts=8, seq_len=SEQ)
    path = write_mla(str(tmp_path / "whole.m"), whole, 9)
    spec, tensors = read_model(path)
    p_whole = load_params(spec, tensors, mode="q40", dtype=F32)
    xb = _layer_input(spec)
    cfg = dict(compute_dtype=F32)
    l = 1
    full = np.asarray(_moe_ffn(xb, p_whole["layers"][l], spec, cfg))[0]
    lw = p_whole["layers"][l]
    # one row of one token takes the gather-the-chosen path: the same layer
    one = np.asarray(_moe_ffn(xb[:, :1], lw, spec, cfg))[0, 0]
    assert rel_l2(one, full[0]) < 1e-5
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
    # the reference, given share 1's file, computes share 1's part
    sub = dataclasses.replace(spec, n_experts=2, expert_offset=2)
    sub_path = str(tmp_path / "share1.m")
    _write_share(path, spec, sub, sub_path)
    mf = ref.MlaFile(sub_path)
    want = ref.highest(ref.moe)(mf, l, xb[0])
    assert rel_l2(parts[1] + shared, np.asarray(want)) < 1e-5


def _take_experts(w, start, n):
    from distributed_llama_tpu.quants.jax_codec import QuantizedTensor

    if isinstance(w, QuantizedTensor):
        return QuantizedTensor(w.packed[start:start + n],
                               w.scales[start:start + n])
    return w[start:start + n]


def _write_share(path, spec, sub, out_path):
    """The file of one share: the whole model's tensors with only the held
    experts [expert_offset, expert_offset + n_experts) kept."""
    from distributed_llama_tpu.io.model_file import write_model

    _, tensors = read_model(path)
    dense = {}
    for name, _, _ in model_tensor_plan(sub):
        src = name
        if ".experts." in name:
            parts = name.split(".")
            parts[3] = str(int(parts[3]) + sub.expert_offset)
            src = ".".join(parts)
        dense[name] = tensors[src].to_f32()
    write_model(out_path, sub, dense)


def test_bias_moves_the_choice_and_not_the_weights(tiny):
    """A large bias on one expert puts it among the chosen; its weight is
    still its sigmoid score over the chosen scores' sum, times 2.5."""
    path, spec, params, _ = tiny
    lw = dict(params["layers"][1])
    xb = _layer_input(spec, t=6)
    mf = ref.MlaFile(path)
    scores = np.asarray(jax_sigmoid(xb[0] @ np.asarray(
        mf.tensor("layers.1.moe_router")).T))
    loser = int(scores[0].argmin())          # never chosen by score alone
    bias = np.zeros(spec.router_width, np.float32)
    bias[loser] = 10.0
    top_i, top_w = ref.route(mf.h, jnp.asarray(scores), jnp.asarray(bias))
    top_i, top_w = np.asarray(top_i), np.asarray(top_w)
    assert (top_i == loser).any(axis=-1).all()
    plain_i, _ = ref.route(mf.h, jnp.asarray(scores), 0.0)
    assert not (np.asarray(plain_i)[0] == loser).any()
    chosen = np.take_along_axis(scores, top_i, -1)
    np.testing.assert_allclose(
        top_w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(top_w.sum(-1), 2.5, rtol=1e-6)
    # and the program routes the same way: with that bias its layer is the
    # reference's layer under that bias
    lw["moe_bias"] = jnp.asarray(bias)
    got = np.asarray(_moe_ffn(xb, lw, spec, dict(compute_dtype=F32)))[0]

    class Biased(ref.MlaFile):
        def tensor(self, name):
            if name == "layers.1.moe_bias":
                return jnp.asarray(bias)
            return super().tensor(name)

    want = ref.highest(ref.moe)(Biased(path), 1, xb[0])
    assert rel_l2(got, np.asarray(want)) < 1e-5


def jax_sigmoid(x):
    return 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))


def test_header_and_tensor_plan_round_trip(tiny, tmp_path):
    path, spec, _, _ = tiny
    want = tiny_mla_spec(seq_len=SEQ)
    for f in dataclasses.fields(want):
        a, b = getattr(spec, f.name), getattr(want, f.name)
        assert a == pytest.approx(b, rel=1e-6), f.name
    assert spec.head_size == 24 and spec.cache_head_size == 40
    assert spec.cache_v_head_size == 0
    assert spec.cache_values_per_token == 3 * 40
    names = [n for n, _, _ in model_tensor_plan(spec)]
    assert names[1:5] == ["layers.0.wq", "layers.0.wkva", "layers.0.wkvb",
                          "layers.0.wo"]
    assert "layers.0.w1" in names and "layers.0.moe_router" not in names
    assert "layers.1.moe_bias" in names and "layers.1.sh_w2" in names
    assert "layers.1.experts.3.down" in names
    assert "layers.1.experts.4.up" not in names        # 4 held of 8
    # the reference's own reader walks the same file to its last byte
    mf = ref.MlaFile(path)
    assert mf.end == os.path.getsize(path)
    assert [n for n, _, _ in mf._plan()] == names
    assert mf.h["rope_factor"] == 40.0 and mf.h["n_routed_experts"] == 8
    # a LLAMA header gains no key: the file is the parent's, byte for byte
    llama, _ = write_fixture(str(tmp_path), spec=tiny_spec())
    with open(llama, "rb") as f:
        f.seek(4)
        assert int.from_bytes(f.read(4), "little") == 8 + 14 * 8
    assert read_spec(llama).kv_lora_rank == 0


def test_publish_and_seed_an_arena_block_of_the_latent_shape(tiny):
    """slot_publish_block copies a slot's latent rows into an arena block
    (the V arena is zero wide); slot_seed_prefix writes them into another
    slot, bit for bit; export and import carry the same bytes."""
    _, spec, params, tokens = tiny
    eng = Engine(spec, params, batch=2, compute_dtype=F32, cache_dtype=F32)
    _slot_run(eng, tokens[:16], 16, 8, row=0)
    arena_k, arena_v = eng.new_prefix_arena(6, 8)
    # flat blocks: 40 is not whole lane tiles (engine.flat_arena)
    assert arena_k.shape == (6, spec.n_layers * 1, 8 * 40)
    assert arena_v.shape == (6, spec.n_layers * 1, 0)
    arena_k, arena_v = eng.slot_publish_block(arena_k, arena_v, 0, 8, 4)
    arena_k, arena_v = eng.slot_publish_block(arena_k, arena_v, 0, 0, 2)
    for l in range(spec.n_layers):
        np.testing.assert_array_equal(
            np.asarray(arena_k[4, l]).reshape(8, 40),
            np.asarray(eng.cache.k[l][0, 0, 8:16]))
    k_blk, v_blk = eng.block_export(arena_k, arena_v, 4)
    assert k_blk.shape == (spec.n_layers, 1, 8, 40)
    assert v_blk.shape == (spec.n_layers, 1, 8, 0)
    arena_k, arena_v = eng.slot_import_block(
        arena_k, arena_v, np.asarray(k_blk), np.asarray(v_blk), 5)
    ids = np.zeros((SEQ // 8,), np.int32)
    ids[:2] = (2, 5)
    before = [np.asarray(leaf[0]) for leaf in eng.cache.k]
    eng.slot_seed_prefix(arena_k, arena_v, 1, ids)
    for l in range(spec.n_layers):
        np.testing.assert_array_equal(np.asarray(eng.cache.k[l][1, 0, :16]),
                                      before[l][0, :16])
        np.testing.assert_array_equal(np.asarray(eng.cache.k[l][0]),
                                      before[l])
    assert eng.cache.v == ()


def test_scheduler_prefix_hit_matches_cold_run_and_counts_pairs(tiny):
    """The served path: a second request sharing two blocks is seeded from
    the latent arena and emits the cold run's greedy tokens; the window
    counters count (token, cached position) pairs on the host."""
    _, spec, params, tokens = tiny
    eng = Engine(spec, params, batch=2, compute_dtype=F32, cache_dtype=F32)
    pc = PrefixCache(eng, num_blocks=8, block_len=4)
    sched = Scheduler(eng, chunk=4, prefix_cache=pc)
    assert sched.stats.cache_bytes_per_token == 3 * 40 * 4
    greedy = Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)

    def run(prompt):
        req = sched.submit(prompt, 5, greedy)
        for _ in range(400):
            if req.finished.is_set():
                return list(req.tokens(timeout=5.0))
            sched.step()
        raise AssertionError("not finished")

    prompt = [int(t) for t in tokens[:11]]
    first = run(prompt)
    s = sched.stats
    # 11 prompt tokens at positions 0..10, then 4 decode rows at 11..14
    assert s.attn_pairs_prefill == sum(range(1, 12))
    assert s.attn_pairs_decode == sum(range(12, 16))
    assert s.prefill_cached_tokens == 4 + 8 + 11
    again = run(prompt)
    assert again == first and pc.stats.hits >= 1
    cold = Engine(spec, params, batch=1, compute_dtype=F32, cache_dtype=F32)
    assert cold.generate(prompt, 5, Sampler(
        spec.vocab_size, temperature=0.0, topp=0.9, seed=1)).tokens == first


@pytest.mark.parametrize("arch,extra", [
    (ArchType.LLAMA, {}),
    (ArchType.MIXTRAL, {"n_experts": 4, "n_active_experts": 2}),
], ids=["llama", "mixtral"])
def test_other_architectures_are_bit_equal_to_the_parent(arch, extra, tmp_path):
    """The shared code (norms, _moe_ffn, the cache write, the header) gives
    LLAMA and MIXTRAL the logits of the commit before SARVAM_MLA, to the
    bit: pinned as sums of the float32 logits' bit patterns."""
    spec = tiny_spec(arch=arch, **extra)
    path, _ = write_fixture(str(tmp_path), spec=spec, seed=77)
    spec, tensors = read_model(path)
    params = load_params(spec, tensors, mode="q40", dtype=F32)
    tokens = jnp.asarray(np.arange(3, 19, dtype=np.int32).reshape(2, 8))
    cache = KVCache.create(spec, 2, 32, F32)
    assert len(cache.v) == spec.n_layers
    logits, cache = forward(params, spec, tokens,
                            jnp.asarray([0, 3], jnp.int32), cache,
                            compute_dtype=F32)
    step, _ = forward(params, spec, tokens[:, :1],
                      jnp.asarray([8, 11], jnp.int32), cache,
                      compute_dtype=F32)
    got = [int(np.asarray(x).view(np.uint32).astype(np.uint64).sum())
           for x in (logits, step)]
    assert got == PARENT_LOGIT_BITS[arch.name], got


# computed on the parent commit (2a0fba1) by this very test body
PARENT_LOGIT_BITS = {
    "LLAMA": [1201127561032, 1206433011091],
    "MIXTRAL": [1213570753140, 1230644289911],
}
