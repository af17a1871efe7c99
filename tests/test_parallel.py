"""Tensor-parallel sharding tests on a virtual 8-device CPU mesh.

Closes the reference's testing gap — it has NO automated multi-node test
(SURVEY.md §4); slicing was only checked shard-by-shard in-process
(ref: src/transformer-test.cpp:21-72). Here the real SPMD program runs on 8
XLA devices and must match the single-device result.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_llama_tpu.models import ArchType, HiddenAct, ModelSpec
from distributed_llama_tpu.models.params import load_params, random_tensors
from distributed_llama_tpu.models.transformer import KVCache, forward
from distributed_llama_tpu.parallel import (
    make_mesh,
    param_pspecs,
    q80_psum,
    shard_params,
)
from distributed_llama_tpu.quants import QuantizedTensor
from distributed_llama_tpu.runtime import Engine
from distributed_llama_tpu.sampler import Sampler

from test_model_forward import make_spec, dense_weights


def test_mesh_axes():
    mesh = make_mesh(tp=4, dp=2)
    assert mesh.shape == {"dp": 2, "sp": 1, "ep": 1, "pp": 1, "tp": 4}


@pytest.mark.parametrize("arch", [ArchType.LLAMA, ArchType.MIXTRAL])
@pytest.mark.parametrize("mode", ["dense", "q40"])
def test_tp_forward_matches_single_device(arch, mode):
    # q40 col-splits must keep whole 32-blocks per shard: dim >= 32*tp
    spec = make_spec(arch, dim=128, n_heads=8, n_kv_heads=4, hidden_dim=256)
    host, _ = dense_weights(spec, seed=5)
    params = load_params(spec, host, mode=mode, dtype=jnp.float32)

    tok = jnp.array([[7]], jnp.int32)
    ref_logits, _ = forward(params, spec, tok, jnp.int32(0), KVCache.create(spec, 1))

    mesh = make_mesh(tp=4, dp=1)
    engine = Engine(spec, params, mesh, compute_dtype=jnp.float32,
                    cache_dtype=jnp.float32)
    got = engine.step(np.array([[7]], np.int32), 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_logits), rtol=0, atol=2e-4)


def test_tp_multi_step_decode_matches():
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4)
    host, _ = dense_weights(spec, seed=6)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)

    toks = [3, 9, 27, 81]
    # single device
    cache = KVCache.create(spec, 1)
    ref = []
    for i, t in enumerate(toks):
        lg, cache = forward(params, spec, jnp.array([[t]], jnp.int32), jnp.int32(i), cache)
        ref.append(np.asarray(lg))
    # 4-way TP (tp must divide n_kv_heads=4, the reference's nSlices rule)
    mesh = make_mesh(tp=4)
    engine = Engine(spec, params, mesh, compute_dtype=jnp.float32,
                    cache_dtype=jnp.float32)
    for i, t in enumerate(toks):
        got = engine.step(np.array([[t]], np.int32), i)
        np.testing.assert_allclose(np.asarray(got), ref[i], rtol=0, atol=5e-4)


def test_dp_tp_mesh_runs():
    """2-way data parallel x 4-way tensor parallel, batch=2."""
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4)
    host, _ = dense_weights(spec, seed=7)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    mesh = make_mesh(tp=4, dp=2)
    engine = Engine(spec, params, mesh, batch=2, compute_dtype=jnp.float32,
                    cache_dtype=jnp.float32)
    logits = engine.step(np.array([[5], [11]], np.int32), 0)
    assert logits.shape == (2, spec.vocab_size)
    # row 0 must equal a single-device run of token 5
    ref, _ = forward(params, spec, jnp.array([[5]], jnp.int32), jnp.int32(0),
                     KVCache.create(spec, 1))
    np.testing.assert_allclose(np.asarray(logits)[0], np.asarray(ref)[0], rtol=0, atol=5e-4)


def test_param_pspecs_cover_all_leaves():
    spec = make_spec(ArchType.GROK1)
    host, _ = dense_weights(spec, seed=8)
    for mode in ("dense", "q40"):
        params = load_params(spec, host, mode=mode)
        specs = param_pspecs(params)
        assert set(specs) == set(params)

        def check(w, sp):
            if isinstance(w, QuantizedTensor):
                assert len(sp.packed) == w.packed.ndim
                assert len(sp.scales) == w.scales.ndim
            else:
                assert len(sp) == w.ndim

        for name, w in params.items():
            if name == "layers":
                for lw, lsp in zip(w, specs[name]):
                    assert set(lsp) == set(lw)
                    for k in lw:
                        check(lw[k], lsp[k])
            else:
                check(w, specs[name])


def test_q80_psum_matches_psum():
    """Quantized all-reduce ~ exact all-reduce (the reference's Q80 wire,
    ref: src/tasks.cpp:124-163)."""
    from jax import shard_map

    mesh = make_mesh(tp=8)
    x = np.random.default_rng(0).standard_normal((8, 4, 64)).astype(np.float32)

    @jax.jit
    def exact(x):
        f = shard_map(lambda v: jax.lax.psum(v, "tp"), mesh=mesh,
                      in_specs=P("tp"), out_specs=P(), check_vma=False)
        return f(x)

    @jax.jit
    def quantized(x):
        f = shard_map(lambda v: q80_psum(v[0], "tp")[None], mesh=mesh,
                      in_specs=P("tp"), out_specs=P(), check_vma=False)
        return f(x)

    a = np.asarray(exact(x))
    b = np.asarray(quantized(x))
    # int8 blocks: small relative error on the reduced values
    assert np.abs(a - b).max() < 8 * np.abs(x).max() / 127 * 1.1


def test_q80_psum_2shot_matches_psum():
    """Two-shot quantized all-reduce ~ exact all-reduce; chunk-block-aligned
    path (the wire-efficient form of the reference's Q80 exchange)."""
    from jax import shard_map

    from distributed_llama_tpu.parallel import q80_psum_2shot

    mesh = make_mesh(tp=8)
    # last dim 512 = 8 shards x 2 blocks: exercises the all_to_all path
    x = np.random.default_rng(1).standard_normal((8, 4, 512)).astype(np.float32)

    @jax.jit
    def exact(x):
        f = shard_map(lambda v: jax.lax.psum(v, "tp"), mesh=mesh,
                      in_specs=P("tp"), out_specs=P(), check_vma=False)
        return f(x)

    @jax.jit
    def quantized(x):
        f = shard_map(lambda v: q80_psum_2shot(v[0], "tp", 8)[None], mesh=mesh,
                      in_specs=P("tp"), out_specs=P(), check_vma=False)
        return f(x)

    a = np.asarray(exact(x))
    b = np.asarray(quantized(x))
    # double quantization (partials + reduced chunk): 2x the one-shot bound
    assert np.abs(a - b).max() < 2 * 8 * np.abs(x).max() / 127 * 1.1


@pytest.mark.parametrize("arch", [ArchType.LLAMA, ArchType.MIXTRAL])
@pytest.mark.parametrize("mode", ["dense", "q40"])
def test_tp_q80_collectives_match_exact(arch, mode):
    """q80-collective TP forward (shard_map + quantized all-reduce on wo/w2)
    ~ GSPMD-exact TP forward within block-quant tolerance (VERDICT r1 #2;
    ref wire compression: src/tasks.cpp:124-163)."""
    from distributed_llama_tpu.parallel.tp_q80 import TpColWeight

    spec = make_spec(arch, dim=256, n_heads=8, n_kv_heads=4, hidden_dim=512)
    host, _ = dense_weights(spec, seed=11)
    params = load_params(spec, host, mode=mode, dtype=jnp.float32)
    mesh = make_mesh(tp=4)

    exact = Engine(spec, params, mesh, compute_dtype=jnp.float32,
                   cache_dtype=jnp.float32)
    q80 = Engine(spec, params, mesh, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32, q80_collectives=True)
    # col weights actually repacked into the shard_map stacked form
    assert isinstance(q80.params["layers"][0]["wo"], TpColWeight)
    if arch == ArchType.MIXTRAL:
        assert isinstance(q80.params["layers"][0]["moe_down"], TpColWeight)

    toks = [7, 3, 1]
    for i, t in enumerate(toks):
        a = np.asarray(exact.step(np.array([[t]], np.int32), i))
        b = np.asarray(q80.step(np.array([[t]], np.int32), i))
        # per-layer quantized exchange: error bounded by a few block-quant
        # steps on the residual stream; logits stay close
        np.testing.assert_allclose(b, a, rtol=0, atol=0.05)
        assert np.argmax(a) == np.argmax(b)


def test_repack_col_tp_roundtrip():
    """The stacked (tp, d, n/tp) shards hold exactly the logical column
    slices of the original weight, for dense and Q40 forms."""
    from distributed_llama_tpu.parallel.tp_q80 import repack_col_tp
    from distributed_llama_tpu.quants.jax_codec import dequantize_q40_jax
    from distributed_llama_tpu.quants.numpy_codec import quantize_q40

    rng = np.random.default_rng(3)
    w = rng.standard_normal((16, 256), dtype=np.float32) * 0.1
    tp = 4

    # dense
    stacked = repack_col_tp(jnp.asarray(w), tp).w
    for k in range(tp):
        np.testing.assert_array_equal(np.asarray(stacked[k]),
                                      w[:, k * 64:(k + 1) * 64])

    # q40: per-shard dequant == dequant-of-slice
    scales, packed = quantize_q40(w)
    qt = QuantizedTensor.from_numpy(scales, packed)
    full = np.asarray(dequantize_q40_jax(qt, dtype=jnp.float32))
    stacked_q = repack_col_tp(qt, tp).w
    for k in range(tp):
        shard = QuantizedTensor(stacked_q.packed[k], stacked_q.scales[k])
        np.testing.assert_allclose(
            np.asarray(dequantize_q40_jax(shard, dtype=jnp.float32)),
            full[:, k * 64:(k + 1) * 64], rtol=0, atol=1e-6)


def test_engine_generate_greedy():
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4)
    host, _ = dense_weights(spec, seed=9)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    mesh = make_mesh(tp=2)
    engine = Engine(spec, params, mesh, compute_dtype=jnp.float32,
                    cache_dtype=jnp.float32, prefill_chunk=4)
    sampler = Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)
    result = engine.generate([1, 5, 9], max_tokens=5, sampler=sampler)
    assert len(result.tokens) == 5
    # greedy is deterministic: same prompt, same continuation
    engine.reset()
    result2 = engine.generate([1, 5, 9], max_tokens=5, sampler=sampler)
    assert result.tokens == result2.tokens
    avg = result.stats.averages()
    assert avg.generation_ms > 0


def test_device_greedy_decode_matches_host_loop():
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4)
    host, _ = dense_weights(spec, seed=10)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)

    # the whole decode loop on the device, greedy (temperature 0) ...
    engine = Engine(spec, params, mesh=None, compute_dtype=jnp.float32,
                    cache_dtype=jnp.float32)
    toks_dev = engine.generate_device([3], 7, temperature=0.0, topp=0.9,
                                      seed=1)

    # ... against the host loop, one round trip a token
    engine2 = Engine(spec, params, mesh=None, compute_dtype=jnp.float32,
                     cache_dtype=jnp.float32)
    sampler = Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)
    res = engine2.generate([3], max_tokens=7, sampler=sampler)
    assert toks_dev == res.tokens and len(toks_dev) == 7


def test_generate_batch_matches_independent_runs():
    """VERDICT r1 #4: batch=4 greedy generation over a dp mesh matches 4
    independent single-sequence runs token-for-token (ragged prompt lengths,
    per-row positions/eos)."""
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4)
    host, _ = dense_weights(spec, seed=12)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    prompts = [[1, 5, 9], [2], [7, 3, 3, 3, 8], [4, 4]]

    greedy = Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)
    refs = []
    for p in prompts:
        eng = Engine(spec, params, compute_dtype=jnp.float32,
                     cache_dtype=jnp.float32)
        refs.append(eng.generate(p, max_tokens=6, sampler=greedy).tokens)

    mesh = make_mesh(tp=2, dp=4)
    eng_b = Engine(spec, params, mesh, batch=4, compute_dtype=jnp.float32,
                   cache_dtype=jnp.float32)
    outs = eng_b.generate_batch(prompts, max_tokens=6, sampler=greedy)
    assert outs == refs


def test_generate_batch_sampled_reproducible_and_distinct():
    """Sampled batch generation (vectorized host sampler): a fixed seed
    reproduces exactly; identical prompts still diverge because the
    shared interleaved xorshift stream gives each row different coins."""
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4)
    host, _ = dense_weights(spec, seed=13)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    prompts = [[1, 5, 9]] * 3

    def run():
        s = Sampler(spec.vocab_size, temperature=0.9, topp=0.9, seed=5,
                    backend="python")
        eng = Engine(spec, params, batch=3, compute_dtype=jnp.float32,
                     cache_dtype=jnp.float32)
        return eng.generate_batch(prompts, max_tokens=8, sampler=s)

    a, b = run(), run()
    assert a == b  # deterministic for a fixed seed
    assert len({tuple(r) for r in a}) > 1  # interleaved stream: rows differ


def test_generate_batch_eos_stops_row():
    """A row sampling the stop token halts while other rows continue."""
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4)
    host, _ = dense_weights(spec, seed=13)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    prompts = [[1, 5], [2, 8]]

    greedy = Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)
    ref0 = Engine(spec, params, compute_dtype=jnp.float32,
                  cache_dtype=jnp.float32).generate(
        prompts[0], max_tokens=8, sampler=greedy).tokens
    # use row 0's third greedy token as the "eos": row 0 must truncate there
    eos = ref0[2]

    eng_b = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                   cache_dtype=jnp.float32)
    outs = eng_b.generate_batch(prompts, max_tokens=8, sampler=greedy,
                                eos_id=eos)
    assert outs[0] == ref0[: ref0.index(eos) + 1]
    assert len(outs[1]) >= 1


def test_generate_batch_stops_at_context_limit():
    """Per-row overflow: a row at seq_len stops exactly where generate()
    would; no clamped rewrites leak extra tokens."""
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4)  # seq 16
    host, _ = dense_weights(spec, seed=14)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    greedy = Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1)

    long_p = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]
    ref = Engine(spec, params, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32).generate(
        long_p, max_tokens=10, sampler=greedy).tokens
    assert len(ref) == 1 + (spec.seq_len - len(long_p))  # context-limited

    eng_b = Engine(spec, params, batch=2, compute_dtype=jnp.float32,
                   cache_dtype=jnp.float32)
    outs = eng_b.generate_batch([long_p, [1, 2]], max_tokens=10, sampler=greedy)
    assert outs[0] == ref
    assert len(outs[1]) == 10  # short row unaffected by the exhausted one


def test_generate_batch_stream_stop_flags_retire_rows():
    """generate_batch_stream: collecting the stream equals generate_batch
    (it IS generate_batch's engine), and a caller-set stop_flags[i]
    retires row i between steps — the API server's stop-sequence scan
    runs on decoded text the engine cannot see."""
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4)
    host, _ = dense_weights(spec, seed=14)
    params = load_params(spec, host, mode="dense", dtype=jnp.float32)
    prompts = [[1, 5, 9], [2, 7], [4]]

    def greedy():
        return Sampler(spec.vocab_size, temperature=0.0, topp=0.9, seed=1,
                       backend="python")

    eng = Engine(spec, params, batch=3, compute_dtype=jnp.float32,
                 cache_dtype=jnp.float32)
    want = eng.generate_batch(prompts, max_tokens=6, sampler=greedy())

    eng.reset()
    got = [[] for _ in prompts]
    for step in eng.generate_batch_stream(prompts, 6, greedy()):
        for i, t in enumerate(step):
            if t is not None:
                got[i].append(t)
    assert got == want

    # retire row 1 after its second token: rows 0/2 must be unaffected
    # (greedy rows are independent; the sampler draws no coins at temp 0)
    eng.reset()
    flags = np.zeros(3, bool)
    got2 = [[] for _ in prompts]
    for step in eng.generate_batch_stream(prompts, 6, greedy(),
                                          stop_flags=flags):
        for i, t in enumerate(step):
            if t is not None:
                got2[i].append(t)
        if len(got2[1]) >= 2:
            flags[1] = True
    assert got2[0] == want[0] and got2[2] == want[2]
    assert got2[1] == want[1][:2]


def test_force_mesh_kernels_one_device_parity():
    """The one-device configuration of the multi-chip kernel path:
    a 1-device Mesh(('tp',)) engine with force_mesh_kernels=True routes
    every Q40 matmul through the shard_map Pallas wrappers (TpRowWeight at
    tp == 1) and must reproduce the direct-kernel engine's greedy stream
    exactly. Interpret mode here."""
    spec = make_spec(ArchType.LLAMA, dim=64, n_heads=8, n_kv_heads=4,
                     vocab_size=128, seq_len=64)
    host, _ = dense_weights(spec, seed=3)

    def greedy():
        return Sampler(spec.vocab_size, 0.0, 0.9, 1, backend="python")

    p1 = load_params(spec, host, mode="q40", dtype=jnp.float32)
    e1 = Engine(spec, p1, compute_dtype=jnp.float32,
                cache_dtype=jnp.float32, use_pallas=True,
                pallas_interpret=True)
    want = e1.generate([1, 5, 9], 8, greedy()).tokens

    mesh = make_mesh(tp=1, devices=jax.devices()[:1])
    p2 = load_params(spec, host, mode="q40", dtype=jnp.float32)
    e2 = Engine(spec, p2, mesh, compute_dtype=jnp.float32,
                cache_dtype=jnp.float32, use_pallas=True,
                pallas_interpret=True, force_mesh_kernels=True)
    from distributed_llama_tpu.parallel.tp_q80 import TpRowWeight
    assert any(isinstance(v, TpRowWeight)
               for v in e2.params["layers"][0].values())
    got = e2.generate([1, 5, 9], 8, greedy()).tokens
    assert got == want


@pytest.mark.slow  # full dryrun compile in a subprocess (~100 s)
def test_dryrun_pins_cpu_before_any_jax_call():
    # dryrun_multichip must succeed with NO ambient cpu pin: it is a CPU
    # check on virtual devices, and its own config pin must land before
    # any backend initializes (it refuses a process that already has one)
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    code = ("import __graft_entry__ as g; g.dryrun_multichip(2); "
            "print('DRYRUN_OK')")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600.0, env=env, cwd=repo)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DRYRUN_OK" in r.stdout
