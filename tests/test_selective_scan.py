"""The selective scan (ops/pallas_selective_scan.py: Mamba-1's recurrence,
whose decay is a number a (state index, channel) pair) against the
token-by-token recurrence in float64: the Pallas kernels in interpret mode
and the XLA twin, slow and fast channels in one call, under the three rules
a state needs (fresh, gated, padded tail), along a slot map whose rows
chain, and with the state or the exponent in a lower precision.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from distributed_llama_tpu.ops import pallas_selective_scan as ss  # noqa: E402
from distributed_llama_tpu.ops.pallas_ssd import chained_rows  # noqa: E402

N = 16            # the published state size


@pytest.fixture(scope="module", autouse=True)
def _free_compiled_programs():
    """As tests/test_kda.py's: compiled programs outlive their tests here
    (tests/conftest.py), so the module gives its own back."""
    yield
    jax.clear_caches()


def scan_inputs(rng, b, t, d=256):
    """Channels whose decay a token runs from exp(-1e-3 x 1e-3) to
    exp(-16 x 1): A uniform in (0, 16) as published, dt log-uniform in
    [0.001, 1]."""
    x = rng.standard_normal((b, t, d)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), 0.0, (b, t, d))).astype(np.float32)
    a = -rng.uniform(1e-3, 16.0, (N, d)).astype(np.float32)
    bm, cm = (rng.standard_normal((b, t, N)).astype(np.float32) for _ in "bc")
    state = rng.standard_normal((b, N, d)).astype(np.float32)
    return [jnp.asarray(v) for v in (x, dt, a, bm, cm, state)]


def token_by_token(x, dt, a, bm, cm, s0, n):
    """One row's first n tokens from s0 (N, D), in float64."""
    x, dt, a, bm, cm = (np.asarray(v, np.float64) for v in (x, dt, a, bm, cm))
    h, ys = np.asarray(s0, np.float64).copy(), []
    for t in range(n):
        h = (np.exp(dt[t][None, :] * a) * h
             + (dt[t] * x[t])[None, :] * bm[t][:, None])
        ys.append((h * cm[t][:, None]).sum(0))
    return np.asarray(ys).reshape(n, x.shape[-1]), h


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["xla", "pallas-interpret"])
@pytest.mark.parametrize("t", [1, 8, 16, 32])
@pytest.mark.parametrize("n_valid,fresh", [
    ([32, 0, 20, 32], [0, 0, 1, 1]),      # live, gated, tail + fresh, fresh
    ([0, 32, 0, 5], [0, 0, 0, 0]),        # gated rows first and between
    ([0, 0, 0, 0], [0, 0, 0, 0]),         # no live row at all (warm-up)
], ids=["mixed", "gated-first", "all-gated"])
def test_selective_scan_equals_the_recurrence(t, n_valid, fresh, kernel):
    """Outputs of the tokens that count and the new state, to float32
    rounding, slow channels (dt A ~ -1e-6) and fast ones (dt A ~ -16)
    in one call; a gated row's state is bit-equal to what came in and a
    tail chunk's pad tokens neither decay nor write."""
    rng = np.random.default_rng(t)
    args = scan_inputs(rng, 4, t)
    nv = np.minimum(np.asarray(n_valid, np.int32), t)
    fr = np.asarray(fresh, bool)
    y, s = ss.selective_scan(*args, jnp.asarray(nv), jnp.asarray(fr),
                             use_pallas=kernel, interpret=kernel)
    y, s = np.asarray(y), np.asarray(s)
    assert np.isfinite(y).all() and np.isfinite(s).all()
    x, dt, a, bm, cm, s0 = args
    for i in range(4):
        if nv[i] == 0:
            assert np.array_equal(s[i], np.asarray(s0)[i])
            assert not y[i].any()
            continue
        start = np.zeros((N, 256)) if fr[i] else s0[i]
        want_y, want_s = token_by_token(x[i], dt[i], a, bm[i], cm[i], start,
                                        nv[i])
        np.testing.assert_allclose(y[i, :nv[i]], want_y, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(s[i], want_s, rtol=1e-4, atol=1e-5)


def test_a_channel_that_forgets_at_once_beside_one_that_never_does():
    """dt A = -1e-3 x 1e-3 (a channel that holds for a million tokens) and
    dt A = -16 x 0.1 and -16 x 4 (gone in one) side by side in one chunk:
    no factorisation over a chunk's running sum survives that spread, and
    the scan, which never forms one, reads the recurrence."""
    rng = np.random.default_rng(3)
    x, dt, a, bm, cm, s0 = (np.asarray(v) for v in scan_inputs(rng, 1, 32))
    a = np.full_like(a, -1e-3)
    a[:, 1::3], a[:, 2::3] = -16.0, -16.0
    dt = np.full_like(dt, 1e-3)
    dt[..., 1::3], dt[..., 2::3] = 0.1, 4.0
    nv, fr = jnp.asarray([32], jnp.int32), jnp.asarray([False])
    want_y, want_s = token_by_token(x[0], dt[0], a, bm[0], cm[0], s0[0], 32)
    for kernel in (False, True):
        y, s = ss.selective_scan(*(jnp.asarray(v) for v in
                                   (x, dt, a, bm, cm, s0)), nv, fr,
                                 use_pallas=kernel, interpret=kernel)
        np.testing.assert_allclose(np.asarray(y)[0], want_y, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(s)[0], want_s, rtol=1e-4,
                                   atol=1e-5)
    # the slow channels kept what they held, the fastest nothing of it
    assert np.abs(want_s[:, 0::3] - s0[0][:, 0::3]).max() < 0.2
    assert np.abs(want_s[:, 2::3]).max() < 50 * np.abs(
        (dt * x)[0, -1, 2::3]).max()


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["xla", "pallas-interpret"])
@pytest.mark.parametrize("t", [1, 16])
def test_rows_of_one_slot_chain_through_the_call(t, kernel):
    """Rows 0-2 are consecutive segments of slot 2 (the last a tail), row 3
    is gated and names slot 0: slot 2's state is what the 2 t + tail tokens
    leave from zeros, its outputs the recurrence's, and every other slot's
    state is bit-equal to what came in."""
    rng = np.random.default_rng(7 + t)
    x, dt, a, bm, cm, s0 = scan_inputs(rng, 4, t)
    tail = max(t - 9, 1)
    slots = jnp.asarray([2, 2, 2, 0], jnp.int32)
    nv = jnp.asarray([t, t, tail, 0], jnp.int32)
    fr = jnp.asarray([True, False, False, False])
    y, s = ss.selective_scan(x, dt, a, bm, cm, s0, nv, fr, slots,
                             chained_rows(slots, nv), use_pallas=kernel,
                             interpret=kernel)
    cat = lambda v: np.concatenate([np.asarray(v)[0], np.asarray(v)[1],  # noqa: E731
                                    np.asarray(v)[2, :tail]])
    want_y, want_s = token_by_token(cat(x), cat(dt), a, cat(bm), cat(cm),
                                    np.zeros((N, 256)), 2 * t + tail)
    np.testing.assert_allclose(cat(y), want_y, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s)[2], want_s, rtol=1e-4,
                               atol=1e-5)
    for i in (0, 1, 3):
        assert np.array_equal(np.asarray(s)[i], np.asarray(s0)[i])


def test_the_kernels_are_the_twin_to_rounding():
    """Chunk and decode kernels against the XLA twin on the same operands:
    the same order of operations a token, so float32 rounding alone."""
    rng = np.random.default_rng(5)
    for t in (1, 16):
        args = scan_inputs(rng, 3, t, d=384)
        nv = jnp.asarray([t, 0, max(t - 2, 1)], jnp.int32)
        fr = jnp.asarray([False, False, True])
        y0, s0 = ss.selective_scan(*args, nv, fr)
        y1, s1 = ss.selective_scan(*args, nv, fr, use_pallas=True,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                                   rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                                   rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("t", [1, 16])
def test_a_row_of_several_channel_blocks(t, monkeypatch):
    """Every served size is ONE channel block a row (CHANNEL_BLOCK is
    d_inner); the blocks stay because the block is what was tuned on the
    chip (PERF.md section 6, PR 52), so they are held here: three blocks
    of 128 channels a row, a gated row between live ones, rows of one slot
    chained in the chunk, against the twin."""
    monkeypatch.setattr(ss, "CHANNEL_BLOCK", 128)
    rng = np.random.default_rng(11 + t)
    args = scan_inputs(rng, 4, t, d=384 + 128 * t)    # shapes no test shares
    nv = jnp.asarray([t, t, 0, max(t - 3, 1)], jnp.int32)
    fr = jnp.asarray([True, False, False, False])
    slots = jnp.asarray([1, 1, 0, 3], jnp.int32) if t > 1 else None
    chain = (slots, chained_rows(slots, nv)) if t > 1 else ()
    y0, s0 = ss.selective_scan(*args, nv, fr, *chain)
    y1, s1 = ss.selective_scan(*args, nv, fr, *chain, use_pallas=True,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-5,
                               atol=1e-5)
    assert np.array_equal(np.asarray(s1)[2 if t == 1 else 0],
                          np.asarray(args[5])[2 if t == 1 else 0])


def test_what_the_kernels_take():
    assert ss.selective_scan_supported(1, 5120, 16)
    assert ss.selective_scan_supported(16, 5120, 16)
    assert not ss.selective_scan_supported(12, 5120, 16)   # not whole tiles
    assert not ss.selective_scan_supported(64, 5120, 16)   # past a chunk
    assert not ss.selective_scan_supported(16, 5000, 16)   # not whole lanes
    assert ss._block(5120, ss.CHANNEL_BLOCK) == 5120
    assert ss._block(8192, ss.CHANNEL_BLOCK) == 4096
    assert ss._block(256, ss.CHANNEL_BLOCK) == 256
    assert ss._block(384, 256) == 128


def _decode_steps(steps, state_bits=None, exponent_bf16=False, writes=True):
    """`steps` decode programs over one row: (the state at the start, the
    float32 state, the state under the lower precision)."""
    rng = np.random.default_rng(3)
    d = 128
    a = jnp.asarray(-rng.uniform(1e-3, 16.0, (N, d)), jnp.float32)
    state = jnp.asarray(rng.standard_normal((1, N, d)), jnp.float32)
    one = jnp.ones((1,), jnp.int32)
    exp = jnp.exp

    @jax.jit
    def step(held, kept, x, bm, cm):
        dt = jnp.full((1, 1, d), 0.002, jnp.float32)
        _, held = ss.selective_scan(x, dt, a, bm, cm, held, one, one == 0)
        if exponent_bf16:
            jnp.exp = lambda v: exp(v.astype(jnp.bfloat16)).astype(v.dtype)
        try:
            _, kept = ss._scan_xla(x, dt, a, bm, cm, kept, one == 0)
        finally:
            jnp.exp = exp
        if state_bits:
            kept = jax.lax.reduce_precision(kept, exponent_bits=8,
                                            mantissa_bits=state_bits)
        return held, kept

    held, kept = state, state
    for _ in range(steps):
        x = jnp.asarray(rng.standard_normal((1, 1, d)), jnp.float32)
        bm, cm = (jnp.asarray(rng.standard_normal((1, 1, N)), jnp.float32)
                  for _ in "bc")
        held, kept = step(held, kept, x, bm if writes else 0 * bm, cm)
    return state, held, kept


def test_a_bf16_state_stops_a_slow_channels_decay_where_no_token_writes():
    """The lower-precision control of the state: rounded to bf16 after
    every decode program it cannot decay by less than half a unit in the
    last place, so the slow (state index, channel) pairs (dt A > -2e-3 a
    step) keep ALL they hold over 200 steps that write nothing, where
    float32 lets each decay at its own rate."""
    state, held, kept = _decode_steps(200, state_bits=7, writes=False)
    off = float(jnp.linalg.norm(kept - held) / jnp.linalg.norm(held))
    assert off > 0.05, off
    assert float(jnp.abs(held / state).max()) < 1.0     # every pair decayed
    assert float((jnp.abs(kept) == jnp.abs(
        jax.lax.reduce_precision(state, 8, 7))).mean()) > 0.05


def test_the_exponent_in_bf16_moves_the_state_past_float32_rounding():
    """The lower-precision control of the scan's exponent: exp(dt A) taken
    in bf16 is off by up to 2^-9 a token, which the state carries on; over
    50 writing steps the state parts from the float32 one by 3e-4 and
    more, a hundred times float32's own rounding."""
    _, held, kept = _decode_steps(50, exponent_bf16=True)
    off = float(jnp.linalg.norm(kept - held) / jnp.linalg.norm(held))
    assert off > 3e-4, off
    _, held, same = _decode_steps(50)
    assert float(jnp.linalg.norm(same - held)
                 / jnp.linalg.norm(held)) < 1e-5
