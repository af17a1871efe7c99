"""The KDA rule (ops/pallas_kda.py: the gated delta rule with a decay a key
CHANNEL) against the token-by-token recurrence in float64: the Pallas
kernels in interpret mode and the XLA twin, at weak and at strong decays,
under the three rules a state needs (fresh, gated, padded tail), and against
the scalar rule where the decay is constant over the channels.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from distributed_llama_tpu.ops.pallas_delta_rule import delta_rule  # noqa: E402
from distributed_llama_tpu.ops.pallas_kda import kda_rule  # noqa: E402


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


def rule_inputs(rng, b, t, g_low, h=4, dk=32, dv=64):
    """g uniform in (g_low, 0) a token a channel."""
    q, k = (rng.standard_normal((b, t, h, dk)).astype(np.float32)
            for _ in "qk")
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q *= dk ** -0.5 / np.linalg.norm(q, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, h, dv)).astype(np.float32)
    g = rng.uniform(g_low, -1e-4, (b, t, h, dk)).astype(np.float32)
    beta = rng.uniform(0.0, 1.0, (b, t, h)).astype(np.float32)
    state = rng.standard_normal((b, h, dk, dv)).astype(np.float32)
    return [jnp.asarray(x) for x in (q, k, v, g, beta, state)]


def token_by_token(q, k, v, g, beta, state, n_valid, fresh):
    """S' = Diag(exp g) S; S = S' + b k (v - S'^T k)^T; o = S^T q, one
    token after another, in float64."""
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
                sk = s[i, hh] * np.exp(g[i, j, hh])[:, None]     # (dk, dv)
                u = beta[i, j, hh] * (v[i, j, hh] - k[i, j, hh] @ sk)
                s[i, hh] = sk + np.outer(k[i, j, hh], u)
                o[i, j, hh] = q[i, j, hh] @ s[i, hh]
    return o, s


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["xla", "pallas-interpret"])
@pytest.mark.parametrize("g_low", [-0.1, -1.5, -8.0],
                         ids=["weak", "middling", "strong"])
@pytest.mark.parametrize("t", [1, 8, 32])
@pytest.mark.parametrize("n_valid,fresh", [
    ([32, 0, 20, 32], [0, 0, 1, 1]),      # live, gated, tail + fresh, fresh
    ([0, 32, 0, 5], [0, 0, 0, 0]),        # gated rows first and between
    ([0, 0, 0, 0], [0, 0, 0, 0]),         # no live row at all (warm-up)
], ids=["mixed", "gated-first", "all-gated"])
def test_kda_rule_equals_the_recurrence(t, n_valid, fresh, g_low, kernel):
    """Outputs of the tokens that count and the new state, to float32
    rounding, whatever the decay: at g down to -8 a token a channel the
    running sum reaches -256 inside a chunk and exp(-G) would be infinite;
    nothing here may be, and nothing may be NaN. A gated row's state is
    bit-equal to what came in."""
    rng = np.random.default_rng(t + int(-g_low * 10))
    args = rule_inputs(rng, 4, t, g_low)
    nv = np.minimum(np.asarray(n_valid, np.int32), t)
    fr = np.asarray(fresh, bool)
    want_o, want_s = token_by_token(*args, nv, fr)
    o, s = kda_rule(*args, jnp.asarray(nv), jnp.asarray(fr),
                    use_pallas=kernel, interpret=kernel)
    o, s = np.asarray(o), np.asarray(s)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    for i in range(4):
        np.testing.assert_allclose(o[i, :nv[i]], want_o[i, :nv[i]],
                                   rtol=1e-4, atol=1e-5)
        if nv[i] == 0:
            assert np.array_equal(s[i], np.asarray(args[5])[i])
            assert not o[i].any()
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-5)


def test_a_channel_that_forgets_at_once_beside_one_that_never_does():
    """One chunk whose channels decay by e^-30 a token (half of them) and
    not at all (the rest), with a token of g = 0 after a run of strong ones:
    the pair (i, i - 1) then has decay 1 where G has already fallen by
    hundreds, the case a clamp on the running sum would get wrong."""
    rng = np.random.default_rng(3)
    q, k, v, g, beta, state = rule_inputs(rng, 1, 32, -1.0)
    g = np.zeros(g.shape, np.float32)
    g[..., ::2] = -30.0
    g[:, 20:] = 0.0                       # tokens 20.. decay nothing
    nv, fr = np.asarray([32], np.int32), np.asarray([False])
    want_o, want_s = token_by_token(q, k, v, g, beta, state, nv, fr)
    for kernel in (False, True):
        o, s = kda_rule(q, k, v, jnp.asarray(g), beta, state,
                        jnp.asarray(nv), jnp.asarray(fr), use_pallas=kernel,
                        interpret=kernel)
        assert np.isfinite(np.asarray(o)).all()
        np.testing.assert_allclose(np.asarray(o), want_o, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(s), want_s, rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["xla", "pallas-interpret"])
@pytest.mark.parametrize("t", [1, 16, 32])
def test_a_decay_constant_over_the_channels_is_the_scalar_rule(t, kernel):
    """g broadcast over d_k gives delta_rule's outputs and state within
    float32 rounding (the two sum in another order)."""
    rng = np.random.default_rng(11 + t)
    q, k, v, g, beta, state = rule_inputs(rng, 3, t, -1.5)
    g1 = g[..., 0]
    nv = jnp.asarray(np.asarray([t, 0, max(t - 3, 1)], np.int32))
    fr = jnp.asarray(np.asarray([False, False, True]))
    o1, s1 = delta_rule(q, k, v, g1, beta, state, nv, fr,
                        use_pallas=kernel, interpret=kernel)
    o2, s2 = kda_rule(q, k, v, jnp.broadcast_to(g1[..., None], g.shape),
                      beta, state, nv, fr, use_pallas=kernel,
                      interpret=kernel)
    for i, n in enumerate(np.asarray(nv)):
        np.testing.assert_allclose(np.asarray(o2)[i, :n],
                                   np.asarray(o1)[i, :n], rtol=2e-5,
                                   atol=2e-6)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), rtol=2e-5,
                               atol=2e-6)


@pytest.mark.parametrize("writes", [False, True])
def test_a_bf16_state_stops_a_slow_channels_decay_where_no_token_writes(
        writes):
    """What the benchmark's check of `kimi-linear-48b-a3b-ep4` is built on
    (its configuration's `assumed.weights`): a state rounded to bf16 after
    every decode program cannot decay by less than half a unit in the last
    place, so a channel whose step is 0.002 keeps ALL it holds over 200
    steps that write nothing (beta 0) where float32 keeps e^-0.4 of it; a
    write at every step dithers the rounding and the two agree to a few
    parts in a thousand."""
    rng = np.random.default_rng(3)
    h, d, steps = 2, 16, 200
    state = jnp.asarray(rng.standard_normal((1, h, d, d)), jnp.float32)
    held, kept = state, state
    one = jnp.ones((1,), jnp.int32)
    for _ in range(steps):
        q, k, v = (jnp.asarray(rng.standard_normal((1, 1, h, d)), jnp.float32)
                   for _ in "qkv")
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        g = jnp.full((1, 1, h, d), -0.002, jnp.float32)
        beta = jnp.full((1, 1, h), 0.5 if writes else 0.0, jnp.float32)
        _, held = kda_rule(q, k, v, g, beta, held, one, one == 0)
        _, kept = kda_rule(q, k, v, g, beta, kept, one, one == 0)
        kept = jax.lax.reduce_precision(kept, exponent_bits=8,
                                        mantissa_bits=7)
    off = float(jnp.linalg.norm(kept - held) / jnp.linalg.norm(held))
    if writes:
        assert off < 0.03, off
    else:
        # float32 decayed to e^-0.4 = 0.67; bf16 still holds the start
        assert abs(float(jnp.linalg.norm(held) / jnp.linalg.norm(state))
                   - np.exp(-0.4)) < 1e-3
        assert float(jnp.linalg.norm(kept) / jnp.linalg.norm(state)) > 0.99
        assert off > 0.4, off
