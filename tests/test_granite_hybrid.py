"""GRANITE_HYBRID (Mamba-2 state-space layers with a per-slot recurrent state
and a short convolution beside full-attention layers without rotation,
softmax-routed experts and a shared expert in EVERY layer under a pre-norm
block, four published multipliers) against the plain reference
`benchmark/reference/granitemoehybrid.py`, at tiny size on the CPU; the three
rules a state needs; and the share test that ties a chip's held experts to
the uncut layer.

Logits, not tokens: with random weights the largest logit changes on
rounding. float32 compute and cache, so the program's chunked scan and the
reference's token-by-token one differ by summation order only: hence the
1e-4 class limits (float32 has 24 bits; six layers of sums of a few hundred
terms leave 1e-6 to 1e-5, and a wrong mechanism reads 1e-2 or more, as the
controls at the end of this file show).
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

from reference import granitemoehybrid as ref  # noqa: E402
from test_olmo_hybrid import (chunk_call, decode_call, engine,  # noqa: E402
                              rel_l2, slot_run, state_of)

from distributed_llama_tpu.io.model_file import (model_tensor_plan,  # noqa: E402
                                                 read_model, read_spec,
                                                 write_model)
from distributed_llama_tpu.models import LayerKind  # noqa: E402
from distributed_llama_tpu.models.params import load_params  # noqa: E402
from distributed_llama_tpu.ops.pallas_ssd import ssd_scan  # noqa: E402
from distributed_llama_tpu.runtime.engine import Engine  # noqa: E402
from distributed_llama_tpu.runtime.scheduler import Scheduler  # noqa: E402
from distributed_llama_tpu.sampler import Sampler  # noqa: E402
from distributed_llama_tpu.testing import (tiny_granite_spec,  # noqa: E402
                                           tiny_spec, write_fixture)

SEQ = 128
F32 = jnp.float32


def draw(spec, seed: int) -> dict:
    """Weights that keep every mechanism alive: std 1/sqrt(fan-in)
    projections, norms and the skip weight D near 1, decays from a few
    tokens to hundreds, convolution taps of the default size, a small
    convolution bias."""
    rng = np.random.default_rng(seed)
    tensors = {}
    for name, shape, _ in model_tensor_plan(spec):
        x = rng.standard_normal(shape).astype(np.float32)
        if "rms" in name or name.endswith("ssm_d"):
            x = 1.0 + 0.1 * x
        elif name.endswith("a_log"):
            x = np.log(rng.uniform(0.05, 8.0, shape)).astype(np.float32)
        elif name.endswith("dt_bias"):
            x = rng.uniform(-3.0, 0.0, shape).astype(np.float32)
        elif name.endswith("conv_w"):
            x = rng.uniform(-0.5, 0.5, shape).astype(np.float32)
        elif name.endswith("conv_b"):
            x = 0.1 * x
        else:
            x = x / np.sqrt(shape[-1])
        tensors[name] = x
    return tensors


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    d = tmp_path_factory.mktemp("granite")
    spec = tiny_granite_spec(seq_len=SEQ)
    path = str(d / "model.m")
    write_model(path, spec, draw(spec, 5))
    spec, tensors = read_model(path)
    params = load_params(spec, tensors, mode="q40", dtype=F32)
    tokens = np.random.default_rng(1).integers(3, 288, 70).astype(np.int32)
    return path, spec, params, tokens, ref.forward(path, tokens)


@pytest.mark.parametrize("kernels,limit", [(False, 1e-4), (True, 2e-4)],
                         ids=["xla", "pallas-interpret"])
def test_slot_prefill_then_decode_agree_with_reference(tiny, kernels, limit):
    """Chunks of 32 up to position 60 (a whole chunk, then a tail of 28),
    then 10 decode steps from the carried state, one slot of three, the
    others gated; against the reference's full forward (token-by-token
    recurrence). The interpreted kernels (Q40 matmul, grouped experts,
    attention, cache write, both SSD kernels together) get twice the room;
    the SSD kernels are held to their twin below."""
    _, spec, params, tokens, want = tiny
    eng = engine(spec, params, kernels=kernels)
    got = slot_run(eng, tokens, 60, 32, row=1)
    assert sorted(got) == list(range(59, 70))
    for at, lg in got.items():
        assert rel_l2(lg, want[at]) < limit, at


def test_cache_holds_one_leaf_set_a_layer_kind(tiny):
    """Rows for the 2 attention layers, state and tail for the 4 SSM
    layers, shaped by the layer KIND's one statement, and NO context-sized
    leaf for an SSM layer."""
    _, spec, params, _, _ = tiny
    c = engine(spec, params).cache
    assert (len(c.k), len(c.v), len(c.s), len(c.conv)) == (2, 2, 4, 4)
    assert c.k[0].shape == (3, 2, SEQ, 16)
    assert c.s[0].shape == (3, 8, 16, 32) and c.s[0].dtype == F32
    assert c.conv[0].shape == (3, 3, 128 + 2 * 32)
    assert spec.state_leaves(LayerKind.SSM) == ((8, 16, 32), (3, 192))
    assert all(SEQ not in x.shape for x in (*c.s, *c.conv))
    assert spec.cache_index == (0, 1, 0, 2, 3, 1)
    assert spec.cache_values_per_token == 2 * 2 * (16 + 16)
    assert spec.state_bytes_per_slot(4) == 4 * (8 * 16 * 32 * 4 + 3 * 192 * 4)


@pytest.mark.parametrize("chunk,n_prompt,kernels", [
    (8, 50, False), (16, 50, False), (32, 50, False),
    (2, 11, True), (1, 6, True)],     # the SLO ladder's last rungs
    ids=["8", "16", "32", "2-interpret", "1-interpret"])
def test_chunk_widths_agree(tiny, chunk, n_prompt, kernels):
    """50 prompt tokens in chunks of 8, 16 or 32 (each with a padded tail),
    then 3 decode steps: the reference's logits whatever the width, so the
    served segment is no parameter of the function computed. Chunks of 2
    and of 1, narrower than the convolution's tail and than anything
    `ssd_chunk` takes, run the mapped twin under the identity map, with
    the other kernels on."""
    _, spec, params, tokens, want = tiny
    got = slot_run(engine(spec, params, kernels=kernels),
                   tokens[:n_prompt + 3], n_prompt, chunk, row=0)
    for at, lg in got.items():
        assert rel_l2(lg, want[at]) < (2e-4 if kernels else 1e-4), (chunk, at)


def test_a_whole_segment_runs_as_chunks_of_32(tiny):
    """The shared-position path (`Engine.prefill`): 67 prompt tokens in one
    segment are two chunks of 32 and one of 3 inside the XLA twin; then
    `step` decodes from the state."""
    _, spec, params, tokens, want = tiny
    eng = engine(spec, params, batch=1)
    lg = eng.prefill([int(t) for t in tokens[:67]])
    assert rel_l2(np.asarray(lg)[0], want[66]) < 1e-4
    lg = eng.step(np.asarray([[tokens[67]]], np.int32), 67)
    assert rel_l2(np.asarray(lg)[0], want[67]) < 1e-4


def test_a_reused_slot_starts_from_zeros(tiny):
    """Rule 1: a chunk that starts at position 0 starts from a zero state
    and a zero tail, whatever the slot's last request left."""
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
    the logits that the same 20 alone leave."""
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


def _scan_inputs(rng, b, t, h=8, p=16, n=32, g=1):
    x = rng.standard_normal((b, t, h, p)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (b, t, h))).astype(
        np.float32)
    a = -rng.uniform(0.01, 16.0, h).astype(np.float32)
    bm, cm = (rng.standard_normal((b, t, g, n)).astype(np.float32)
              for _ in "bc")
    state = rng.standard_normal((b, h, p, n)).astype(np.float32)
    return [jnp.asarray(v) for v in (x, dt, a, bm, cm, state)]


def _token_by_token(x, dt, a, bm, cm, state, n_valid, fresh):
    """The recurrence one token after another, in float64."""
    x, dt, a, bm, cm, state = (np.asarray(v, np.float64)
                               for v in (x, dt, a, bm, cm, state))
    b, t, h, _ = x.shape
    per = h // bm.shape[2]
    y = np.zeros(x.shape)
    s = state.copy()
    for i in range(b):
        if fresh[i] and n_valid[i]:
            s[i] = 0.0
        for j in range(int(n_valid[i])):
            for hh in range(h):
                s[i, hh] = (np.exp(dt[i, j, hh] * a[hh]) * s[i, hh]
                            + dt[i, j, hh] * np.outer(x[i, j, hh],
                                                      bm[i, j, hh // per]))
                y[i, j, hh] = s[i, hh] @ cm[i, j, hh // per]
    return y, s


@pytest.mark.parametrize("t", [1, 8, 32])
@pytest.mark.parametrize("n_valid,fresh", [
    ([32, 0, 20, 32], [0, 0, 1, 1]),      # live, gated, tail + fresh, fresh
    ([0, 32, 0, 5], [0, 0, 0, 0]),        # gated rows first and between
    ([0, 0, 0, 0], [0, 0, 0, 0]),         # no live row at all (warm-up)
], ids=["mixed", "gated-first", "all-gated"])
def test_ssd_kernels_equal_their_twin_and_the_recurrence(t, n_valid, fresh):
    """ssd_decode (t = 1) and ssd_chunk in interpret mode, their one XLA
    twin (the same chunk algebra) and the token-by-token recurrence in
    float64 agree on the outputs of the tokens that count and on the new
    state, at ragged n_valid; a gated row's state is bit-equal to what came
    in and its outputs are zeros."""
    rng = np.random.default_rng(t)
    args = _scan_inputs(rng, 4, t)
    nv = np.minimum(np.asarray(n_valid, np.int32), t)
    fr = np.asarray(fresh, bool)
    want_y, want_s = _token_by_token(*args, nv, fr)
    for kernel in (False, True):
        y, s = ssd_scan(*args, jnp.asarray(nv), jnp.asarray(fr),
                        use_pallas=kernel, interpret=kernel)
        y, s = np.asarray(y), np.asarray(s)
        for i in range(4):
            np.testing.assert_allclose(y[i, :nv[i]], want_y[i, :nv[i]],
                                       rtol=1e-4, atol=1e-4)
            if nv[i] == 0:
                assert np.array_equal(s[i], np.asarray(args[5])[i])
                assert not y[i].any()
        np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-5)


def test_the_twin_takes_groups_and_long_segments():
    """What the kernels do not take (two groups, 40 tokens in one call)
    runs in the twin, and is the recurrence."""
    rng = np.random.default_rng(3)
    args = _scan_inputs(rng, 2, 40, h=4, p=8, n=16, g=2)
    nv, fr = np.asarray([40, 33], np.int32), np.asarray([True, False])
    want_y, want_s = _token_by_token(*args, nv, fr)
    y, s = ssd_scan(*args, jnp.asarray(nv), jnp.asarray(fr), use_pallas=True,
                    interpret=True)
    np.testing.assert_allclose(np.asarray(y)[1, :33], want_y[1, :33],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=1e-4, atol=1e-5)


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["xla", "pallas-interpret"])
@pytest.mark.parametrize("heads", [4, 16],
                         ids=["one-head-block", "two-head-blocks"])
@pytest.mark.parametrize("k,fresh", [(2, True), (5, False), (8, True)])
def test_chained_rows_of_a_slot_are_the_same_segments_one_a_program(
        k, fresh, heads, kernels):
    """`ssd_scan` under a slot map: rows 0..k-1 are consecutive segments of
    slot 3 (the last a tail of 20 tokens, the first fresh or not), the rows
    left over gated and naming the other slots (`chain_map`). Each row
    starts from the final state of the row before it INSIDE the call, and
    the outputs, slot 3's final state and every other slot's state are BIT
    for bit those of the same k segments one a call (float32 handed over is
    the same float32 written and read back); and the recurrence's, token by
    token, within rounding. With one head block a row (4 heads: no whole
    block of 8, so all heads at once) and with two."""
    from distributed_llama_tpu.ops.pallas_ssd import chained_rows
    from distributed_llama_tpu.runtime.scheduler import chain_map

    b, t, slot = 8, 32, 3
    x, dt, a, bm, cm, state = _scan_inputs(np.random.default_rng(k), b, t,
                                           h=heads)
    nv = np.asarray([t] * (k - 1) + [20] + [0] * (b - k), np.int32)
    fr = np.asarray([fresh] + [False] * (b - 1))

    def scan(rows, slots, n_valid, fr, state):
        slots, n_valid = jnp.asarray(slots), jnp.asarray(n_valid, jnp.int32)
        return ssd_scan(x[rows], dt[rows], a, bm[rows], cm[rows], state,
                        n_valid, jnp.asarray(fr), slots,
                        chained_rows(slots, n_valid), use_pallas=kernels,
                        interpret=kernels)

    y, s = scan(np.arange(b), chain_map(slot, k, b), nv, fr, state)
    s_seq = state
    for i in range(k):      # segment i alone, in row 0 of a call of its own
        rows = np.asarray([i] + [r for r in range(b) if r != i])
        y_i, s_seq = scan(rows, chain_map(slot, 1, b),
                          [nv[i]] + [0] * (b - 1),
                          [bool(fr[i])] + [False] * (b - 1), s_seq)
        np.testing.assert_array_equal(_bits(y[i, :nv[i]]),
                                      _bits(y_i[0, :nv[i]]))
    np.testing.assert_array_equal(_bits(s), _bits(s_seq))
    others = np.asarray([i for i in range(b) if i != slot])
    np.testing.assert_array_equal(_bits(s)[others], _bits(state)[others])
    assert not np.asarray(y[k:]).any()
    # and it is the recurrence over the slot's tokens in their order
    n_tok = int(nv.sum())
    flat = lambda v: v[:k].reshape(1, k * t, *v.shape[2:])[:, :n_tok]  # noqa: E731
    want_y, want_s = _token_by_token(
        flat(x), flat(dt), a, flat(bm), flat(cm), state[slot:slot + 1],
        [n_tok], [fresh])
    np.testing.assert_allclose(np.asarray(flat(y)), want_y, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s[slot]), want_s[0], rtol=1e-4,
                               atol=1e-5)


def test_live_slots_side_by_side_keep_their_own_states():
    """The identity map, as several slots that prefill pass it: three live
    rows of three slots and gated rows between them advance their own
    states and nobody else's, as the map-less call does: the kernel's body
    is the same, so bit for bit; the twin runs a row at a time where it ran
    them side by side, so within float32's rounding, the gated rows' states
    to the bit."""
    from distributed_llama_tpu.ops.pallas_ssd import chained_rows

    args = _scan_inputs(np.random.default_rng(9), 6, 32, h=16)
    nv = jnp.asarray([0, 32, 7, 0, 32, 0], jnp.int32)
    fr = jnp.asarray([False, True, False, False, False, False])
    slots = jnp.arange(6, dtype=jnp.int32)
    for kernels in (False, True):
        want = ssd_scan(*args, nv, fr, use_pallas=kernels, interpret=kernels)
        got = ssd_scan(*args, nv, fr, slots, chained_rows(slots, nv),
                       use_pallas=kernels, interpret=kernels)
        for w, g in zip(want, got):
            if kernels:
                np.testing.assert_array_equal(_bits(w), _bits(g))
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6)
        gated = np.asarray(nv) == 0
        np.testing.assert_array_equal(_bits(got[1])[gated],
                                      _bits(args[5])[gated])


def _todays_short_conv(xin, tail, lw, rows, taps):
    """`_short_conv` as it stood before a slot map could reach it."""
    import jax
    from jax import lax

    t = xin.shape[1]
    tail = jnp.where(rows.fresh[:, None, None], 0, tail)
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
    return y, tail


@pytest.mark.parametrize("t", [32, 2], ids=["chunk32", "chunk2"])
def test_the_convolutions_tail_chains_and_the_mapless_trace_is_todays(t):
    """`_short_conv` under a slot map: a chained row's tail is what the row
    before it leaves (its last taps - 1 inputs; at a chunk of 2 tokens,
    shorter than the tail, the last of [its tail ; its inputs]), the output
    and the slot's new tail are bit for bit those of the same segments one
    a call, the last live row alone writes the leaf, and no other slot's
    tail moves. Without a map the function traces to what it traced before
    the map existed (olmo's layers and every decode step)."""
    import jax

    import distributed_llama_tpu.models.transformer as tr
    from distributed_llama_tpu.ops.pallas_ssd import chained_rows, last_rows
    from distributed_llama_tpu.runtime.scheduler import chain_map

    rng = np.random.default_rng(t)
    b, k, ch, taps, slot = 8, 5, 24, 4, 6
    xin = jnp.asarray(rng.standard_normal((b, t, ch)), F32)
    conv = jnp.asarray(rng.standard_normal((b, taps - 1, ch)), F32)
    lw = {"conv_w": jnp.asarray(rng.uniform(-.5, .5, (taps, ch)), F32),
          "conv_b": jnp.asarray(0.1 * rng.standard_normal(ch), F32)}

    def rows_of(slots, nv, fresh=False):
        slots, nv = jnp.asarray(slots), jnp.asarray(nv, jnp.int32)
        chained = chained_rows(slots, nv)
        return tr.SegmentRows(nv, jnp.asarray([fresh] + [False] * (b - 1)),
                              slots, chained, last_rows(chained, nv))

    nv = [t] * (k - 1) + [max(t - 1, 1)] + [0] * (b - k)
    y, leaf = tr._short_conv(xin, conv, lw, rows_of(chain_map(slot, k, b),
                                                    nv), taps)
    seq = conv
    for i in range(k):
        order = np.asarray([i] + [r for r in range(b) if r != i])
        y_i, seq = tr._short_conv(
            xin[order], seq, lw,
            rows_of(chain_map(slot, 1, b), [nv[i]] + [0] * (b - 1)), taps)
        np.testing.assert_array_equal(_bits(y[i, :nv[i]]),
                                      _bits(y_i[0, :nv[i]]))
    np.testing.assert_array_equal(_bits(leaf), _bits(seq))
    keep = np.asarray([i for i in range(b) if i != slot])
    np.testing.assert_array_equal(_bits(leaf)[keep], _bits(conv)[keep])
    assert not np.array_equal(_bits(leaf)[slot], _bits(conv)[slot])

    plain = tr.SegmentRows(jnp.asarray(nv, jnp.int32),
                           jnp.asarray([True] + [False] * (b - 1)))
    assert str(jax.make_jaxpr(
        lambda x, c: tr._short_conv(x, c, lw, plain, taps))(xin, conv)) == str(
        jax.make_jaxpr(lambda x, c: _todays_short_conv(
            x, c, lw, plain, taps))(xin, conv))


@pytest.mark.parametrize("t", [1, 8])
def test_both_attention_paths_take_the_published_scale(t):
    """attention_multiplier is ONE number handed to the attention that runs
    (the kernel's score scale or the XLA path's), not folded into q: at
    1/128 a head of 16 gives other weights than 16^-1/2, and the two paths
    agree with each other."""
    from distributed_llama_tpu.ops.attention import decode_attention
    from distributed_llama_tpu.ops.pallas_attention import flash_attention

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, t, 4, 16)) * 8, jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 2, 128, 16)), jnp.float32)
            for _ in range(2))
    pos = jnp.asarray([[40 + i for i in range(t)],
                       [90 + i for i in range(t)]], jnp.int32)
    want = np.asarray(decode_attention(q, k, v, pos, scale=1 / 128))
    got = np.asarray(flash_attention(q, k, v, pos, interpret=True,
                                     scale=1 / 128))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    default = np.asarray(flash_attention(q, k, v, pos, interpret=True))
    assert np.abs(default - want).max() > 1e-2
    np.testing.assert_allclose(
        default, np.asarray(decode_attention(q, k, v, pos)), rtol=1e-5,
        atol=1e-5)


def test_scheduler_counts_experts_in_state_layers_too(tiny):
    """The served path, three slots, kernels interpreted (so the grouped
    expert path and its counters run): a request emits the greedy tokens it
    emits alone; the gauges read the spec's one statement; and every one of
    the 6 layers (4 of them SSM) counts its live pairs."""
    _, spec, params, tokens, _ = tiny
    greedy = lambda: Sampler(spec.vocab_size, temperature=0.0, topp=0.9,  # noqa: E731
                             seed=1)
    prompt = [int(x) for x in tokens[:21]]
    alone = engine(spec, params, batch=1).generate(prompt, 6,
                                                   greedy()).tokens
    eng = engine(spec, params, batch=3, kernels=True)
    sched = Scheduler(eng, chunk=8)
    assert sched.stats.cache_bytes_per_token == 2 * 2 * 32 * 4
    assert sched.stats.state_bytes_per_slot == spec.state_bytes_per_slot(4)
    req = sched.submit(prompt, 6, greedy())
    for _ in range(200):
        if req.finished.is_set():
            break
        sched.step()
    assert list(req.tokens(timeout=5.0)) == alone
    s = sched.stats
    # 21 prompt tokens and 5 decoded ones through 6 MoE layers; of a
    # token's 4 chosen experts those among the 4 held count
    assert 0 < s.expert_pairs_prefill <= 21 * 4 * spec.n_layers
    assert 0 < s.expert_pairs_decode <= 5 * 4 * spec.n_layers
    assert s.expert_reads_decode <= s.expert_pairs_decode
    routing: list = []
    ref.forward(tiny[0], np.asarray(prompt, np.int32), routing=routing)
    assert len(routing) == spec.n_layers
    held = sum(int((r["top_i"] < spec.n_experts).sum()) for r in routing)
    assert s.expert_pairs_prefill == held


def test_header_and_tensor_plan_round_trip(tiny, tmp_path):
    path, spec, _, _, _ = tiny
    want = tiny_granite_spec(seq_len=SEQ)
    for f in dataclasses.fields(want):
        assert getattr(spec, f.name) == pytest.approx(
            getattr(want, f.name), rel=1e-6), f.name
    assert spec.layer_kinds == ((LayerKind.SSM,) * 2
                                + (LayerKind.ATTENTION,)) * 2
    assert spec.has_state and spec.n_state_layers == 4
    names = [n for n, _, _ in model_tensor_plan(spec)]
    assert names[1:12] == [f"layers.0.{w}" for w in (
        "wz", "wx", "wbc", "wdt", "wo", "conv_w", "conv_b", "a_log",
        "dt_bias", "ssm_d", "rms_o")]
    assert "layers.2.wq" in names and "layers.2.conv_w" not in names
    assert all(f"layers.{l}.moe_router" in names
               and f"layers.{l}.sh_w1" in names for l in range(6))
    # the reference's own reader walks the same file to its last byte
    mf = ref.GraniteFile(path)
    assert mf.end == os.path.getsize(path)
    assert [n for n, _, _ in mf._plan()] == names
    assert mf.h["residual_scale"] == pytest.approx(0.22) and mf.kind(2) == 0
    # without a convolution bias the plan holds no conv_b
    assert not any(n.endswith("conv_b") for n, _, _ in model_tensor_plan(
        dataclasses.replace(spec, ssm_conv_bias=0)))
    # a LLAMA header gains no key: the file is the parent's, byte for byte
    llama, _ = write_fixture(str(tmp_path), spec=tiny_spec())
    with open(llama, "rb") as f:
        f.seek(4)
        assert int.from_bytes(f.read(4), "little") == 8 + 14 * 8
    assert read_spec(llama).residual_scale == 1.0


def test_streamed_loader_builds_the_same_leaves(tiny):
    """models/loader (what the CLI uses) and load_params agree leaf for
    leaf, the fused gate | x projection and the stacked B | C | dt rows
    too."""
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
    moe = {"moe_router", "moe_up", "moe_gate", "moe_down", "sh_w1", "sh_w2",
           "sh_w3", "rms_att", "rms_ffn"}
    assert set(streamed["layers"][0]) == moe | {
        "wzx", "w_bcdt", "wo", "conv_w", "conv_b", "a_log", "dt_bias",
        "ssm_d", "rms_o"}
    assert set(streamed["layers"][2]) == moe | {"wqkv", "wo"}
    assert streamed["layers"][0]["w_bcdt"].shape == (2 * 32 + 8, 64)


def test_real_size_products_are_the_issues():
    """granite-4.0-h-small's two products: cache bytes a token over the 4
    layers that have a cache, state bytes a slot over the 36 that have a
    state (4,194,304 B of state and 3 x 8,448 bf16 tail values a layer)."""
    period = [3] * 5 + [0] + [3] * 4
    spec = tiny_granite_spec(
        dim=4096, n_heads=32, n_kv_heads=8, n_layers=40,
        mixers=tuple(period * 4), ssm_heads=128, ssm_head_dim=64,
        ssm_d_state=128)
    assert spec.cache_values_per_token * 2 == 16_384
    assert spec.state_leaves(LayerKind.SSM) == ((128, 64, 128), (3, 8448))
    assert spec.state_bytes_per_slot(2) == 152_819_712
    assert (spec.n_cache_layers, spec.n_state_layers) == (4, 36)


def test_synthetic_weights_draw_the_published_initialisation(tmp_path):
    from distributed_llama_tpu.testing import write_synthetic_model

    spec = tiny_granite_spec(dim=256, n_heads=4, n_kv_heads=2, ssm_heads=16,
                             ssm_head_dim=32)
    path = str(tmp_path / "m.m")
    write_synthetic_model(path, spec, 7)
    _, tensors = read_model(path)
    a = np.exp(tensors["layers.0.a_log"].to_f32())
    dt = np.log1p(np.exp(tensors["layers.0.dt_bias"].to_f32()))
    assert 0 < a.min() and a.max() <= 16
    assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-6
    wx = tensors["layers.0.wx"].to_f32()
    assert abs(wx.mean()) < 0.05 * wx.std()          # zero-mean nibbles
    assert tensors["layers.0.wdt"].to_f32().std() < 0.15 * wx.std()
    assert np.abs(tensors["layers.0.conv_w"].to_f32()).max() <= 0.5
    # the skip weight near 1 as published; the convolution's bias small
    assert abs(tensors["layers.0.ssm_d"].to_f32().mean() - 1.0) < 0.05
    assert np.abs(tensors["layers.0.conv_b"].to_f32()).max() < 0.2


@pytest.mark.parametrize("flags,says", [
    (["--prefix-cache"], "--prefix-cache"),
    (["--kv-transfer"], "--kv-transfer"),
    (["--draft", "self:2"], "--draft"),
    (["--tp", "2"], "--tp / --pp / --sp / --ep"),
    (["--ep", "2"], "--tp / --pp / --sp / --ep"),
    (["--session", "s.npz"], "--session"),
])
def test_what_assumes_rows_is_refused_at_start_up(tiny, flags, says):
    """STATE_REFUSALS apply through `has_state` as they are: one clear
    message each, from the header, before anything is loaded."""
    from distributed_llama_tpu.apps.dllama import main

    path = tiny[0]
    tok = os.path.join(os.path.dirname(path), "tok.t")
    with pytest.raises(SystemExit) as e:
        main(["inference", "--model", path, "--tokenizer", tok,
              "--prompt", "x", "--steps", "1"] + flags)
    assert "GRANITE_HYBRID keeps a recurrent state" in str(e.value)
    assert says in str(e.value)


def test_two_shares_add_up_to_the_uncut_layer(tmp_path):
    """THE SHARE TEST. An uncut model routes over 8 experts and holds all
    8; two chips hold experts 0-3 and 4-7 of the same router. The routed
    parts the program computes for the two shares (each normalising its
    gates over all the chosen four, summing its own experts' terms only),
    with the shared expert counted ONCE, add up to the uncut reference's
    layer output, in an SSM layer and in an ATTENTION layer."""
    from distributed_llama_tpu.models.transformer import _moe_ffn

    whole = tiny_granite_spec(seq_len=SEQ, n_experts=8)
    tensors = draw(whole, 11)
    path = str(tmp_path / "whole.m")
    write_model(path, whole, tensors)
    mf = ref.GraniteFile(path)
    u = np.random.default_rng(2).standard_normal((2, 9, 64)).astype(
        np.float32)
    cfg = dict(activation_q80=False, compute_dtype=F32, use_pallas=False,
               tp_mesh=None, tp_reduce="exact", pallas_interpret=False)
    for l in (0, 2):
        want = np.stack([np.asarray(ref.experts(mf, l, jnp.asarray(row)))
                         for row in u])
        total = np.zeros_like(want)
        for offset in (0, 4):
            share = dataclasses.replace(whole, n_experts=4,
                                        expert_offset=offset)
            kept = {}
            for name, x in tensors.items():
                parts = name.split(".")
                if "experts" in parts:
                    e = int(parts[3])
                    if not offset <= e < offset + 4:
                        continue
                    parts[3] = str(e - offset)
                kept[".".join(parts)] = x
            spath = str(tmp_path / f"share{offset}.m")
            write_model(spath, share, kept)
            spec, host = read_model(spath)
            assert spec.router_width == 8 and spec.expert_offset == offset
            lw = dict(load_params(spec, host, mode="q40",
                                  dtype=F32)["layers"][l])
            if offset:          # the shared expert is counted once
                for w in ("sh_w1", "sh_w2", "sh_w3"):
                    del lw[w]
            total += np.asarray(_moe_ffn(jnp.asarray(u), lw, spec, cfg))
        assert rel_l2(total, want) < 1e-5, l


@pytest.mark.parametrize("name,least", [
    ("served", None), ("rows_fp8", None), ("state_bf16", 3e-4),
    ("state_zeroed_between_chunks", 0.01), ("pad_tokens_advance", 0.05),
    ("dt_bias_dropped", 0.05), ("router_next_best", 0.05)])
def test_the_checks_controls_break_what_they_name(tiny, name, least):
    """tools/granite_hybrid_controls.py, the chip-side controls of the
    logits check: each swaps ONE thing of the program and puts it back. At
    tiny size in float32 the served path agrees with the reference to 1e-4
    and every control that can run on a CPU does not (an fp8 dot cannot: its
    control is a flag of the CLI)."""
    import granite_hybrid_controls as tool
    import jax

    import distributed_llama_tpu.models.transformer as tr
    import distributed_llama_tpu.ops.pallas_ssd as ssd

    _, spec, params, tokens, want = tiny
    before = (ssd.ssd_scan, tr._segment_rows, jax.lax.top_k)
    flags, spec_change, change, patch = tool.controls(
        spec.router_width)[name]
    if name == "rows_fp8":
        assert flags == ["--cache-dtype", "f8"]
        return
    assert not flags and not spec_change
    with patch():
        got = slot_run(engine(spec, change(params) if change else params),
                       tokens, 60, 32, row=1)
    assert (ssd.ssd_scan, tr._segment_rows, jax.lax.top_k) == before
    worst = max(rel_l2(lg, want[at]) for at, lg in got.items())
    assert worst < 1e-4 if least is None else worst > least, worst


def test_the_chip_side_proof_of_chaining_runs_at_tiny_size(tmp_path, capsys):
    """`tools/granite_hybrid_controls.py --chained`, what the chip runs at
    full depth before any cell is timed, at the bench test's tiny
    configuration on the CPU (B=4, chunks of 8: 32 tokens are one 4-row
    program, 30 end on a tail, 70 are 3 + 3 + 3 against 9): every leaf and
    both logits bit-equal, the other slots' noise untouched, and a verdict
    that fails where one leaf differs."""
    import json

    import granite_hybrid_controls as tool
    from test_granite_hybrid_bench import TINY

    cfg = tmp_path / "tiny-granite-test.json"
    cfg.write_text(json.dumps(TINY))
    argv = ["--chained", "--config", str(cfg), "--cache", str(tmp_path),
            "--lengths", "32", "30", "70", "--slot", "2",
            "--out", str(tmp_path / "out" / "bits.json"),
            "--engine-flags", "--compute-dtype", "f32", "--cache-dtype",
            "f32", "--buffer-float-type", "f32"]
    assert tool.chained_against_one_a_program(argv) == 0
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict == json.loads((tmp_path / "out" / "bits.json").read_text())
    assert verdict["bit_equal"] and sorted(verdict["lengths"]) == [
        "30", "32", "70"]
    assert verdict["lengths"]["70"]["programs"] == [3, 9]
    for v in verdict["lengths"].values():
        # 2 K + 2 V + 4 states + 4 tails
        assert v["leaves_compared"] == 12 and v["state_norm"] > 0
        assert not v["differ"] and not v["other_slots_moved"]
    # the comparison can fail: with the state zeroed at every program's
    # start, three programs and nine do not leave the same bits
    import distributed_llama_tpu.models.transformer as tr

    with tool.swapped(tr, "_segment_rows",
                      tool.rows_zeroed(tr._segment_rows)):
        assert tool.chained_against_one_a_program(
            argv[:6] + ["70"] + argv[9:11] + argv[13:]) == 1
    broken = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"logits", "s[0]"} <= set(broken["lengths"]["70"]["differ"])
