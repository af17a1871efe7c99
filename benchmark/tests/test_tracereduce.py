"""Busy/idle union, gap listing and attribution on hand-made event lists."""

import pytest

import tracereduce as t


def test_union_merges_overlaps_and_nesting():
    iv = [(0.0, 1.0), (0.5, 1.5), (2.0, 3.0), (2.2, 2.4), (5.0, 5.0)]
    assert t.merge(iv) == [(0.0, 1.5), (2.0, 3.0)]
    assert t.busy_seconds(iv) == pytest.approx(2.5)


def test_gaps_cover_exactly_what_the_union_leaves():
    iv = [(1.0, 2.0), (1.5, 3.0), (4.0, 4.5)]
    gaps = t.idle_gaps(iv, (0.0, 6.0))
    assert gaps == [(0.0, 1.0), (3.0, 4.0), (4.5, 6.0)]
    assert sum(e - s for s, e in gaps) + t.busy_seconds(iv) == \
        pytest.approx(6.0)
    assert t.idle_gaps([], (0.0, 2.0)) == [(0.0, 2.0)]


def test_stems_are_stable_names():
    assert t.stem("jit_slot_decode_step(139873)") == "slot_decode_step"
    assert t.stem("%fusion.123 = bf16[8,4096] fusion(...)") == "fusion"
    assert t.stem("jit_slot_prefill_chunk_32(7)") == "slot_prefill_chunk_32"
    ks = ["q40_matmul", "q40_expert_matmul"]
    assert t.kernel_of("%q40_expert_matmul.7 = bf16[8] custom-call(%x)",
                       ks) == "q40_expert_matmul"
    # an op that only READS a kernel's result is not that kernel
    assert t.kernel_of("%copy.3 = bf16[8] copy(%q40_matmul.3)", ks) is None
    assert t.kernel_of("q40_matmul.3", ks) == "q40_matmul"


def hand_made():
    dev = {"modules": [(0.000, 0.010, "jit_slot_decode_step(1)"),
                       (0.100, 0.130, "jit_slot_prefill_chunk_32(2)"),
                       (0.200, 0.210, "jit_slot_decode_step(1)")],
           "ops": [(0.000, 0.004, "%q40_matmul.1 = bf16[8] custom-call(%p)"),
                   (0.004, 0.006, "%fusion.3 = bf16[8] fusion(%q40_matmul.1)"),
                   (0.006, 0.010, "%flash_attention.9 = bf16[8] custom-call(%q)"),
                   (0.100, 0.120, "q40_matmul.1"),
                   (0.120, 0.130, "q40_expert_matmul.4"),
                   (0.200, 0.205, "q40_matmul.1"),
                   (0.2005, 0.2010, "copy.2"),      # nested: no double count
                   (0.205, 0.210, "fusion.3")]}
    host = [(0.0, 0.3, "serve_forever"),
            (0.011, 0.095, "$sampler.py:88 sample"),
            (0.131, 0.199, "$scheduler.py:810 _decode"),
            (0.150, 0.1500001, "tiny")]
    return dev, host


def test_reduction_of_a_hand_made_trace():
    dev, host = hand_made()
    r = t.reduce_events([dev], host, ["q40_matmul", "q40_expert_matmul",
                                      "flash_attention"])
    assert r["window_s"] == pytest.approx(0.210)
    assert r["busy_s"] == pytest.approx(0.010 + 0.030 + 0.010)
    assert r["modules"]["slot_decode_step"]["count"] == 2
    assert r["modules"]["slot_prefill_chunk_32"]["device_s"] == pytest.approx(0.03)
    ex = r["executions"]
    assert ex[0]["kernel_s"] == {"q40_matmul": pytest.approx(0.004),
                                 "flash_attention": pytest.approx(0.004)}
    assert ex[1]["kernel_s"]["q40_expert_matmul"] == pytest.approx(0.010)
    ops = dict(r["device_ops"])
    assert ops["slot_decode_step/q40_matmul"] == pytest.approx(0.009)
    assert ops["slot_prefill_chunk_32/q40_matmul"] == pytest.approx(0.020)
    gaps = dict(r["idle_gaps"])
    # each gap goes to the innermost host event covering at least half of it
    assert gaps["$sampler.py:88 sample"] == pytest.approx(0.090)
    assert gaps["$scheduler.py:810 _decode"] == pytest.approx(0.070)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_gap_nobody_covers_is_unattributed_and_chips_are_averaged():
    dev, _ = hand_made()
    other = {"modules": [], "ops": [(0.0, 0.105, "fusion.1")]}
    r = t.reduce_events([dev, other], [], ["q40_matmul"])
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((0.050 + 0.105) / 2)
    assert dict(r["idle_gaps"])["unattributed"] == pytest.approx(0.160)
    assert t.reduce_events([{"modules": [], "ops": []}], [], []) == {}
