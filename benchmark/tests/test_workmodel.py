"""Operations and bytes for both configurations against numbers worked out
by hand from the published sizes, and the peaks table."""

import json
import os

import pytest

import workmodel as w

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# Mistral-7B: a layer holds wq, wo 4096 x 4096, wk, wv 1024 x 4096 and three
# 14336 x 4096 matrices; the head is 32000 x 4096
ATT = 2 * 4096 * 4096 + 2 * 1024 * 4096          # 41,943,040
FFN = 3 * 14336 * 4096                            # 176,160,768
HEAD = 32000 * 4096                               # 131,072,000


def test_mistral_7b_decode_and_prefill_work():
    c = config("mistral-7b")
    assert ATT == 41_943_040 and FFN == 176_160_768
    values = 32 * (ATT + FFN) + HEAD
    assert values == 7_110_393_856                # "7.11 G Q40 values"
    dec = w.matmul_work(c, 8, logit_rows=8)
    assert dec["bytes"] == values * 18 / 32 == 3_999_596_544
    assert dec["flops"] == 2 * 8 * 32 * (ATT + FFN) + 2 * 8 * HEAD
    pre = w.matmul_work(c, 256)
    assert pre["bytes"] == dec["bytes"]           # dense: read once, always
    assert pre["flops"] == 2 * 256 * 32 * (ATT + FFN) + 2 * HEAD
    peaks = w.load_peaks("TPU v5 lite")
    t, bound = w.roofline_seconds(dec, peaks)
    assert bound == "memory" and t == pytest.approx(3_999_596_544 / 819e9)
    t, bound = w.roofline_seconds(pre, peaks)
    assert bound == "compute" and t == pytest.approx(pre["flops"] / 197e12)


def test_mixtral_12_layers_routes_top_2_of_8():
    c = config("mixtral-8x7b-12l")
    router = 8 * 4096
    one = w.matmul_work(c, 1)                     # one token: two experts
    assert one["bytes"] == (12 * (ATT + 2 * FFN + router) + HEAD) * 18 / 32
    assert one["flops"] == 2 * 12 * (ATT + 2 * FFN + router) + 2 * HEAD
    s = w.shapes(c)
    assert w.experts_touched(s, 1) == pytest.approx(2.0)
    assert w.experts_touched(s, 8) == pytest.approx(8 * (1 - 0.75 ** 8))
    full = w.matmul_work(c, 256)                  # 256 tokens: all eight
    assert full["bytes"] == pytest.approx(
        (12 * (ATT + 8 * FFN + router) + HEAD) * 18 / 32, rel=1e-9)
    # ... but each token still needs only its two experts' FLOPs
    assert full["flops"] == 2 * 256 * 12 * (ATT + 2 * FFN + router) + 2 * HEAD


def test_sizing_matches_the_issue_and_fits_the_chip():
    m = w.sizing(config("mistral-7b"))
    assert m["weights"] == 3_999_596_544 + 32000 * 4096 * 2     # 4.26 GB
    assert m["cache_per_token"] == 128 * 1024                   # 128 KiB
    assert m["slots"] == m["arena"] == 4 * 2**30                # 4.0 GiB each
    x = w.sizing(config("mixtral-8x7b-12l"))
    assert x["cache_per_token"] == 48 * 1024
    assert 10.0e9 < x["weights"] < 10.2e9
    for s in (m, x):
        total = s["weights"] + s["slots"] + s["arena"]
        assert 0.25 * 16.9e9 < total < 15.75 * 2**30


def test_an_unknown_device_is_an_error():
    assert w.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        w.load_peaks("cpu")
