"""The generator: the same work for every seed, the same schedule for the
same seed, exact offered rate."""

import pytest

import traffic

SEEDS = (0, 1, 17, 2**31 + 11, 3000000007)


def mix_and_cell(name):
    cells = {"chat-steady": "mistral-7b.chat-steady",
             "doc-batch": "mistral-7b.doc-batch"}
    return (traffic.load_json("traffic", name + ".json"),
            traffic.load_json("cells", cells[name] + ".json"))


@pytest.mark.parametrize("name", ["chat-steady", "doc-batch"])
def test_every_seed_draws_the_same_multisets(name):
    mix, cell = mix_and_cell(name)
    seen = set()
    for seed in SEEDS:
        by_phase = {}
        for r in traffic.build_schedule(
                mix, dict(cell, schedule_seed=seed % 1000), seed, 51):
            by_phase.setdefault(r.phase, []).append(r)
        seen.add(tuple((ph, tuple(sorted(r.prompt_tokens for r in rs)),
                        tuple(sorted(r.max_tokens for r in rs)))
                       for ph, rs in sorted(by_phase.items())))
    assert len(seen) == 1


def test_every_order_draws_its_gaps_from_the_same_multiset():
    mix, cell = mix_and_cell("chat-steady")
    n = round(cell["rate_rps"] * 51)
    allowed = traffic.quantile_gaps(n, 51.0)
    assert sum(allowed) == pytest.approx(51.0)
    for sched in SEEDS:
        w = [r for r in traffic.build_schedule(
                 mix, dict(cell, schedule_seed=sched), 1, 51)
             if r.phase == "window"]
        pool = sorted(allowed)
        for g in sorted(b.due - a.due for a, b in zip(w, w[1:])):
            k = min(range(len(pool)), key=lambda i: abs(pool[i] - g))
            assert pool.pop(k) == pytest.approx(g)   # each gap used once
        assert len(pool) == 1      # the gap after the last arrival


def test_same_seed_same_inputs_and_other_seed_other_bytes_only():
    mix, cell = mix_and_cell("chat-steady")
    a = traffic.build_schedule(mix, cell, 5, 51)
    b = traffic.build_schedule(mix, cell, 5, 51)
    c = traffic.build_schedule(mix, cell, 6, 51)
    assert a == b
    assert [r.prompt() for r in a[:3]] == [r.prompt() for r in b[:3]]
    # another seed: the same timetable, other bytes and sampling seeds
    assert [(r.due, r.prompt_tokens, r.max_tokens) for r in a] == \
        [(r.due, r.prompt_tokens, r.max_tokens) for r in c]
    assert all(x.prompt() != y.prompt() and x.seed != y.seed
               for x, y in zip(a, c))
    # another schedule_seed: the same multisets in another order
    d = traffic.build_schedule(mix, dict(cell, schedule_seed=7), 5, 51)
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in d]
    assert sorted(r.prompt_tokens for r in a) == \
        sorted(r.prompt_tokens for r in d)


def test_offered_rate_and_window_are_exact():
    mix, cell = mix_and_cell("chat-steady")
    rate, ramp = cell["rate_rps"], cell["ramp_s"]
    s = traffic.build_schedule(mix, cell, 9, 51)
    win = [r for r in s if r.phase == "window"]
    assert len(win) == round(rate * 51)
    assert win[0].due == ramp and all(ramp <= r.due < ramp + 51 for r in win)
    assert all(r.due < ramp for r in s if r.phase == "ramp")


def test_lengths_are_the_stated_quantiles_and_prompts_are_exact_bytes():
    mix, _ = mix_and_cell("chat-steady")
    lens = traffic.quantile_lengths(mix["prompt_tokens"], 31)
    assert lens[15] == 256 and min(lens) >= 64 and max(lens) <= 1024
    assert lens == sorted(lens)
    uni = traffic.quantile_lengths({"dist": "uniform", "min": 1024,
                                    "max": 3072}, 4)
    assert uni == [1280, 1792, 2304, 2816]
    r = traffic.Request(0, "window", 0.0, None, 300, 5, 1, 12345)
    text = r.prompt()
    assert len(text.encode()) == 299 and set(text) <= set(traffic.ALPHABET)


def test_closed_loop_deals_the_pool_round_robin():
    mix, cell = mix_and_cell("doc-batch")
    s = traffic.build_schedule(mix, cell, 3, 51)
    assert len(s) == mix["pool"]
    for c in range(mix["clients"]):
        assert len([r for r in s if r.client == c]) == \
            mix["pool"] // mix["clients"]
    assert all(1024 <= r.prompt_tokens <= 3072 and 16 <= r.max_tokens <= 48
               for r in s)
