"""The one traffic generator: a mix file and a cell file in, a schedule out.

A mix (`traffic/<mix>.json`) states distributions; a cell
(`cells/<cell>.json`) states the rate, the ramp and the limits. Lengths are
the QUANTILES of the stated distribution, so every run of a cell sends the
same multiset of prompt and output lengths (and, in an open loop, of
arrival gaps), and in the same ORDER, which the cell's `schedule_seed`
draws once; `--seed` chooses the bytes of every prompt and the sampling
seeds. Two seeds therefore send different text through the same timetable.
(Letting `--seed` permute the order moved the median time to first token of
33 requests by 15-18 % between seeds, and by under 1 % between two runs of
one seed: which long prompt meets which burst was changing the work.)
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import random
from statistics import NormalDist

HERE = os.path.dirname(os.path.abspath(__file__))
ALPHABET = "abcdefghijklmnopqrstuvwxyz      "  # 32 symbols, a fifth spaces


@dataclasses.dataclass
class Request:
    """One request of the schedule. `due` is seconds after the start of the
    ramp (open loop) or None (closed loop: due when the client is free)."""
    index: int
    phase: str            # "ramp" | "window"
    due: float | None
    client: int | None
    prompt_tokens: int    # as the server counts them: bytes + BOS
    max_tokens: int
    seed: int
    text_seed: int

    def prompt(self) -> str:
        """prompt_tokens - 1 printable bytes (the byte-fallback tokenizer
        gives one token a byte and adds BOS), drawn from text_seed."""
        rng = random.Random(self.text_seed)
        n = self.prompt_tokens - 1
        bits = rng.getrandbits(5 * n) if n else 0
        return "".join(ALPHABET[(bits >> (5 * i)) & 31] for i in range(n))


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """The n mid-point quantiles of `dist`, clipped to [min, max], as whole
    token counts — the same list for every seed."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if dist["dist"] == "uniform":
            x = lo + p * (hi - lo)
        elif dist["dist"] == "lognormal":
            x = dist["median"] * math.exp(
                dist["sigma"] * NormalDist().inv_cdf(p))
        else:
            raise ValueError(f"unknown distribution {dist['dist']!r}")
        out.append(int(min(max(round(x), lo), hi)))
    return out


def quantile_gaps(n: int, span: float) -> list[float]:
    """The n mid-point quantiles of an exponential distribution, scaled to
    sum to `span`: n arrivals fill the span exactly, in every run."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span / sum(raw)
    return [g * scale for g in raw]


def _phase(order: random.Random, rng: random.Random, mix: dict, phase: str,
           n: int, start: float, span: float | None,
           first_index: int) -> list[Request]:
    prompts = quantile_lengths(mix["prompt_tokens"], n)
    outputs = quantile_lengths(mix["output_tokens"], n)
    order.shuffle(prompts)
    order.shuffle(outputs)
    dues: list[float | None] = [None] * n
    if span is not None:
        gaps = quantile_gaps(n, span)
        order.shuffle(gaps)
        t = start
        for i, g in enumerate(gaps):   # the first is due at the start
            dues[i] = t
            t += g
    return [Request(first_index + i, phase, dues[i], None, prompts[i],
                    outputs[i], rng.randrange(1, 2**31 - 1),
                    rng.getrandbits(48)) for i in range(n)]


def build_schedule(mix: dict, cell: dict, seed: int,
                   seconds: float) -> list[Request]:
    """Open loop: ramp then window, each with its own fixed multisets
    (round(rate x length) requests whose gaps fill the phase exactly), due
    times in seconds from the start of the ramp. Closed loop: `pool`
    requests dealt round-robin to `clients`, each client working through
    its own list for as long as ramp and window last (phase and due are
    set when a request is sent)."""
    order = random.Random(int(cell["schedule_seed"]))
    rng = random.Random(int(seed))
    if mix["loop"] == "open":
        rate, ramp = float(cell["rate_rps"]), float(cell["ramp_s"])
        n_ramp = max(round(rate * ramp), 1)
        n_win = max(round(rate * seconds), 1)
        reqs = _phase(order, rng, mix, "ramp", n_ramp, 0.0, ramp, 0)
        reqs += _phase(order, rng, mix, "window", n_win, ramp, seconds,
                       n_ramp)
        return reqs
    if mix["loop"] == "closed":
        reqs = _phase(order, rng, mix, "pool", int(mix["pool"]), 0.0, None,
                      0)
        for i, r in enumerate(reqs):
            r.client = i % int(mix["clients"])
        return reqs
    raise ValueError(f"unknown loop {mix['loop']!r}")
