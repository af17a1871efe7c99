"""Arithmetic from request records to end-to-end metrics.

A record is what `client.py` keeps of one request: when it was due, when it
was sent, the host-clock instant of every streamed token, how it ended. A
request is timed from the instant it was DUE, so a stall that delays later
sends counts against the system and not for it.
"""

from __future__ import annotations


def percentile(values: list[float], p: float) -> float | None:
    """Linear-interpolated percentile (numpy's default rule), None if empty."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def ttfts_ms(records: list[dict], origin: str = "due") -> list[float]:
    """Time to the first streamed token of every record that has one, from
    the due instant (what a user waits) or the sent instant (what the
    server can be charged with)."""
    return [(r["tokens_at"][0] - r[origin]) * 1e3
            for r in records if r["tokens_at"]]


def pooled_gaps_ms(records: list[dict]) -> list[float]:
    """Every gap between consecutive streamed tokens, pooled over requests."""
    out = []
    for r in records:
        t = r["tokens_at"]
        out.extend((b - a) * 1e3 for a, b in zip(t, t[1:]))
    return out


def lateness_ms(records: list[dict]) -> dict:
    late = [(r["sent"] - r["due"]) * 1e3 for r in records]
    return {"median": percentile(late, 50), "worst": max(late, default=None)}


def tokens_per_s(records: list[dict]) -> float | None:
    """Prompt and output tokens of the completed requests over the time
    from the first due instant to the last completion."""
    done = [r for r in records if r["ok"]]
    if not done:
        return None
    span = max(r["ended"] for r in done) - min(r["due"] for r in records)
    tokens = sum(r["prompt_tokens"] + len(r["tokens_at"]) for r in done)
    return tokens / span if span > 0 else None


def end_to_end(records: list[dict]) -> dict:
    """Every end-to-end quantity the benchmark knows, over the window's
    records; `run.py` prints those the cell's BENCHMARK.json entry lists."""
    ok = [r for r in records if r["ok"]]
    gaps = pooled_gaps_ms(ok)
    return {"ttft_p50_ms": percentile(ttfts_ms(ok), 50),
            "itl_p50_ms": percentile(gaps, 50),
            "itl_p99_ms": percentile(gaps, 99),
            "tokens_per_s": tokens_per_s(records)}
