"""What the HOST does in every iteration of a served run, by scheduler span.

A server started with `--trace --trace-dir <dir> --trace-sample 0` writes one
`step` record an iteration (runtime/trace.py: its wall ms, its decoding and
prefilling rows and the ms of each span: sched.admit, sched.dispatch.prefill,
sched.dispatch.decode, sched.wait, sched.sample_emit). This prints, a
directory, the medians and means of the iterations that ran BOTH a chunk
program and a decode step between `--skip-s` and `--skip-s + --window-s`
seconds after the first of them:

    python tools/step_timeline.py [--decode-only] <dir> [<dir> ...]

`--decode-only` takes the iterations that ran a decode step and NO chunk
program instead (94 % of `granite-4.0-h-small-ep2.decode-batch`'s: what its
`itl_p50_ms` is made of, PERF.md section 5, PR 53).

`host` is an iteration's wall less its `sched.wait`; `host_by_dec` its median
by the number of decoding rows. How PR 52 compared eight runs of one cell on
the chip (PERF.md section 6): a slow run's spans against a fast one's.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics


def steps_of(directory: str, decode_only: bool = False) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(directory, "trace-*.jsonl"))):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:      # a line cut by a rotation
                    continue
                if (rec.get("kind") == "step" and rec.get("dec")
                        and bool(rec.get("pre")) != decode_only):
                    out.append(rec)
    return out


def summary(steps: list[dict], skip_s: float, window_s: float) -> dict:
    if not steps:
        return {"n": 0}
    t0 = steps[0]["ts"]
    w = [r for r in steps if t0 + skip_s <= r["ts"] <= t0 + skip_s + window_s]
    if not w:
        return {"n": 0}

    def both(xs):
        return [round(statistics.median(xs), 3), round(sum(xs) / len(xs), 3)]

    row = {"n": len(w), "ms": both([r["ms"] for r in w]),
           "dec_mean": round(sum(r["dec"] for r in w) / len(w), 3),
           "pre_mean": round(sum(r["pre"] for r in w) / len(w), 3)}
    for name in sorted({k for r in w for k in r.get("phases") or {}}):
        row[name.replace("sched.", "")] = both(
            [(r.get("phases") or {}).get(name, 0.0) for r in w])
    host = [r["ms"] - (r.get("phases") or {}).get("sched.wait", 0.0)
            for r in w]
    row["host"] = both(host)
    by: dict[int, list[float]] = {}
    for r, h in zip(w, host):
        by.setdefault(r["dec"], []).append(h)
    row["host_by_dec"] = {k: round(statistics.median(v), 3)
                          for k, v in sorted(by.items()) if len(v) >= 20}
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("dirs", nargs="+")
    ap.add_argument("--skip-s", type=float, default=20.0)
    ap.add_argument("--window-s", type=float, default=46.0)
    ap.add_argument("--decode-only", action="store_true")
    args = ap.parse_args()
    for d in args.dirs:
        print(json.dumps({"run": os.path.basename(os.path.normpath(d)),
                          **summary(steps_of(d, args.decode_only),
                                    args.skip_s, args.window_s)}))


if __name__ == "__main__":
    main()
