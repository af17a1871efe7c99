"""Find a cell's knee, once, when the cell is defined:

    python benchmark/sweep.py --workload <name> --rates 0.4,0.6,0.8 --seconds 40

One server session, the cell's traffic mix at each rate in turn (a short
ramp, the window, then the drain). The knee is the highest swept rate, with
every lower rate, at which no backlog forms: every request answered, no more
requests in flight than the server has slots (at the middle and at the end of
the window), and a median time to first token under twice that of the lowest
rate. (Comparing the requests in flight at the end with those at the middle,
two instants of a handful of requests, proved too noisy to rank rates.) The
cell then runs at 0.8 x the knee, written into `cells/<name>.json` by hand
with the table in PERF.md. Not part of a run: `run.py` offers load at the
cell's fixed rate and never searches for one.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading

import run
from client import Load
from metrics import end_to_end, lateness_ms
from server import BenchFailure, say
import traffic


def one_rate(plan: run.Plan, srv, rate: float, seconds: float,
             ramp: float) -> dict:
    cell = dict(plan.cell, rate_rps=rate, ramp_s=ramp)
    schedule = traffic.build_schedule(plan.mix, cell, plan.seed, seconds)
    load = Load(srv.port, plan.mix, cell, schedule, seconds)
    reading = {}

    def watch(t0: float) -> None:
        for name, at in (("mid", ramp + seconds / 2), ("end", ramp + seconds)):
            samples = []
            for k in range(5):      # five readings over the last 2 s
                run.sleep_until(t0 + at - 2.0 + 0.5 * k)
                samples.append(load.in_flight())
            reading[name] = sum(samples) / len(samples)

    a = run.snapshot(srv)
    t0 = load.start()
    w = threading.Thread(target=watch, args=(t0,), daemon=True)
    w.start()
    w.join()
    b = run.snapshot(srv)
    records = load.finish()
    window = [r for r in records if r["phase"] == "window"]
    ok = [r for r in window if r["ok"]]
    row = {"rate_rps": rate, "sent": len(window), "ok": len(ok),
           "in_flight_mid": reading["mid"], "in_flight_end": reading["end"],
           "drain_s": max(r["ended"] for r in records) - (t0 + ramp + seconds),
           "steps": b["steps"] - a["steps"],
           "tokens_out": b["tokens_out"] - a["tokens_out"],
           "lateness_ms": lateness_ms(window), **end_to_end(window)}
    say("sweep " + json.dumps(row))
    return row


def knee(rows: list[dict], slots: int) -> float | None:
    best = None
    for r in sorted(rows, key=lambda r: r["rate_rps"]):
        if not (r["ok"] == r["sent"]
                and max(r["in_flight_mid"], r["in_flight_end"]) <= slots
                and r["ttft_p50_ms"] < 2 * rows[0]["ttft_p50_ms"]):
            break
        best = r["rate_rps"]
    return best


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--ramp", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    try:
        plan = run.load_plan(args.workload, args.seed, args.seconds, False)
        model, tok, _ = run.prepare(plan)
        srv, device, _ = run.boot(plan, model, tok)
        try:
            rows = [one_rate(plan, srv, r, args.seconds, args.ramp)
                    for r in sorted(map(float, args.rates.split(",")))]
            srv.stop()
        finally:
            srv.close()
    except (BenchFailure, KeyError, OSError) as e:
        say(f"FAILED: {type(e).__name__}: {e}")
        return 1
    out = {"workload": args.workload, "device": device, "rows": rows,
           "knee_rps": knee(rows, plan.config["server"]["serve_batch"])}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
