"""One run of one cell of BENCHMARK.json:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Serves the cell's configuration through the program's own entry point
(`python -m distributed_llama_tpu.apps.dllama api`), sends the cell's traffic
over HTTP with streaming on, and prints as the LAST stdout line one JSON
object: `correct`, `attempted`, `failed`, `metrics`, `device` (and
`breakdown` with --trace 1), and last `compared`: every number `correct`
rests on beside its limit, which are also the last lines on stderr. With
--trace 0 the metrics are the cell's end-to-end metrics, with --trace 1 its
per-layer metrics. Every earlier line is information. Without a TPU (or
with fewer chips than the cell asks for, or a device kind missing from
peaks.json) the run fails: non-zero exit, no result line. Nothing falls back
to a CPU.

This parent never imports JAX — a process that has touched JAX holds the
chip, and the server needs it. Everything that belongs to one configuration,
traffic mix, cell or per-layer metric is a data file found by its name in
BENCHMARK.json (see README.md); this file has no per-cell code.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is counted from here

import argparse
import dataclasses
import hashlib
import importlib
import json
import math
import os
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import metrics  # noqa: E402
import traffic  # noqa: E402
import workmodel  # noqa: E402
from client import Load  # noqa: E402
from server import (BenchFailure, Server, check, child_env, run_child,  # noqa: E402
                    say, tail)

CACHE = os.path.join(HERE, ".cache")
CAPTURE_LIMIT_S = 240.0   # for the server to export one capture (~60 s)
PROBE = {"prompt": "the quick brown fox jumps over the lazy dog and runs",
         "max_tokens": 16, "temperature": 0.0, "seed": 1}


@dataclasses.dataclass
class Plan:
    """What one run needs. `main()` builds the real one from BENCHMARK.json
    (chip children get JAX_PLATFORMS=tpu); the CPU rehearsal in tests/
    builds a tiny one. The command itself has no option that lets it pass
    without a chip."""
    workload: dict
    config: dict
    mix: dict
    cell: dict
    end_to_end: list      # the manifest's entries this cell reports
    per_layer: list
    seed: int
    seconds: float
    trace: bool
    chip_env: dict = dataclasses.field(
        default_factory=lambda: {"JAX_PLATFORMS": "tpu"})
    want_platform: str = "tpu"
    engine_flags: list = dataclasses.field(default_factory=list)

    @property
    def check_lengths(self) -> tuple[int, int]:
        """Prompt tokens and decode steps of the logits check: the
        configuration's `check` (so that its verdict can cross its window
        or its state), else 100 and 4."""
        c = self.config.get("check", {})
        return c.get("prompt_tokens", 100), c.get("decode_steps", 4)


def load_plan(workload: str, seed: int, seconds: float, trace: bool) -> Plan:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    check(workload in cells, f"no workload {workload!r} in BENCHMARK.json "
                             f"({sorted(cells)})")
    w = cells[workload]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    config["name"] = w["config"]

    def mine(entries):
        return [m for m in entries
                if "workloads" not in m or workload in m["workloads"]]

    return Plan(workload=w, config=config,
                mix=traffic.load_json("traffic", w["traffic"] + ".json"),
                cell=traffic.load_json("cells", workload + ".json"),
                end_to_end=mine(manifest["end_to_end"]),
                per_layer=mine(manifest["per_layer"]),
                seed=seed, seconds=seconds, trace=trace)


# -- set-up: files and the correctness verdict, once per checkout -----------


def package_hash(plan: Plan) -> str:
    """Hash of the program's source files, of the references, of the shapes
    (this configuration's own, wherever it is kept) and of the check's
    lengths: a changed program, mapping, count or length computes its
    verdict anew."""
    h = hashlib.sha256(json.dumps(
        [plan.check_lengths, plan.config.get("check")]).encode())
    for root in (os.path.join(REPO, "distributed_llama_tpu"),
                 os.path.join(HERE, "reference"),
                 os.path.join(HERE, "shapes")):
        for dirpath, dirnames, files in sorted(os.walk(root)):
            dirnames.sort()
            for name in sorted(files):
                if name.endswith(".py"):
                    path = os.path.join(dirpath, name)
                    h.update(os.path.relpath(path, REPO).encode())
                    with open(path, "rb") as f:
                        h.update(f.read())
    for rel in ("children.py", "workmodel.py", "weights.py",
                plan.config.get("shape")):
        if rel:
            with open(os.path.join(HERE, rel), "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:20]


def prepare(plan: Plan) -> tuple[str, str, dict]:
    """Model and tokenizer files of the configuration (written once per
    checkout from the configuration's weights_seed, so that the file and
    the compile cache serve every later run) and the logits verdict."""
    cfg = plan.config
    check(os.path.isdir(os.path.join(REPO, "distributed_llama_tpu")),
          "the program under test (distributed_llama_tpu/) is not in this "
          "checkout")
    d = os.path.join(CACHE, f"{cfg['name']}-{cfg['weights_seed']}")
    logs = os.path.join(CACHE, "run")
    shutil.rmtree(logs, ignore_errors=True)
    os.makedirs(logs)
    model, tok = os.path.join(d, "model.m"), os.path.join(d, "tok.t")
    vpath = os.path.join(d, f"verdict-{package_hash(plan)}.json")
    cold = not (os.path.exists(model) and os.path.exists(tok)
                and os.path.exists(vpath))
    if cold and plan.want_platform == "tpu":
        # before writing gigabytes: is there a chip at all?
        out = run_child("probe", {}, os.path.join(logs, "probe.log"),
                        child_env(**plan.chip_env), 300)
        check_device(plan, out["device"])
    os.makedirs(d, exist_ok=True)
    if not (os.path.exists(model) and os.path.exists(tok)):
        out = run_child("synth", {"config": cfg, "model": model,
                                  "tokenizer": tok},
                        os.path.join(logs, "synth.log"),
                        child_env(JAX_PLATFORMS="cpu"), 1200)
        say(f"set-up: wrote {out['bytes'] / 1e9:.2f} GB {cfg['name']} Q40 "
            f".m in {out['seconds']}s (once per checkout)")
    if os.path.exists(vpath):
        with open(vpath) as f:
            verdict = json.load(f)
        say(f"set-up: cached logits verdict {os.path.basename(vpath)}")
    else:
        n_prompt, n_decode = plan.check_lengths
        verdict = run_child(
            "check", {"config": cfg, "model": model, "tokenizer": tok,
                      "seed": cfg["weights_seed"] + 1,
                      "prompt_tokens": n_prompt, "decode_steps": n_decode,
                      "engine_flags": plan.engine_flags},
            os.path.join(logs, "check.log"), child_env(**plan.chip_env), 1500)
        with open(vpath + ".part", "w") as f:
            json.dump(verdict, f)
        os.replace(vpath + ".part", vpath)
    for r in verdict["rows"]:
        say(f"logits vs {cfg['reference']}: {r['step']} at {r['position']}: "
            f"rel_l2 {r['rel_l2']:.5f} argmax_agree {r['argmax_agree']}")
    say(f"logits verdict: worst rel_l2 {verdict['worst_rel_l2']:.5f}, limits "
        f"{verdict['limits']} -> {'ok' if verdict['ok'] else 'NOT ok'}")
    return model, tok, verdict


def check_device(plan: Plan, device: dict) -> dict:
    check(device["platform"] == plan.want_platform,
          f"runs on {device['platform']!r}, not {plan.want_platform!r}")
    check(device["count"] >= plan.workload["chips"],
          f"{device['count']} devices, the cell asks for "
          f"{plan.workload['chips']}")
    if plan.want_platform == "tpu":
        workmodel.load_peaks(device["kind"])   # KeyError: unknown device
    return device


# -- the served run -----------------------------------------------------------


def boot(plan: Plan, model: str, tok: str) -> tuple[Server, dict, dict]:
    logs = os.path.join(CACHE, "run")
    srv = Server(model, tok,
                 plan.config["server_flags"] + plan.engine_flags
                 + ["--profile-dir", os.path.join(logs, "profile")],
                 os.path.join(logs, "server.log"), child_env(**plan.chip_env))
    try:
        ready_s = srv.wait_ready()
        build = srv.get("/healthz")["build"]
        device = check_device(plan, {
            "platform": build["backend"], "kind": build["device_kind"],
            "count": build["device_count"]})
        check(device["count"] == plan.workload["chips"],
              f"the server sees {device['count']} devices, the cell is for "
              f"{plan.workload['chips']}")
        comp = srv.get("/stats")["compiles"]
        say(f"server ready after {ready_s:.1f}s: {comp['total']} executables "
            f"minted in {comp['total_ms'] / 1e3:.1f}s, persistent compile "
            f"cache {comp['persistent_cache_hits']} hits / "
            f"{comp['persistent_cache_misses']} misses")
        return srv, device, comp
    except BaseException:
        srv.close()
        raise


def kernels_listed(plan: Plan, comp: dict) -> bool:
    """Every serving executable lists the configuration's Pallas kernels (a
    prefill wider than the kernel's row limit drops, silently, to an XLA
    dequantising path — PR 22, item 5)."""
    good = True
    for key in plan.config["compile_keys"]:
        ks = (comp["by_key"].get(key) or {}).get("kernels")
        say(f"executable {key}: kernels {json.dumps(ks)}")
        if plan.want_platform != "tpu":
            continue          # no compiled kernels off the chip (rehearsal)
        if not ks or any(ks.get(k, 0) <= 0 for k in plan.config["kernels"]):
            good = False
    return good


def probe(srv: Server) -> str:
    out = srv.post("/v1/completions", PROBE, timeout=300)
    return out["choices"][0]["text"]


def snapshot(srv: Server) -> dict:
    s = srv.get("/stats")
    s["at"] = time.perf_counter()
    return s


def sleep_until(t: float) -> None:
    d = t - time.perf_counter()
    if d > 0:
        time.sleep(d)


def drive(plan: Plan, srv: Server) -> dict:
    """Ramp, window, drain. Returns the records and the /stats snapshots
    (at the window's start and end, and around the profiler capture)."""
    schedule = traffic.build_schedule(plan.mix, plan.cell, plan.seed,
                                      plan.seconds)
    load = Load(srv.port, plan.mix, plan.cell, schedule, plan.seconds)
    stats: dict = {}
    profile: dict = {}
    ramp = float(plan.cell["ramp_s"])

    def observe(t0: float) -> None:
        sleep_until(t0 + ramp)
        stats["window_start"] = snapshot(srv)
        sleep_until(t0 + ramp + plan.seconds)
        stats["window_end"] = snapshot(srv)

    def capture(t0: float) -> None:
        # on a thread of its own: the server answers only once the trace is
        # exported (60 s for 4 s of doc-batch, more on a slow host), and the
        # window's last snapshot must not wait for that
        sleep_until(t0 + ramp + min(float(plan.cell["trace_after_s"]),
                                    plan.seconds / 3))
        ms = min(float(plan.cell["trace_ms"]), plan.seconds * 500)
        stats["trace_start"] = snapshot(srv)
        profile.update(srv.post(f"/admin/profile?ms={ms:.0f}",
                                timeout=ms / 1e3 + CAPTURE_LIMIT_S))
        stats["trace_end"] = snapshot(srv)
        say(f"capture of {ms:.0f} ms: the stop and export took "
            f"{profile.get('stop_ms', float('nan')) / 1e3:.1f}s in the "
            "server's process")

    errors: list[str] = []

    def guarded(fn, t0: float) -> None:
        try:
            fn(t0)
        except Exception as e:      # named in the failure, not lost
            errors.append(f"{fn.__name__}: {type(e).__name__}: {e}")

    t0 = load.start()
    watchers = [threading.Thread(target=guarded, args=(fn, t0), daemon=True)
                for fn in ((observe, capture) if plan.trace else (observe,))]
    for w in watchers:
        w.start()
    records = load.finish()
    end = time.perf_counter() + CAPTURE_LIMIT_S + 60
    for w in watchers:
        w.join(timeout=max(end - time.perf_counter(), 0.0))
    check(not errors and not any(w.is_alive() for w in watchers),
          "the /stats observer or the capture did not finish: "
          + ("; ".join(errors) or "still running"))
    return {"t0": t0, "window_at": t0 + ramp, "records": records,
            "attempted": load.sent_log.count("window"), "stats": stats,
            "profile": profile, "schedule": schedule}


def metric_specs(plan: Plan) -> dict:
    return {m["name"]: traffic.load_json("layer_metrics", m["name"] + ".json")
            for m in plan.per_layer}


def layer_metrics(plan: Plan, ctx: dict) -> dict:
    out = {}
    specs = metric_specs(plan)
    for m in plan.per_layer:
        spec = specs[m["name"]]
        reader = importlib.import_module("readers." + spec["reader"])
        got = reader.read(ctx, **spec.get("args", {}))
        if isinstance(got, dict):
            say(f"{m['name']}: {got['note']}")
            got = got["value"]
        if got is not None:       # a reader that finds nothing: left out
            out[m["name"]] = {"value": got, "unit": m["unit"]}
    return out


def run(plan: Plan) -> dict:
    cfg = plan.config
    size = workmodel.for_config(cfg).sizing(cfg)
    say(f"{plan.workload['name']}: seed {plan.seed}, {plan.seconds:g}s, "
        f"trace {int(plan.trace)}; sizing from shapes: weights "
        f"{size['weights'] / 1e9:.2f} GB + slots {size['slots'] / 1e9:.2f} GB"
        f" + arena {size['arena'] / 1e9:.2f} GB")
    model, tok, verdict = prepare(plan)
    srv, device, comp0 = boot(plan, model, tok)
    try:
        kernels_ok = kernels_listed(plan, comp0)
        text_before = probe(srv)
        d = drive(plan, srv)
        text_after = probe(srv)
        final = srv.get("/stats")
        srv.stop()
    finally:
        srv.close()

    recs = d["records"]
    window = [r for r in recs if r["phase"] == "window"]
    ok = [r for r in window if r["ok"]]
    failed = d["attempted"] - len(ok)
    for r in recs:
        if not r["ok"]:
            say(f"request {r['index']} ({r['phase']}) failed: status "
                f"{r['status']} {r['error']}")
    late = metrics.lateness_ms(window)
    stops = sum(r["finish"] == "stop" for r in recs)
    say(f"requests: {len(recs)} ended ({len(window)} of the window's "
        f"{d['attempted']}), {failed} failed, {stops} cut short by an "
        f"end-of-sequence token; generator lateness median "
        f"{late['median']:.2f} ms worst {late['worst']:.2f} ms")
    comp = final["compiles"]
    hbm = final.get("hbm") or {}
    say("hbm " + json.dumps({k: hbm.get(k) for k in (
        "weights_bytes", "vocab_bytes", "kv_slot_bytes", "prefix_arena_bytes",
        "device_bytes_in_use", "device_bytes_limit",
        "per_device_bytes_in_use")}))
    say(f"compiles after warm-up: {comp['after_warmup']}; total "
        f"{comp['total']}; greedy probe equal before and after: "
        f"{text_before == text_after}")
    in_use = [max((s.get("hbm") or {}).get("per_device_bytes_in_use")
                  or [(s.get("hbm") or {}).get("device_bytes_in_use") or 0])
              for s in list(d["stats"].values()) + [final]]
    device["memory_peak_bytes"] = max(in_use)

    ill_formed = [r for r in recs if (r["error"] or "").startswith("ill-")]
    # every number `correct` rests on, beside its limit ("least": at least)
    compared = {
        **{f"logits_{k}_rel_l2": {
            "value": verdict[f"{k}_rel_l2"]
            if math.isfinite(verdict[f"{k}_rel_l2"]) else None, "limit": limit}
           for k, limit in verdict["limits"].items()},
        "logits_rows_not_finite": {
            "value": sum(not r["finite"] for r in verdict["rows"]),
            "limit": 0},
        "executables_missing_a_kernel": {"value": int(not kernels_ok),
                                         "limit": 0},
        "compiles_after_warmup": {"value": comp["after_warmup"], "limit": 0},
        "compiles_since_boot": {"value": comp["total"] - comp0["total"],
                                "limit": 0},
        "ill_formed_answers": {"value": len(ill_formed), "limit": 0},
        "greedy_probe_changed": {"value": int(text_before != text_after),
                                 "limit": 0},
        "window_answered": {"value": len(ok), "least": 1}}
    correct = bool(verdict["ok"] and all(
        c["value"] <= c["limit"] if "limit" in c else c["value"] >= c["least"]
        for c in compared.values()))
    setup_s = d["window_at"] - T_PROCESS
    result = {"correct": correct, "attempted": d["attempted"],
              "failed": failed, "metrics": {}, "device": device}
    if not plan.trace:
        e2e = metrics.end_to_end(window)
        e2e["setup_s"] = setup_s
        say("end to end (every quantity; the result line holds the cell's "
            "metrics): " + json.dumps(e2e))
        for m in plan.end_to_end:
            check(e2e.get(m["name"]) is not None,
                  f"no value for end-to-end metric {m['name']}")
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
        result["compared"] = compared
        return result

    kernels = set(cfg["kernels"])      # the names a trace op may carry
    for spec in metric_specs(plan).values():
        kernels.update(spec.get("args", {}).get("kernels", ()))
    reduced = run_child(
        "reduce", {"dir": d["profile"].get("dir", ""),
                   "kernels": sorted(kernels)},
        os.path.join(CACHE, "run", "reduce.log"),
        child_env(JAX_PLATFORMS="cpu"), 600)
    say("trace planes: " + json.dumps(reduced.get("inventory", {}))[:2000])
    ctx = {"config": cfg, "seconds": plan.seconds, "stats": d["stats"],
           "trace": reduced,
           "peaks": (workmodel.load_peaks(device["kind"])
                     if plan.want_platform == "tpu" else None),
           "client": {"window_ok": ok,
                      "all_ok": [r for r in recs if r["ok"]]}}
    result["metrics"] = layer_metrics(plan, ctx)
    if reduced.get("window_s"):
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        for name, x in reduced["modules"].items():
            say(f"traced program {name}: {x['count']} executions, "
                f"{x['device_s'] * 1e3 / x['count']:.3f} ms each on the "
                "device")
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced["device_ops"][:10]],
            "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"][:10]]}
    else:
        check(plan.want_platform != "tpu",
              "the trace holds no device plane")
    result["compared"] = compared
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(load_plan(args.workload, args.seed, args.seconds,
                               bool(args.trace)))
    except (BenchFailure, KeyError, OSError) as e:
        msg = (f"FAILED after {time.perf_counter() - T_PROCESS:.0f}s: "
               f"{type(e).__name__}: {e}")
        say(msg)
        # a checker keeps the end of stderr of a run that failed: the cause
        # and the end of the server's log go there too
        print(msg[-1500:] + "\nserver.log ends:\n"
              + tail(os.path.join(CACHE, "run", "server.log"), 1500),
              file=sys.stderr, flush=True)
        return 1
    assert "jax" not in sys.modules, "the parent must never import jax"
    for name, c in result["compared"].items():
        print(f"compared {name}: {json.dumps(c)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
