"""From a profiler trace to numbers: device busy and idle, kernel time per
execution of a step program, the operations that took most time, and the
idle gaps by what the host was doing.

The walk over `.xplane.pb` follows runtime/netstats.per_step_op_ms (device
planes, the "XLA Modules" line for executions, the "XLA Ops" line for
operations) and extends it to the union of op intervals, per-kernel time and
gaps. The arithmetic works on plain lists, so the tests feed it hand-made
events; only `reduce_dir` touches JAX, in a child with JAX_PLATFORMS=cpu.
"""

from __future__ import annotations

import bisect
import glob
import re

MIN_GAP_S = 1e-3        # gaps shorter than this are summed but not attributed
MIN_HOST_EVENT_S = 20e-6


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in merge(intervals))


def idle_gaps(intervals: list[tuple[float, float]],
              window: tuple[float, float]) -> list[tuple[float, float]]:
    """The (start, end) stretches of `window` that no interval covers."""
    gaps, at = [], window[0]
    for s, e in merge(intervals):
        s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            continue
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if window[1] > at:
        gaps.append((at, window[1]))
    return gaps


def stem(name: str) -> str:
    """`jit_slot_prefill_chunk_32(123)` -> `slot_prefill_chunk_32`;
    `%fusion.123 = ...` -> `fusion`."""
    name = name.split("(")[0].split(" = ")[0].lstrip("%").strip()
    if name.startswith("jit_"):
        name = name[4:]
    return re.sub(r"\.\d+$", "", name) or "op"


def kernel_of(name: str, kernels: list[str]) -> str | None:
    """The kernel an op event IS (an event's name is the whole HLO line,
    operands included, so only the part before ` = ` counts)."""
    own = stem(name)
    return own if own in kernels else None


def attribute_gaps(gaps: list[tuple[float, float]],
                   host_events: list[tuple[float, float, str]]) -> list:
    """[(label, seconds)] summed by label, longest first: each gap of at
    least MIN_GAP_S goes to the SHORTEST host event that covers at least
    half of it (the innermost thing the host was doing), or to
    `unattributed`; shorter gaps are summed under `gaps_under_1ms`."""
    ev = sorted(e for e in host_events if e[1] - e[0] >= MIN_HOST_EVENT_S)
    starts = [e[0] for e in ev]
    reach, m = [], float("-inf")
    for e in ev:            # running maximum of the ends, to stop the scan
        m = max(m, e[1])
        reach.append(m)
    totals: dict[str, float] = {}
    for gs, ge in gaps:
        dur = ge - gs
        if dur < MIN_GAP_S:
            totals["gaps_under_1ms"] = totals.get("gaps_under_1ms", 0.0) + dur
            continue
        best = None
        i = bisect.bisect_left(starts, ge) - 1
        while i >= 0 and reach[i] > gs:
            s, e, name = ev[i]
            if min(e, ge) - max(s, gs) >= 0.5 * dur and (
                    best is None or e - s < best[0]):
                best = (e - s, name)
            i -= 1
        label = stem(best[1])[:64] if best else "unattributed"
        totals[label] = totals.get(label, 0.0) + dur
    return sorted(totals.items(), key=lambda kv: -kv[1])


def reduce_events(devices: list[dict], host_events: list,
                  kernels: list[str]) -> dict:
    """devices: one {"modules": [(start, end, name)], "ops": [(start, end,
    name)]} a chip, times in seconds. Returns the dictionary that
    `run.py` hands to the trace readers; busy and window are averaged over
    the chips, executions and operations are the first chip's."""
    busy, window, gaps_all = [], [], []
    for dev in devices:
        spans = [(s, e) for s, e, *_ in dev["ops"]]
        edges = spans + [(s, e) for s, e, _ in dev["modules"]]
        if not edges:
            continue
        win = (min(s for s, _ in edges), max(e for _, e in edges))
        busy.append(busy_seconds(spans))
        window.append(win[1] - win[0])
        if not gaps_all:
            gaps_all = idle_gaps(spans, win)
    if not busy:
        return {}
    dev = devices[0]
    mods = sorted(dev["modules"])
    starts = [m[0] for m in mods]
    execs = [{"module": stem(m[2]), "start_s": m[0], "dur_s": m[1] - m[0],
              "kernel_s": {}} for m in mods]
    op_total: dict[str, float] = {}
    for s, e, name in dev["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < mods[i][1]
        k = kernel_of(name, kernels)
        label = (execs[i]["module"] if inside else "outside") + "/" + (
            k or stem(name))
        op_total[label] = op_total.get(label, 0.0) + (e - s)
        if inside and k:
            ks = execs[i]["kernel_s"]
            ks[k] = ks.get(k, 0.0) + (e - s)
    modules: dict[str, dict] = {}
    for x in execs:
        m = modules.setdefault(x["module"], {"count": 0, "device_s": 0.0})
        m["count"] += 1
        m["device_s"] += x["dur_s"]
    n = len(busy)
    return {"busy_s": sum(busy) / n, "window_s": sum(window) / n,
            "chips": n, "executions": execs, "modules": modules,
            "device_ops": sorted(op_total.items(), key=lambda kv: -kv[1]),
            "idle_gaps": attribute_gaps(gaps_all, host_events)}


def reduce_dir(trace_dir: str, kernels: list[str]) -> dict:
    """Read the newest `.xplane.pb` under trace_dir and reduce it. {} when
    the trace has no device plane (a CPU run): the readers then find
    nothing and the metrics are left out."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        return {}
    pd = ProfileData.from_file(files[-1])
    devices, host, inventory = [], [], {}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        inventory[plane.name] = {n: sum(1 for _ in ln.events)
                                 for n, ln in lines.items()}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            dev = {"modules": [], "ops": []}
            mods = lines.get("XLA Modules")
            for e in (mods.events if mods else ()):
                dev["modules"].append((e.start_ns / 1e9, e.end_ns / 1e9,
                                       e.name))
            for e in lines["XLA Ops"].events:
                dev["ops"].append((e.start_ns / 1e9, e.end_ns / 1e9, e.name))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.duration_ns >= MIN_HOST_EVENT_S * 1e9:
                        host.append((e.start_ns / 1e9, e.end_ns / 1e9,
                                     e.name))
    out = reduce_events(devices, host, kernels)
    out["inventory"] = inventory
    out["file"] = files[-1]
    return out
