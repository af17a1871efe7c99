"""What the server's own report of its capture says (`/stats`
`capture.report`, made by the program after the export: runtime/profiler
.capture_report; read from the snapshot taken once `/admin/profile` has
answered, `trace_end`). The report gives, for each step program, mean SELF
milliseconds an execution by `jax.named_scope` path, a Pallas kernel apart
from everything XLA made, and the capture's idle seconds by the scheduler's
span open at that instant.

`what` picks the number:

  outside_kernels     `program`'s `xla` self ms an execution, all scopes:
                      what the step costs outside its kernels. The note is
                      the program's scope table.
  unscoped_share      self time under no scope over all device time of the
                      configuration's step programs, in percent: what the
                      vocabulary of scopes does not reach.
  idle_host_running   idle seconds overlapped by a span of the program
                      other than `sched.wait` and `sched.idle_wait`, over
                      the captured window, in percent: the device waits
                      while the host runs. The note is idle seconds by span.

The programs' names are the configuration's own (`executables`). None
(nothing to read) where the server made no report: a program older than
the report, or a capture without a device plane (a CPU run).
"""

WAITING = ("sched.wait", "sched.idle_wait", "no_span")


def _report(ctx: dict):
    capture = (ctx["stats"].get("trace_end") or {}).get("capture") or {}
    report = capture.get("report") or {}
    return report if report.get("programs") else None


def _ms(by: dict, kinds: tuple = ("kernel", "xla")) -> float:
    """A scope's ms an execution, over its kernels, its other ops or both."""
    return sum(sum(by[kind].values()) for kind in kinds)


def _table(name: str, prog: dict) -> str:
    rows = sorted(prog["scopes"].items(), key=lambda kv: -_ms(kv[1]))
    return (f"{prog['executions']} executions of {name}, "
            f"{prog['device_ms']:.3f} ms each, busy {prog['busy_ms']:.3f}; "
            "scope kernel/xla ms: " + ", ".join(
                f"{scope} {_ms(by, ('kernel',)):.3f}/{_ms(by, ('xla',)):.3f}"
                for scope, by in rows))


def read(ctx: dict, what: str, program: str | None = None):
    report = _report(ctx)
    if report is None:
        return None
    if what == "outside_kernels":
        name = ctx["config"]["executables"][program]
        prog = report["programs"].get(name)
        if prog is None:
            return None
        return {"value": sum(_ms(by, ("xla",))
                             for by in prog["scopes"].values()),
                "note": _table(name, prog)}
    if what == "unscoped_share":
        mine = [report["programs"][n]
                for n in ctx["config"]["executables"].values()
                if n in report["programs"]]
        busy = sum(p["busy_ms"] * p["executions"] for p in mine)
        if busy <= 0:
            return None
        loose = sum(_ms(p["scopes"]["unscoped"]) * p["executions"]
                    for p in mine if "unscoped" in p["scopes"])
        return {"value": 100.0 * loose / busy,
                "note": f"{loose:.1f} of {busy:.1f} device ms of "
                        + ", ".join(ctx["config"]["executables"].values())
                        + f"; the server made the report in "
                          f"{report.get('report_ms', float('nan')) / 1e3:.1f}"
                          " s"}
    if what == "idle_host_running":
        if not report.get("window_s"):
            return None
        idle = report.get("idle", {})
        running = sum(s for span, s in idle.items() if span not in WAITING)
        return {"value": 100.0 * running / report["window_s"],
                "note": f"idle {report['idle_s']:.3f} s of "
                        f"{report['window_s']:.3f}: " + ", ".join(
                            f"{span} {s:.3f}" for span, s in idle.items())}
    raise KeyError(what)
