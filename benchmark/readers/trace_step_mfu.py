"""The whole step program's share of the chip's peak FLOP/s: the FLOPs of
the Q40 matmuls the capture's MEAN execution needs (trace_roofline's
`capture_work`: its real tokens, and with experts the (token, expert) pairs
they were routed to, all from the capture's own counters) over the MEAN
device time of the traced executions of that program, against the bf16
peak of this device kind. It stands beside the kernels' rooflines and
bounds them: a change that takes a kernel off the step's path leaves that
kernel's roofline silent, and this share still says what the step got out
of the chip.

The FLOPs are the matmuls' alone (attention, norms and the sampler are left
out), so the share understates and cannot pass 100 % by its count. A decode
step of a few rows is memory-bound and reads a percent or two: what moves
it is the step's time, since its FLOPs follow the rows.

Nothing to read (None): no traced execution of the program, or nothing
from `capture_work`.
"""

from readers.trace_roofline import capture_work


def read(ctx: dict, program: str):
    module = ctx["config"]["executables"][program]
    times = [x["dur_s"] for x in (ctx["trace"] or {}).get("executions", ())
             if x["module"] == module and x["dur_s"] > 0]
    got = capture_work(ctx, program) if times else None
    if got is None:
        return None
    work, said = got
    mean = sum(times) / len(times)
    return {"value": 100.0 * work["flops"]
            / (ctx["peaks"]["bf16_flops_per_s"] * mean),
            "note": f"{len(times)} executions of {module}, {said}, "
                    f"{work['flops'] / 1e9:.1f} GFLOP over a mean "
                    f"{mean * 1e3:.3f} ms"}
