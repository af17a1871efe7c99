"""What a capture says of itself (runtime/profiler: the program's one
xplane walker), on hand-made events: self time, scope paths, idle seconds
by overlap, the wire reader of the plane's op names, the report's child,
and `netstats.per_step_op_ms` through the shared walker. Times in seconds
unless a name says ms."""

import json
import sys

import pytest

from distributed_llama_tpu.runtime import profiler as P

PALLAS = ', custom_call_target="tpu_custom_call"'
DECODE = "jit_slot_decode_step(11)"
JIT = "jit(slot_decode_step)/"


def op(start, end, name, op_name=None):
    return (start * 1e-3, end * 1e-3, name, op_name and JIT + op_name)


def ms(x):
    return round(x * 1e3, 6)


# -- self time ---------------------------------------------------------------


@pytest.mark.limit_s(20)
@pytest.mark.parametrize("events,want", [
    # a `while` over two kernels and a gap
    ([(0, 10), (1, 3), (5, 8)], {0: 5, 1: 2, 2: 3}),
    # an async pair around other ops, and an op after it
    ([(0, 6), (2, 3), (3, 4), (8, 9)], {0: 4, 1: 1, 2: 1, 3: 1}),
    # three levels
    ([(0, 10), (2, 8), (4, 5)], {0: 4, 1: 5, 2: 1}),
    # an op that outlives the one it started in: each instant has one owner
    ([(0, 4), (2, 6)], {0: 2, 1: 4}),
    # nothing, and an event of no length
    ([], {}), ([(1, 1)], {}),
], ids=["while", "async-pair", "nested", "overlap", "empty", "zero"])
def test_self_time_is_the_duration_less_what_an_event_contains(events, want):
    got: dict = {}
    for a, b, i in P.innermost(events):
        got[i] = got.get(i, 0) + b - a
    assert got == want
    # and all together the union of the events
    union, at = 0, float("-inf")
    for s, e in sorted(events):
        union += max(e - max(s, at), 0)
        at = max(at, e)
    assert sum(got.values()) == union


@pytest.mark.limit_s(20)
def test_a_programs_scopes_sum_to_its_busy_time_and_a_loop_is_not_charged_its_body():
    # two executions of 10 ms; in each a `while` of 6 ms that names no line
    # (the compiler clones a loop) over two kernel calls of 2 ms, a copy
    # under `ffn/act_q80` and an unnamed async pair
    ops = []
    for t in (0, 20):
        ops += [
            op(t + 0, t + 1, "%copy.1 = f32[8]{0} copy(%p)", "ffn/act_q80/mul"),
            op(t + 1, t + 7, "%while.3 = (s32[]) while(%t), body=%b"),
            op(t + 2, t + 4, "%q40_expert_matmul.5 = bf16[8] custom-call(%x)"
               + PALLAS, "moe_routed/while/body/jit(q40_expert_matmul)/x"),
            op(t + 4, t + 4.5, "%fusion.9 = bf16[8] fusion(%y), kind=kLoop",
               "moe_routed/while/body/mul"),
            op(t + 4.5, t + 6.5, "%q40_expert_matmul.6 = bf16[8] custom-call("
               "%x)" + PALLAS, "moe_routed/while/body/jit(q40_expert_matmul)/x"),
            op(t + 8, t + 9, "%copy-start.2 = (f32[8]) copy-start(%w)")]
    got = P.program_scopes([(0, 0.010, DECODE), (0.020, 0.030, DECODE)], ops)
    prog = got["slot_decode_step"]
    assert prog["executions"] == 2 and prog["device_ms"] == 10.0
    assert prog["scopes"] == {
        "ffn/act_q80": {"kernel": {}, "xla": {"copy": 1.0}},
        # the loop's own 1.5 ms beside its kernels' 4: once each
        "moe_routed": {"kernel": {"q40_expert_matmul": 4.0},
                       "xla": {"while": 1.5, "fusion": 0.5}},
        "unscoped": {"kernel": {}, "xla": {"copy-start": 1.0}}}
    total = sum(v for by in prog["scopes"].values() for kind in by.values()
                for v in kind.values())
    assert total == prog["busy_ms"] == 8.0


@pytest.mark.limit_s(20)
@pytest.mark.parametrize("op_name,want", [
    (JIT + "ffn/act_q80/jit(quantize_q80_jax)/reduce_max", "ffn/act_q80"),
    (JIT + "moe_shared/ffn/jit(q40_matmul)/q40_matmul/pallas_call",
     "moe_shared/ffn"),
    (JIT + "attn_core/jit(flash_attention)/flash_attention/pallas_call",
     "attn_core"),
    (JIT + "layer_3/while/body/add", "unscoped"),      # not of the vocabulary
    (JIT + "add", "unscoped"), (None, "unscoped"), ("", "unscoped"),
    # an op the compiler merged from two carries both names
    (JIT + "gdn_rule/transpose;" + JIT + "gdn_rule/reshape", "gdn_rule"),
    # the inliner wrote the callee's whole path behind the call's
    (JIT + "moe_routed/jit(searchsorted)/" + JIT
     + "moe_routed/jit(searchsorted)/vmap()/while/body/select_n",
     "moe_routed"),
])
def test_a_scope_path_is_the_op_name_cut_to_the_vocabulary(op_name, want):
    assert P.scope_path(op_name) == want


# -- idle seconds by overlap ---------------------------------------------------


PHASES = [(0, 10, "sched.step"), (0, 4, "sched.wait"),
          (4, 7, "sched.sample_emit"), (7, 10, "sched.publish")]


@pytest.mark.limit_s(20)
@pytest.mark.parametrize("ops,window,spans,want", [
    # ONE gap through three consecutive phases of an iteration: each child
    # its seconds, the parent none (whole-gap attribution named `sched.step`)
    ([(0, 2), (9, 10)], (0, 10), PHASES,
     {"sched.wait": 2, "sched.sample_emit": 3, "sched.publish": 2}),
    # the parent owns only what lies between its children
    ([(0, 1), (9, 10)], (0, 10),
     [(0, 10, "sched.step"), (0, 4, "sched.wait"), (5, 10, "sched.publish")],
     {"sched.wait": 3, "sched.step": 1, "sched.publish": 4}),
    # no span open: no_span; sched.idle_wait stays its own
    ([(4, 5)], (0, 12), [(6, 9, "sched.idle_wait")],
     {"no_span": 4 + 1 + 3, "sched.idle_wait": 3}),
    # a device that never idles, and one with no spans at all
    ([(0, 10)], (0, 10), PHASES, {}),
    ([(2, 3)], (0, 5), [], {"no_span": 4}),
], ids=["three-phases", "parent-between", "no-span", "busy", "no-spans"])
def test_idle_time_is_split_by_overlap_over_the_innermost_span(
        ops, window, spans, want):
    got = P.idle_by_span([(s, e, "op", None) for s, e in ops], window, spans)
    assert got == want
    busy = sum(e - s for s, e in ops)
    assert sum(got.values()) == window[1] - window[0] - busy


@pytest.mark.limit_s(20)
def test_the_report_of_a_plane_holds_programs_and_idle_and_an_empty_one_nothing():
    assert P.report_events([], [], []) == {}
    got = P.report_events(
        [(0.0, 0.004, DECODE)],
        [op(0, 1, "%fusion.1 = f32[8] fusion(%p), kind=kLoop", "head/mul"),
         op(3, 4, "%q40_matmul.2 = bf16[8] custom-call(%x)" + PALLAS,
            "head/jit(q40_matmul)/q40_matmul/pallas_call")],
        [(0.0, 0.004, "sched.step"), (0.0005, 0.0025, "sched.sample_emit")])
    assert (got["window_s"], got["busy_s"], got["idle_s"]) == (
        0.004, 0.002, 0.002)
    assert got["idle"] == {"sched.sample_emit": 0.0015, "sched.step": 0.0005}
    assert got["programs"]["slot_decode_step"]["scopes"] == {
        "head": {"kernel": {"q40_matmul": 1.0}, "xla": {"fusion": 1.0}}}
    json.dumps(got)     # what /stats and the reply carry


# -- the file ----------------------------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _instruction(uid, name, opcode, op_name="", operands=(), packed=True):
    """An HloInstructionProto: .name = 1, .opcode = 2, .metadata = 7
    (.op_name = 2), .id = 35, .operand_ids = 36 (packed or one a field)."""
    body = _field(1, name) + _field(2, opcode) + _field(35, uid)
    if op_name:
        body += _field(7, _field(1, "an op_type") + _field(2, op_name))
    if packed:
        body += _field(36, b"".join(_varint(o) for o in operands))
    else:
        body += b"".join(_field(36, o) for o in operands)
    return _field(2, body + _field(3, b"a shape the reader skips"))


def _metadata_plane(programs: dict) -> bytes:
    """The `/host:metadata` XPlane: .name = 2, .event_metadata = 4, one
    entry a program whose one stat holds the HloProto as bytes (6)."""
    body = _field(1, 3) + _field(2, "/host:metadata")
    for pid, computations in programs.items():
        module = _field(1, "jit_f") + b"".join(
            _field(3, _field(1, cname) + b"".join(rows)
                   + (_field(7, 1) if cname.startswith("fused") else b""))
            for cname, rows in computations.items())
        meta = _field(1, pid) + _field(2, f"jit_f({pid})") + _field(
            5, _field(1, 1) + _field(6, _field(1, module)))
        body += _field(4, _field(1, pid) + _field(2, meta))
    return body


@pytest.mark.limit_s(20)
def test_an_ops_name_is_its_own_or_that_of_the_first_line_that_reads_it(
        tmp_path):
    """The compiled module the capture carries, off the wire format: the
    compiler's own ops (a relayout copy, an async pair) go to the line that
    reads what they make; a loop without a name keeps none; a fusion's
    inside is no op."""
    ffn = JIT + "ffn/jit(q40_matmul)/q40_matmul/pallas_call"
    core = JIT + "attn_core/mul"
    big = 2 ** 63 + 5                   # a program id past a signed 64 bits
    main = [
        _instruction(1, "p0", "parameter"),
        _instruction(2, "copy.1", "copy", operands=[1]),
        _instruction(3, "bitcast.2", "bitcast", operands=[2], packed=False),
        _instruction(4, "q40_matmul.3", "custom-call", ffn, [3, 1]),
        _instruction(5, "copy-start.4", "copy-start", operands=[1]),
        _instruction(6, "copy-done.5", "copy-done", operands=[5]),
        _instruction(7, "fusion.6", "fusion", core, [6, 4], packed=False),
        _instruction(8, "while.7", "while", operands=[7]),
        _instruction(9, "add.8", "add", JIT + "add", [8]),
        _instruction(10, "copy.9", "copy", operands=[9]),        # read by none
        _instruction(11, "layout.10", "copy", "cache.k[1]", [1])]  # no line
    fused = [_instruction(1, "mul.1", "multiply", JIT + "head/mul")]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        _field(1, _field(2, "/device:TPU:0") + _field(3, b"\x22\x03abc" * 9))
        + _field(1, _metadata_plane({big: {"main.1": main,
                                           "fused_computation": fused},
                                     11: {"main.2": main[:4]}}))
        + _field(4, "hostname"))
    assert P.device_planes(str(path)) == ["/device:TPU:0"]
    want = {"p0": ffn, "copy.1": ffn, "bitcast.2": ffn, "q40_matmul.3": ffn,
            "copy-start.4": core, "copy-done.5": core, "fusion.6": core,
            "add.8": JIT + "add"}
    assert P.hlo_op_names(str(path)) == {
        big: want, 11: {k: ffn for k in ("p0", "copy.1", "bitcast.2",
                                         "q40_matmul.3")}}
    assert P.scope_path(want["copy-done.5"]) == "attn_core"


@pytest.mark.limit_s(60)
def test_an_empty_trace_gives_an_error_and_no_numbers(tmp_path):
    assert P.capture_report(str(tmp_path)) == {
        "error": "no .xplane.pb under the directory"}
    assert P.walk_trace(str(tmp_path)) == {}
    report, took_ms = P.make_report(str(tmp_path))
    assert report == {"error": "no device plane in the capture",
                      "report_ms": round(took_ms, 3)}
    assert took_ms < 5e3                # no child for a capture like that
    assert json.loads((tmp_path / "report.json").read_text()) == report


@pytest.mark.limit_s(120)
@pytest.mark.parametrize("module,limit_s,error", [
    ("distributed_llama_tpu.no_such_module", 60.0, "exit code 1"),
    (P.REPORT_MODULE, 0.2, "no report within 0.2 s"),  # its imports take 2
], ids=["fails", "out-of-time"])
def test_a_report_that_fails_leaves_its_error_and_the_captures_reply_intact(
        tmp_path, monkeypatch, module, limit_s, error):
    """The capture's reply and /stats `capture.report` say so; nothing
    raises, and the capture counts."""
    monkeypatch.setattr(P, "REPORT_MODULE", module)
    monkeypatch.setattr(P, "REPORT_LIMIT_S", limit_s)
    monkeypatch.setattr(P, "device_planes", lambda path: ["/device:TPU:0"])
    try:
        out = P.PROFILER.capture(str(tmp_path), 5, lambda: {"steps": 1})
        assert out["dir"] == str(tmp_path) and out["ms"] == 5.0
        assert out["stop_ms"] >= 0
        assert out["report_ms"] == out["report"]["report_ms"] > 0
        assert error in out["report"]["error"]
        assert P.PROFILER.last_report == out["report"]
        assert P.PROFILER.last_counters == {"start": {"steps": 1},
                                            "stop": {"steps": 1}}
        assert P.PROFILER.captures == 1
    finally:
        P.PROFILER.reset()
    assert P.PROFILER.last_report is None


@pytest.mark.limit_s(120)
def test_the_child_writes_the_report_of_a_capture_as_one_json_line(tmp_path):
    """The module as a script, on a capture of this CPU: no device plane,
    which the report says in `error` with exit code 0."""
    import subprocess

    P.PROFILER.capture(str(tmp_path), 5)
    P.PROFILER.reset()
    done = subprocess.run([sys.executable, "-m", P.REPORT_MODULE,
                           str(tmp_path)], capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.strip().splitlines()[-1]) == {
        "error": "no device plane"}


# -- netstats through the shared walker -----------------------------------------


@pytest.mark.limit_s(20)
@pytest.mark.parametrize("hint,want", [
    ("decode_step", [3.0, 0.0]),
    ("prefill", [2.0]),
    (None, [3.0, 2.0, 0.0]),
    ("verify", []),
])
def test_per_step_op_ms_through_the_shared_walker_gives_what_it_gave(
        monkeypatch, hint, want):
    """Collective ms an execution, bucketed by the module span that holds
    the op's START, `XLA Ops` and `Async XLA Ops` alike (what the walk in
    netstats gave before it became a caller)."""
    from distributed_llama_tpu.runtime import netstats

    devices = [
        {"plane": "/device:TPU:0", "modules": [], "ops": []},   # ran nothing
        {"plane": "/device:TPU:1",
         "modules": [(0.010, 0.020, "jit_decode_step(1)"),
                     (0.030, 0.040, "jit_prefill_chunk_32(2)"),
                     (0.050, 0.060, "jit_decode_step(1)")],
         "ops": [(0.011, 0.012, "%all-reduce.1 = f32[8] all-reduce(%x)"),
                 (0.013, 0.015, "%all-gather-start.2 = f32[8] all-gather-"
                                "start(%x)"),
                 (0.016, 0.019, "%fusion.3 = f32[8] fusion(%x)"),
                 (0.031, 0.033, "%all-reduce.4 = f32[8] all-reduce(%x)"),
                 (0.045, 0.046, "%all-reduce.5 = f32[8] all-reduce(%x)")]}]
    seen = {}

    def walk(trace_dir, **kw):
        seen.update(kw, dir=trace_dir)
        return {"file": "x", "devices": devices, "host": []}

    monkeypatch.setattr(P, "walk_trace", walk)
    got = netstats.per_step_op_ms("somewhere", module_hint=hint)
    assert [round(x, 6) for x in got] == want
    assert seen == {"dir": "somewhere",
                    "op_lines": ("XLA Ops", "Async XLA Ops")}
