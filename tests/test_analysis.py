"""dlgrind analyzer tests: every AST rule has a tripping fixture and a
clean fixture; the jaxpr audit is exercised with planted violations
(host callback, f64 promotion, full-precision activation re-replication);
and the REAL gate — the committed baseline vs the current tree — runs as
a normal (non-slow) test so `pytest -m "not slow"` enforces it exactly
like CI's `python -m distributed_llama_tpu.analysis --check`.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llama_tpu.analysis.ast_lint import lint_source
from distributed_llama_tpu.analysis.entrypoints import (EntryPoint,
                                                        signature_fingerprint)
from distributed_llama_tpu.analysis.findings import (Finding, format_github,
                                                     load_baseline,
                                                     parse_suppressions,
                                                     split_by_baseline,
                                                     write_baseline)
from distributed_llama_tpu.analysis.jaxpr_audit import audit_entry


def rules_of(findings):
    return {f.rule for f in findings}


def lint(path, src):
    return lint_source(path, src)


# -- Level 1: one tripping + one clean fixture per rule ---------------------


def test_dlg101_host_sync_in_jit_trips():
    src = (
        "import jax, numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.asarray(x)\n")
    assert "DLG101" in rules_of(lint("runtime/fx.py", src))


def test_dlg101_clean_on_host_values():
    src = (
        "import jax, numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    table = np.asarray([1, 2, 3])\n"  # host constant: fine
        "    return x + table.shape[0]\n")
    assert "DLG101" not in rules_of(lint("runtime/fx.py", src))


def test_dlg101_item_and_device_get_trip():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    a = x.item()\n"
        "    b = jax.device_get(x)\n"
        "    return a, b\n")
    found = [f for f in lint("ops/fx.py", src) if f.rule == "DLG101"]
    assert len(found) == 2


def test_dlg102_numpy_on_traced_trips():
    src = (
        "import jax, numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.dot(x, x)\n")
    assert "DLG102" in rules_of(lint("runtime/fx.py", src))


def test_dlg102_clean_numpy_on_host():
    src = (
        "import jax, numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    scale = np.dot([1.0, 2.0], [3.0, 4.0])\n"
        "    return x * scale\n")
    assert "DLG102" not in rules_of(lint("runtime/fx.py", src))


def test_dlg103_branch_on_traced_trips():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x.sum() > 0:\n"
        "        return x\n"
        "    return -x\n")
    assert "DLG103" in rules_of(lint("runtime/fx.py", src))


def test_dlg103_clean_on_static_shape_branch():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x, layers):\n"
        "    if x.shape[0] > 2 and layers:\n"  # shapes + container
        "        return x\n"                   # truthiness are static
        "    if 'wqkv' in layers:\n"
        "        return x\n"
        "    return -x\n")
    assert "DLG103" not in rules_of(lint("runtime/fx.py", src))


def test_dlg103_while_on_traced_trips():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    while x > 0:\n"
        "        x = x - 1\n"
        "    return x\n")
    assert "DLG103" in rules_of(lint("runtime/fx.py", src))


def test_dlg104_bare_literal_in_ops_trips():
    src = (
        "import jax.numpy as jnp\n"
        "def act(x):\n"
        "    return x * 0.5\n")
    assert "DLG104" in rules_of(lint("ops/fx.py", src))


def test_dlg104_clean_with_explicit_dtype_and_outside_ops():
    clean = (
        "import jax.numpy as jnp\n"
        "def act(x):\n"
        "    return x * jnp.float32(0.5)\n")
    assert "DLG104" not in rules_of(lint("ops/fx.py", clean))
    bare = (
        "import jax.numpy as jnp\n"
        "def act(x):\n"
        "    return x * 0.5\n")
    # the rule is scoped to ops kernels; parallel code is exempt
    assert "DLG104" not in rules_of(lint("parallel/fx.py", bare))


def test_dlg105_missing_donate_trips():
    src = (
        "import jax\n"
        "class E:\n"
        "    def build(self):\n"
        "        def run(params, tok, pos, cache):\n"
        "            return tok, cache\n"
        "        return jax.jit(run)\n")
    assert "DLG105" in rules_of(lint("runtime/engine.py", src))


def test_dlg105_clean_with_donate_and_cacheless():
    src = (
        "import jax\n"
        "class E:\n"
        "    def build(self):\n"
        "        def run(params, tok, pos, cache):\n"
        "            return tok, cache\n"
        "        fn = jax.jit(run, donate_argnums=(3,))\n"
        "        amax = jax.jit(lambda l: l.argmax())\n"  # no cache: fine
        "        return fn, amax\n")
    assert "DLG105" not in rules_of(lint("runtime/engine.py", src))


def test_dlg106_debug_leftovers_trip():
    src = (
        "import jax\n"
        "def k(x):\n"
        "    jax.debug.print('x={}', x)\n"
        "    print('done')\n"
        "    return x\n")
    found = [f for f in lint("ops/fx.py", src) if f.rule == "DLG106"]
    assert len(found) == 2


def test_dlg106_scoped_to_kernel_dirs():
    src = "def main():\n    print('hello')\n"
    assert "DLG106" not in rules_of(lint("apps/cli.py", src))


def test_dlg107_host_boundary_sync_trips():
    src = (
        "import jax, numpy as np\n"
        "def fetch(logits: jax.Array):\n"
        "    return np.asarray(logits)\n")
    assert "DLG107" in rules_of(lint("runtime/fx.py", src))


def test_dlg107_numpy_params_are_not_device_values():
    src = (
        "import numpy as np\n"
        "def pack(x: np.ndarray):\n"
        "    return np.ascontiguousarray(x.swapaxes(-1, -2))\n")
    assert "DLG107" not in rules_of(lint("quants/fx.py", src))


def test_dlg107_taint_through_jitted_step_handle():
    src = (
        "import jax, numpy as np\n"
        "class E:\n"
        "    def step(self):\n"
        "        fn = self._compiled_step(1)\n"
        "        logits, cache = fn(self.params, self.cache)\n"
        "        return np.asarray(logits)\n")
    assert "DLG107" in rules_of(lint("runtime/fx.py", src))


def test_dlg101_rebinding_to_host_clears_taint_inside_branch():
    """Regression: the sink scan must see in-branch re-bindings — a
    pre-walk of the whole subtree with pre-branch taint flagged the second
    call here even though `x` is a host constant by then."""
    src = (
        "import jax, numpy as np\n"
        "@jax.jit\n"
        "def f(x, flag=True):\n"
        "    if flag:\n"
        "        x = np.asarray([1.0])\n"
        "        y = np.asarray(x)\n"
        "    return x\n")
    assert "DLG101" not in rules_of(lint("runtime/fx.py", src))


# -- suppression + baseline mechanics ---------------------------------------


def test_inline_suppression():
    src = (
        "import jax, numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.asarray(x)  # dlgrind: ignore[DLG101]\n")
    assert "DLG101" not in rules_of(lint("runtime/fx.py", src))
    # the ignore is rule-specific: other rules on the line still fire
    supp = parse_suppressions(src)
    assert supp[4] == {"DLG101"}


def test_bare_suppression_covers_all_rules():
    supp = parse_suppressions("x = 1  # dlgrind: ignore\n")
    assert supp[1] is None


def test_baseline_roundtrip_and_split(tmp_path):
    f1 = Finding("DLG107", "info", "runtime/engine.py", 10, "sync A")
    f2 = Finding("DLG107", "info", "runtime/engine.py", 99, "sync B")
    path = str(tmp_path / "baseline.json")
    write_baseline(path, [f1], {"decode_step": "abc"})
    base = load_baseline(path)
    new, accepted = split_by_baseline([f1, f2], base)
    assert [f.message for f in accepted] == ["sync A"]
    assert [f.message for f in new] == ["sync B"]
    # line moves must not invalidate the baseline (keys are line-free)
    f1_moved = Finding("DLG107", "info", "runtime/engine.py", 42, "sync A")
    new2, _ = split_by_baseline([f1_moved], base)
    assert new2 == []


def test_baseline_counts_occurrences_per_key(tmp_path):
    """Multiset semantics: one allowlisted `int(n)` sync must not mask a
    reintroduced second copy with the identical message."""
    f = Finding("DLG107", "info", "runtime/engine.py", 10, "`int(n)` sync")
    path = str(tmp_path / "baseline.json")
    write_baseline(path, [f, f], {})  # two accepted sites
    base = load_baseline(path)
    assert base["findings"].count(f.key()) == 2
    trio = [Finding("DLG107", "info", "runtime/engine.py", ln,
                    "`int(n)` sync") for ln in (10, 99, 150)]
    new, accepted = split_by_baseline(trio, base)
    assert len(accepted) == 2 and len(new) == 1


def test_github_format():
    f = Finding("DLG101", "error", "runtime/engine.py", 7, "bad sync")
    out = format_github([f])
    assert out == "::error file=runtime/engine.py,line=7::DLG101: bad sync"


# -- Level 2: jaxpr audit with planted violations ---------------------------


def _ep(name, fn, args, act=4):
    return EntryPoint(name, fn, args, {"activation_elems": act})


def test_jaxpr_audit_detects_planted_f64():
    def promoted(x):
        return x * np.float64(1.5)  # the planted f64 promotion

    findings, _ = audit_entry(_ep("planted_f64", promoted,
                                  (jnp.ones((4,), jnp.float32),)))
    assert "DLG202" in rules_of(findings)


def test_jaxpr_audit_clean_on_pinned_dtypes():
    def pinned(x):
        return x * jnp.float32(1.5) + 0.25  # weak literal: no promotion

    findings, _ = audit_entry(_ep("pinned", pinned,
                                  (jnp.ones((4,), jnp.float32),)))
    assert rules_of(findings) == set()


def test_jaxpr_audit_detects_host_callback():
    def chatty(x):
        jax.debug.print("x = {}", x)
        return x + 1

    findings, _ = audit_entry(_ep("chatty", chatty,
                                  (jnp.ones((4,), jnp.float32),)))
    assert "DLG201" in rules_of(findings)


def test_jaxpr_audit_detects_replication_leak():
    from jax.sharding import PartitionSpec as P

    from distributed_llama_tpu.parallel import make_mesh
    from jax import shard_map

    mesh = make_mesh(tp=2, dp=1)
    x = jnp.ones((2, 8), jnp.float32)

    def leaky(x):
        # f32 all_gather re-replicates the tp-sharded activation — the
        # exact pattern the q80 exchange exists to avoid
        def body(v):
            return jax.lax.all_gather(v, "tp", tiled=True)
        return shard_map(body, mesh=mesh, in_specs=P("tp"), out_specs=P(),
                         check_vma=False)(x)

    findings, _ = audit_entry(_ep("leaky", leaky, (x,), act=16))
    assert "DLG203" in rules_of(findings)

    def compressed(x):
        # int8 payload (the q80 wire) must NOT trip the rule
        def body(v):
            q = v.astype(jnp.int8)
            return jax.lax.all_gather(q, "tp", tiled=True).astype(jnp.float32)
        return shard_map(body, mesh=mesh, in_specs=P("tp"), out_specs=P(),
                         check_vma=False)(x)

    findings2, _ = audit_entry(_ep("compressed", compressed, (x,), act=16))
    assert "DLG203" not in rules_of(findings2)


def test_audit_reports_unauditable_entry_points(monkeypatch):
    """A backend too small for the tp/ep entries must FAIL the gate
    (DLG200), not skip them silently — a vacuous pass is the worst
    outcome for a correctness gate."""
    from distributed_llama_tpu.analysis import jaxpr_audit

    monkeypatch.setattr(jax, "device_count", lambda *a, **k: 1)
    findings, fingerprints = jaxpr_audit.audit_all({})
    skipped = [f for f in findings if f.rule == "DLG200"]
    assert skipped, "short mesh produced no DLG200 findings"
    assert "tp_q80_col" in {f.file.strip("<>").split(":")[1]
                            for f in skipped}
    assert "tp_q80_col" not in fingerprints


def test_callback_finding_message_is_stable():
    """DLG201 messages are baseline keys — they must not embed object
    reprs (memory addresses change every process)."""
    def chatty(x):
        jax.debug.print("x = {}", x)
        return x

    f1, _ = audit_entry(_ep("c", chatty, (jnp.ones((2,), jnp.float32),)))
    f2, _ = audit_entry(_ep("c", chatty, (jnp.ones((2,), jnp.float32),)))
    m1 = [f.message for f in f1 if f.rule == "DLG201"]
    m2 = [f.message for f in f2 if f.rule == "DLG201"]
    assert m1 and m1 == m2
    assert "0x" not in m1[0]


def test_fingerprint_detects_signature_drift():
    def f(x):
        return x + 1

    a = signature_fingerprint(_ep("e", f, (jnp.ones((4,), jnp.float32),)))
    same = signature_fingerprint(_ep("e", f, (jnp.ones((4,), jnp.float32),)))
    wider = signature_fingerprint(_ep("e", f,
                                      (jnp.ones((4,), jnp.bfloat16),)))
    reshaped = signature_fingerprint(_ep("e", f,
                                         (jnp.ones((8,), jnp.float32),)))
    assert a == same
    assert len({a, wider, reshaped}) == 3
    # weak-typed scalars are a distinct compilation key from pinned ones —
    # the classic accidental-retrace source
    strong = signature_fingerprint(_ep("e", f, (jnp.float32(1.0),)))
    weak = signature_fingerprint(_ep("e", f, (jnp.asarray(1.0),)))
    assert strong != weak


def test_stale_pinned_fingerprint_reported_as_dlg108():
    """A baseline fingerprint for an entry point that no longer exists is
    baseline staleness (DLG108), distinct from live drift (DLG204) — a
    dead pin would otherwise shadow a future entry of the same name."""
    from distributed_llama_tpu.analysis.jaxpr_audit import audit_all

    findings, fingerprints = audit_all({"no_such_entry": "deadbeef"})
    stale = [f for f in findings if f.rule == "DLG108"]
    assert any(f.file == "<entry:no_such_entry>" for f in stale)
    assert all(f.rule != "DLG204" or "no_such_entry" not in f.file
               for f in findings)
    assert "no_such_entry" not in fingerprints


# -- the real gate: current tree vs committed baseline ----------------------


def test_analyzer_gate_repo_is_clean():
    """The CI gate, pytest-collected: the package's own source plus the
    traced entry points must produce NO findings beyond the committed
    baseline. A new host sync / f64 promotion / debug leftover anywhere in
    the package fails this test with the finding list in the message."""
    from distributed_llama_tpu.analysis.__main__ import (DEFAULT_BASELINE,
                                                         gather_findings,
                                                         hygiene_findings)

    baseline = load_baseline(DEFAULT_BASELINE)
    # same collection the CLI gate runs (L1 + dlrace + DLG206 + jaxpr),
    # so this test and `--check` cannot drift
    findings, fingerprints = gather_findings(baseline)
    new, _ = split_by_baseline(findings, baseline)
    new.extend(hygiene_findings(findings, baseline))
    assert not new, "\n".join(f"{f.anchor()}: {f.rule} {f.message}"
                              for f in new)
    # every audited entry point must have a pinned fingerprint — a NEW
    # entry point must be baselined deliberately, not silently accepted
    missing = set(fingerprints) - set(baseline.get("fingerprints", {}))
    assert not missing, f"entry points without baseline fingerprints: {missing}"
