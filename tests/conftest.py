"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip sharding is validated the way the reference validates multi-node
slicing without a cluster (ref: src/transformer-test.cpp:21-72 instantiates
all slices in one process) — but stronger: a real 8-device SPMD mesh via
XLA's host-platform device partitioning.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite runs on the CPU backend

# the 8-device convention lives in ONE place, shared with the dlgrind
# jaxpr audit and the multichip dryrun (utils/virtual_mesh.py is jax-free,
# so importing it here cannot initialize a backend early)
from distributed_llama_tpu.utils.virtual_mesh import \
    ensure_virtual_cpu_devices  # noqa: E402

ensure_virtual_cpu_devices()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

assert jax.device_count() == 8, jax.devices()

# persistent compilation cache: the suite's cost is dominated by XLA
# compiles of the SPMD mesh tests; cached executables cut a warm rerun
# drastically. Placed by the one helper every compiling process uses.
from distributed_llama_tpu.utils.compile_cache import \
    ensure_compile_cache  # noqa: E402

ensure_compile_cache()

import gc  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# the suite segfaults intermittently when Python's cyclic GC traverses
# jax tracing objects (faulthandler shows "Garbage-collecting" under
# partial_eval.to_jaxpr frames; an explicit between-test gc.collect()
# crashed the same way, so it is the traversal itself that is unsafe on
# this jaxlib/CPython pin, not its timing). Cyclic GC is disabled for the
# whole run: device buffers and most of the heap are refcount-freed as
# usual; only cyclic garbage accumulates, which a finite test session
# tolerates.
gc.collect()
gc.freeze()  # startup objects never become garbage — skip scanning them
gc.disable()

_exit_status: list = [None]


def pytest_sessionfinish(session, exitstatus):
    _exit_status[0] = int(exitstatus)


@pytest.hookimpl(trylast=True)
def pytest_unconfigure(config):
    # interpreter finalization runs a last GC pass over everything the
    # session accumulated, which crashes the same way (exit code 139 AFTER
    # the summary printed — the run looked like a segfault to CI). All
    # reporting is done by the time unconfigure fires: flush and leave
    # without finalizing, preserving pytest's real exit status.
    if _exit_status[0] is not None:
        import sys

        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(_exit_status[0])


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """`@pytest.mark.limit_s(n)`: a test's own limit in seconds, on the
    interval timer of the worker's main thread (where pytest runs a test);
    a test that outlasts it fails there and does not hold the run."""
    mark = item.get_closest_marker("limit_s")
    if mark is None:
        yield
        return
    import signal

    def over(signum, frame):
        pytest.fail(f"over its limit of {mark.args[0]} s", pytrace=False)

    before = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, mark.args[0])
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def forward_entry_inputs(arch: str = "LLAMA", *, batch: int = 1, t: int = 1,
                         spec=None, dtype=None):
    """Shared builder for abstract entry-point inputs — (spec, params,
    tokens, pos0, cache) for a forward() call. The SAME programs the
    analyzer's jaxpr audit traces (distributed_llama_tpu/analysis/
    entrypoints.py): test_hlo_wire.py lowers them to count collectives,
    the audit walks their jaxprs, and both stay in lock-step by
    construction."""
    import jax.numpy as jnp

    from distributed_llama_tpu.analysis.entrypoints import \
        build_forward_inputs

    return build_forward_inputs(spec, batch=batch, t=t,
                                dtype=dtype or jnp.float32, arch=arch)
