"""CPU rehearsal of chip_smoke.py's control flow (slow: it boots real
`dllama api` servers). The phase functions run unchanged over a tiny spec
with children pinned to the CPU backend; what only silicon can show
(kernels in the executables, allocator bytes, the `tpu` platform) is
skipped by the phases themselves when the plan does not want a TPU, and
`chip_smoke.main()` — which always wants one — must fail here."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TINY = dict(arch="LLAMA", dim=128, hidden_dim=256, n_layers=2, n_heads=4,
            n_kv_heads=4, vocab_size=288, seq_len=256, hidden_act="SILU",
            rope_theta=10000.0)


def _plan(tmp_path, **kw):
    import chip_smoke

    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           # tiny CPU compiles finish under JAX's 1 s caching threshold;
           # cache them all so the procs phase's hit check means here what
           # it means on the chip (the worker re-boots serve's programs)
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    return chip_smoke.Plan(
        spec=TINY, workdir=str(tmp_path), child_env=env, want_backend="cpu",
        serve_batch=2, serve_chunk=16, max_seq_len=256, prefix_blocks=16,
        prefix_block_len=16, max_tokens=6, interpret=True,
        boot_timeout=300.0, **kw)


@pytest.mark.slow
def test_one_chip_phases_rehearse_on_cpu(tmp_path):
    import chip_smoke

    plan = _plan(tmp_path)
    chip_smoke.phase_synth(plan)
    dev = chip_smoke.phase_serve(plan)
    assert dev["platform"] == "cpu"
    assert chip_smoke.phase_parity(plan)["platform"] == "cpu"
    assert chip_smoke.phase_procs(plan)["platform"] == "cpu"


@pytest.mark.slow
def test_four_chip_phase_rehearses_on_virtual_devices(tmp_path):
    import chip_smoke

    plan = _plan(tmp_path)
    chip_smoke.phase_synth(plan)
    assert chip_smoke.phase_tp(plan, 4)["count"] == 4


@pytest.mark.slow
def test_main_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    last = r.stdout.strip().splitlines()[-1]
    with pytest.raises(ValueError):
        json.loads(last)
