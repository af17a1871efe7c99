"""The one compile-cache helper (utils/compile_cache.py): placed from
outside when JAX_COMPILATION_CACHE_DIR is set, else one fixed path inside
the checkout — identical in every process, so workers and reboots share
compiles."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = ("import jax, json; "
        "from distributed_llama_tpu.utils.compile_cache import "
        "ensure_compile_cache; "
        "before = jax.config.jax_compilation_cache_dir; "
        "got = ensure_compile_cache(); "
        "print(json.dumps([before, got, "
        "jax.config.jax_compilation_cache_dir]))")


def _run(env_extra: dict, cwd: str) -> tuple:  # (before, got, after)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_extra)
    r = subprocess.run([sys.executable, "-c", CODE], env=env, cwd=cwd,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return tuple(json.loads(r.stdout.strip().splitlines()[-1]))


def test_env_var_set_leaves_config_untouched(tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    before, got, after = _run({"JAX_COMPILATION_CACHE_DIR": placed},
                              str(tmp_path))
    # JAX read the variable itself; the helper changed nothing
    assert before == after == got == placed


def test_unset_gives_the_fixed_in_checkout_path_in_every_process(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    a = _run({}, REPO)
    b = _run({"HOME": str(tmp_path), "TMPDIR": str(tmp_path)},
             str(tmp_path))  # another cwd, HOME and TMPDIR: same path
    assert a[0] is None and b[0] is None
    assert a[1:] == b[1:] == (want, want)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_hits_and_misses_are_counted_on_the_compile_ledger():
    from distributed_llama_tpu.runtime.profiler import COMPILES
    from distributed_llama_tpu.utils import compile_cache

    compile_cache.ensure_compile_cache()  # conftest already did: idempotent
    before = dict(compile_cache.COUNTS)
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_event("/jax/compilation_cache/cache_misses")
    compile_cache._on_event("/jax/some/other/event")
    s = COMPILES.summary()
    assert s["persistent_cache_hits"] == before["hits"] + 1
    assert s["persistent_cache_misses"] == before["misses"] + 1
