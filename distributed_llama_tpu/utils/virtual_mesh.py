"""Virtual CPU mesh bootstrap — the ONE place the device-count convention
lives.

Two consumers need "N virtual CPU devices" before jax is even imported:
tests/conftest.py (the 8-device SPMD test mesh) and the dlgrind jaxpr audit
(analysis/__main__.py traces mesh entry points). XLA parses XLA_FLAGS once
per process, so both must append the flag the same way and early; hand-rolled
copies of this logic drifted — hence this module, which imports nothing heavy
(NO jax) so it is safe to call before backend selection. (Code that has
already imported jax sets `jax_num_cpu_devices` instead.)
"""

from __future__ import annotations

import os

VIRTUAL_MESH_DEVICES = 8  # the CI/test convention (tests/conftest.py)


def ensure_virtual_cpu_devices(n: int = VIRTUAL_MESH_DEVICES) -> None:
    """Idempotently request `n` host-platform devices via XLA_FLAGS.

    Takes effect only if no XLA backend has materialized yet (flags are
    parsed once per process); callers that can verify afterwards should
    (tests/conftest.py asserts the device count).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}")
