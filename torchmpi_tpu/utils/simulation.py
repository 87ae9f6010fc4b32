"""Simulated-mesh bootstrap: force N CPU devices in one process.

The rebuild's analog of "mpirun -np N on localhost is the fixture"
(SURVEY.md §5).  Shared by the test conftest, examples, and benchmarks so
the platform-forcing quirks live in exactly one place:

- ``XLA_FLAGS`` is read at backend-init time, so appending the forced host
  device count here works even if jax was already imported;
- ``JAX_PLATFORMS`` is read when jax is imported, which may already have
  happened, so the platform is forced via ``jax.config`` instead of the
  environment.
"""

from __future__ import annotations

import os
import re


def force_cpu_devices(n: int) -> None:
    """Make this process see at least ``n`` simulated CPU devices.  Must run
    before the first JAX backend use (not merely before import).

    A pre-set count smaller than ``n`` is raised to ``n`` — EXCEPT under the
    multi-process launcher (``TORCHMPI_TPU_COORDINATOR`` set), where the
    per-process device count is deliberate topology (nproc x devices_per_proc
    = global) and must not be clobbered."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    elif (int(m.group(1)) < n
          and "TORCHMPI_TPU_COORDINATOR" not in os.environ):
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}")
    import jax

    jax.config.update("jax_platforms", "cpu")
