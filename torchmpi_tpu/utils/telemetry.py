"""The sys.modules-gated obs dispatch shim.

Every off-by-default layer (faults, guard, integrity) reports telemetry
through :mod:`torchmpi_tpu.obs` *without importing it* — a
faults-only or guard-only session must never pull the telemetry layer
into the process (the never-imported-when-off discipline).  This is
the ONE implementation of that contract: look the module up in
``sys.modules``, check ``active()``, dispatch, and swallow everything
— telemetry never fails a step.  Dependency-free on purpose.
"""

from __future__ import annotations

import sys


def _obs():
    """The obs module iff it is imported AND active, else None."""
    mod = sys.modules.get("torchmpi_tpu.obs")
    try:
        if mod is not None and mod.active():
            return mod
    except Exception:  # noqa: BLE001 — telemetry never fails a step
        pass
    return None


def active() -> bool:
    """Whether :func:`emit` would dispatch: for a caller with a batch of
    reports to prepare, so that it can skip the preparation."""
    return _obs() is not None


def emit(method: str, *args, **kwargs) -> None:
    """Call ``torchmpi_tpu.obs.<method>(*args, **kwargs)`` iff obs is
    imported AND active; no-op (and exception-proof) otherwise."""
    mod = _obs()
    try:
        if mod is not None:
            getattr(mod, method)(*args, **kwargs)
    except Exception:  # noqa: BLE001 — telemetry never fails a step
        pass


def flight_tail(n: int = 8) -> list:
    """The last ``n`` flight-recorder events, when obs is active — the
    evidence a typed hang/timeout error ships with so the exception
    that kills a step arrives with what ``obs_tool blame`` would
    otherwise dig out of a post-mortem dump.  The ONE implementation
    (``faults.policy`` and ``watchdog`` both route here); same
    sys.modules gate as :func:`emit`."""
    mod = _obs()
    try:
        if mod is not None:
            return mod.recorder().to_records(best_effort=True)[-n:]
    except Exception:  # noqa: BLE001 — evidence must not mask the error
        pass
    return []
