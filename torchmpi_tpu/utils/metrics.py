"""Structured metrics & timing (SURVEY.md §6.5: the reference had print-only
observability; the BASELINE metrics demand per-step structure).

:func:`fence` synchronizes with a one-element device->host readback: the
value cannot reach the host before the program that produces it has run.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np


def fence(x) -> None:
    """Hard synchronization: force a readback of one element of ``x``."""
    leaf = jax.tree.leaves(x)[0]
    np.asarray(jax.device_get(leaf.ravel()[0] if leaf.ndim else leaf))


# Per-round seconds/iter of the most recent timed() call, fastest first
# is NOT applied — this is the raw chronological spread, so a consumer
# can audit how far min-of-rounds sits from the mean (ADVICE r3: the
# min-selection headline must leave the spread on the record).  Kept for
# backward compatibility; new code should read TimedResult.round_times.
last_round_times: List[float] = []


class TimedResult(float):
    """Structured result of :func:`timed`.

    IS a float (min-of-rounds seconds/iter) so every existing consumer
    keeps working, and carries the full per-round spread:

    - ``round_times``  chronological seconds/iter of each round
    - ``median``       median of the rounds (the autotune scoring rule)
    - ``jitter``       half the inter-quartile range — the scale a knob
                       delta must clear to be more than noise
    """

    __slots__ = ("round_times", "median", "jitter")

    def __new__(cls, round_times: List[float]) -> "TimedResult":
        ts = list(round_times)
        self = super().__new__(cls, min(ts))
        s = sorted(ts)
        n = len(s)
        self.round_times = ts
        self.median = (s[n // 2] if n % 2
                       else 0.5 * (s[n // 2 - 1] + s[n // 2]))
        self.jitter = (0.5 * (s[(3 * n) // 4] - s[n // 4]) if n >= 4
                       else 0.5 * (s[-1] - s[0]))
        return self


def timed(step, iters: int, fence=fence, rounds: int = 3) -> TimedResult:
    """Seconds per iteration of ``step``: one warm/compile call, then
    ``rounds`` fenced timing rounds of ``iters`` dispatches, returned as
    a :class:`TimedResult` — a float equal to the FASTEST round, with
    the median/jitter/per-round spread attached.

    The float is the minimum of the rounds (the first post-compile
    round can run slower than steady state even after a fenced warmup
    call); the median and the spread ride along for consumers that want
    them.  The per-round times of the last call are also published in
    ``last_round_times`` (chronological, backward compat).  The shared
    harness behind bench.py, the scripts/ sweeps, and the online
    collective autoselector (``torchmpi_tpu.tuning``)."""
    out = step()
    fence(out)
    del last_round_times[:]
    for _ in range(max(1, rounds)):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step()
        fence(out)
        last_round_times.append((time.perf_counter() - t0) / iters)
    return TimedResult(last_round_times)


def chained(fn, depth: int = 4):
    """One jit program running ``depth`` dependent invocations of
    ``fn(x, *rest) -> y`` with ``y`` fed back as ``x`` — divide the
    measured time by ``depth`` for the per-invocation figure.

    Per-dispatch host overhead can be larger than a small kernel:
    single-call timings put it in both sides of every ratio.  Inside
    one program it is paid once, and the data dependence stops CSE from
    collapsing the identical calls (ops whose output cannot feed their
    input must rotate an operand instead — see bench.py stage C2).
    Shared by bench.py stage C and scripts/flash_sweep.py."""
    import jax

    @jax.jit
    def run(x, *rest):
        for _ in range(depth):
            x = fn(x, *rest).astype(x.dtype)
        return x

    return run


class Timer:
    """Wall-clock step timer with warmup and fenced boundaries."""

    def __init__(self):
        self._t0: Optional[float] = None
        self.steps = 0

    def start(self, fence_on=None):
        if fence_on is not None:
            fence(fence_on)
        self._t0 = time.time()
        self.steps = 0

    def tick(self):
        self.steps += 1

    def stop(self, fence_on=None) -> float:
        if fence_on is not None:
            fence(fence_on)
        assert self._t0 is not None
        return time.time() - self._t0


class MetricsLogger:
    """Per-step metrics as JSONL (img/s/chip, step time, achieved GB/s).

    A thin wrapper over the observability registry: when
    ``torchmpi_tpu.obs`` is active (``Config.obs != "off"``) every
    record is also counted there (``tm_log_records_total{logger=...}``)
    so a telemetry dump shows how much step-log traffic each stream
    produced.  The lookup goes through ``sys.modules`` — a process that
    never enabled obs never imports it (the off-path discipline)."""

    def __init__(self, path: Optional[str] = None, name: str = "metrics"):
        self.path = path
        self.name = name
        self.records: List[Dict[str, Any]] = []

    def log(self, **kw) -> None:
        rec = {"t": time.time(), **kw}
        self.records.append(rec)
        if self.path:
            with open(self.path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        obs = sys.modules.get("torchmpi_tpu.obs")
        if obs is not None and obs.active():
            obs.record_log(self.name)


def allreduce_bus_bandwidth(nbytes: int, n_devices: int,
                            seconds: float) -> float:
    """Effective bus bandwidth GB/s, the reference's benchmark metric:
    algbw = size/time; busbw = algbw * 2(n-1)/n (ring lower bound)."""
    if seconds <= 0 or n_devices <= 1:
        return 0.0
    algbw = nbytes / seconds
    return algbw * 2 * (n_devices - 1) / n_devices / 1e9
