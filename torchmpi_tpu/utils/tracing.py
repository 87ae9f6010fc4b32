"""Profiler capture (SURVEY.md §6.1).

The reference had nothing built-in (external MPI profilers only); here
:func:`trace` captures a ``jax.profiler`` trace around a region of the
program and leaves perfetto-compatible files.  What the capture shows by
name is written where the work is, not here: ``tm.step`` (the train
step's dispatch), the ``tm.serve.*`` tree (the serving tier's phases),
the Pallas kernels' ``tm_kernel`` identities and the models'
``jax.named_scope``s (docs/OBSERVABILITY.md, "What a profile shows").
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/torchmpi_tpu_trace",
          create_perfetto_link: bool = False) -> Iterator[str]:
    """Capture a profiler trace around a code region.

    View with tensorboard or ui.perfetto.dev (the trace.json.gz under
    ``<log_dir>/plugins/profile/...``).

    Robust to nested/failed ``start_trace``: jax allows one trace per
    process, so a ``trace()`` inside another (or after a crashed one
    left the profiler running) degrades to a no-op span instead of
    raising — and ``stop_trace`` only runs when OUR start succeeded, so
    a failed start can never raise a masking error out of the
    ``finally`` over the body's real exception.
    """
    os.makedirs(log_dir, exist_ok=True)
    started = False
    try:
        jax.profiler.start_trace(log_dir,
                                 create_perfetto_link=create_perfetto_link)
        started = True
    except RuntimeError:
        pass  # already tracing (nested start): body still runs, unprofiled
    try:
        yield log_dir
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except RuntimeError:
                pass  # torn down elsewhere; never mask the body's error
