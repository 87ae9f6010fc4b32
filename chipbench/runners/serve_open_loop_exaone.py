"""``serve_open_loop_experts`` for a served model whose attention cache is of
two kinds, a ring of ``sliding_window`` rows in each window layer and every
position in each global layer (``k-exaone-236b-a23b-serve``): the same
runner, loop, window, sample and reference comparison, with two names of
``serve_open_loop`` bound anew when this one is imported, as the other thin
runners bind theirs (the benchmark's tools load ``serve_open_loop`` by name,
so binding serves them too):

- ``window`` -> :func:`window`: the same window; when it has closed, what
  the engine books for a cached token and for a slot's rings is kept on the
  run (``cache_bytes``), before the pool is freed.
- ``judge`` -> :func:`judge`: a configuration whose ``tolerance`` has
  ``cache_bytes_as_stated`` is judged here, any other by what was bound
  before.

What is judged here.  ``serve_open_loop_experts``' judge and its two shares
(``off_the_top_share``, ``worst_request_off_share``), which tell the program
from the float8 control.  The file also states the cache's type
(``cache_dtype`` float32), and no statistic of the served tokens can hold
the program to it: the keys and values come out of bfloat16 projections
(the stated ``compute_dtype``), so a value is a bfloat16 number before it is
cached and a key differs from one only by the bits its QK-norm and rotation
add.  A cache held in bfloat16 moves the served tokens less than the
program's own bfloat16 arithmetic does (the readings are in the file's
``tolerance.reason``).  What such a cache DOES change is what the pool
holds, and the engine books that from the pool's own shapes and types.  So
``correct`` also asks, exactly:

- ``cache_bytes_per_token``: what the engine books for one cached position
  (the global layers' key and value) is what those layers' keys and values
  cost at ``cache_dtype`` (the configuration's ``flops`` module,
  ``kv_bytes_per_row``);
- ``ring_bytes_per_slot``: what it books for a slot's rings is ``window``
  such rows in every window layer.
"""

from __future__ import annotations

import importlib

from chipbench import harness
from chipbench.readers.serve_decode_roofline import WIDTH

experts = harness.load_module(harness.load_manifest(), "runners",
                              "serve_open_loop_experts")
base = experts.base


def window(cell, ready, schedule, seconds, **kw):
    s = _window(cell, ready, schedule, seconds, **kw)
    s.cache_bytes = {"cache_bytes_per_token":
                     ready.engine.cache_bytes_per_token,
                     "ring_bytes_per_slot": ready.engine.ring_bytes_per_slot}
    return s


def stated_cache_bytes(config):
    """What a cached position of the global layers and a slot's rings cost
    with keys and values at the file's ``cache_dtype``."""
    f = config["flops"]
    counts = importlib.import_module(f"chipbench.{f['module']}")
    sizes = {k: config[k] for k in f["sizes"]}
    row = counts.kv_bytes_per_row(cache_bytes=WIDTH[config["cache_dtype"]],
                                  **sizes)
    kinds = counts.layers_of(**sizes)
    rings = sum(windowed for windowed, _ in kinds)
    return {"cache_bytes_per_token": (len(kinds) - rings) * row,
            "ring_bytes_per_slot": rings * config["sliding_window"] * row}


def judge(cell, seed, s, compile_events=()):
    if "cache_bytes_as_stated" not in cell.config["tolerance"]:
        return _before(cell, seed, s, compile_events)
    checked, compared, correct = experts.judge(cell, seed, s, compile_events)
    stated = stated_cache_bytes(cell.config)
    mine = {k: [s.cache_bytes[k], stated[k]] for k in stated}
    ok = all(got == want for got, want in mine.values())
    return checked, {**compared, **mine}, bool(correct and ok)


_window, _before = base.window, base.judge
base.window = window
base.judge = judge

setup, served, layer_metrics, run = (base.setup, base.served,
                                     base.layer_metrics, base.run)
CHECKED_REQUESTS, TRACE_DIR = base.CHECKED_REQUESTS, base.TRACE_DIR
