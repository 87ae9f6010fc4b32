"""The train-step runner: one cell, one process, one window.

Set-up (all of it ``setup_s``): ``mpi.init()``, the configuration's step
built by ``steps/<step>.py`` (weights, a ring of device-resident batches,
the correctness check against the plain reference), three warm-up steps.
Then the window: a loop that keeps ONE step in flight.  It dispatches step
i+1, then blocks on step i's loss and stamps its completion: how a
training loop that logs its loss runs.  The device stays fed, and there is
one interval per step with no fence between dispatches.

With ``--trace 1`` the same window runs untraced (throughput for the MFU,
host spans), then ``TRACED_STEPS`` more steps run under the profiler, and
the per-layer readers get both.
"""

from __future__ import annotations

import contextlib
import faulthandler
import math
import os
import shutil
import time

from chipbench import harness

WARMUP_STEPS = 3
TRACED_STEPS = 10
SETUP_DEADLINE_S = 1150      # the contract allows a compiling run 1200 s
TRACE_DIR = os.path.join(harness.ROOT, ".chipbench_trace")

clock = time.perf_counter


def deadline(seconds):
    """No step may hang the chip: past ``seconds`` the process dumps every
    thread's stack and exits non-zero (``chip_smoke.deadline``)."""
    faulthandler.dump_traceback_later(seconds, exit=True)


class CompileCounter:
    """Counts the programs jax compiles, or loads from the persistent
    cache, while armed.  (Tracing alone is not counted: the Pallas
    interpreter of the rehearsal traces its kernels on every call.)"""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.armed, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.events.append(event)


def window(bundle, state, *, seconds, max_steps=None, annotate=False):
    """Run steps for ``seconds`` (or ``max_steps``), one in flight.

    Returns the state and a record: ``t0`` (the priming step's completion,
    where the window opens), one completion stamp and one loss per step,
    and the host spans ``dispatch`` (the call into the step: enqueue only)
    and ``wait_loss`` (blocked on the previous step's loss)."""
    if annotate:
        import jax.profiler

        span = jax.profiler.TraceAnnotation
    else:
        span = contextlib.nullcontext
    ring = bundle.batches
    spans = {"dispatch": [], "wait_loss": []}
    count = [0]

    def dispatch(state, record=True):
        batch = ring[count[0] % len(ring)]
        count[0] += 1
        t = clock()
        with span("dispatch"):
            *state, loss = bundle.step(*state, *batch)
        if record:
            spans["dispatch"].append((t, clock()))
        return state, loss

    state, pending = dispatch(state, record=False)
    state, nxt = dispatch(state, record=False)
    with span("wait_prime"):
        float(pending)
    t0 = clock()
    pending = nxt
    stamps, losses = [], []
    while True:
        state, nxt = dispatch(state)
        t = clock()
        with span("wait_loss"):
            value = float(pending)
        now = clock()
        spans["wait_loss"].append((t, now))
        stamps.append(now)
        losses.append(value)
        pending = nxt
        if now - t0 >= seconds or (max_steps and len(stamps) >= max_steps):
            break
    with span("wait_drain"):
        float(pending)           # the step still in flight: not counted
    return state, {"t0": t0, "stamps": stamps, "losses": losses,
                   "spans": spans}


def layout_ok(bundle, devices):
    """On several chips every device holds a shard of the batch and a
    replica of the parameters."""
    want = set(devices)
    shards = bundle.sharded.addressable_shards
    replicas = bundle.replicated.addressable_shards
    per = bundle.sharded.shape[0] // len(devices)
    return ({s.device for s in shards} == want
            and all(s.data.shape[0] == per for s in shards)
            and {s.device for s in replicas} == want
            and all(s.data.shape == bundle.replicated.shape
                    for s in replicas))


def traced_layers(cell, bundle, state, ctx):
    """``TRACED_STEPS`` more steps under the profiler, then every per-layer
    reader of the cell.  ``ctx`` carries what the untraced window gave;
    the trace and its step count are added here.  A reader that finds
    nothing to read returns None, and its metric is left out."""
    import jax.profiler

    from chipbench import xplane

    trace_dir = os.path.join(TRACE_DIR, cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0    # the host's Python frames would slow
    #                                    the very loop they watch
    deadline(300)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        state, traced = window(bundle, state, seconds=math.inf,
                               max_steps=TRACED_STEPS, annotate=True)
    finally:
        jax.profiler.stop_trace()
    trace = xplane.load(xplane.newest(trace_dir))
    ctx = {**ctx, "trace": trace, "traced_steps": len(traced["stamps"])}
    layer = {}
    for m in cell.per_layer:
        reader = harness.load_module(cell.manifest, "readers", m["reader"])
        value = reader.read(ctx, **m["args"])
        if value is not None:
            layer[m["name"]] = {"value": value, "unit": m["unit"]}
    return state, layer, xplane.device_busy(trace), xplane.breakdown(trace)


def run(cell, args):
    import jax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.utils import compilecache

    devices = jax.devices()
    chips = len(devices)
    harness.log(f"{cell.name}: {devices[0].platform} "
                f"{devices[0].device_kind} x{chips}; compile cache at "
                f"{compilecache.enable_persistent_cache()}")
    compiles = CompileCounter()

    # ---------------------------------------------------------- set-up
    deadline(SETUP_DEADLINE_S)
    mesh = mpi.init()
    step_mod = harness.load_module(cell.manifest, "steps",
                                   cell.config["step"])
    bundle = step_mod.build(cell, mesh, harness.seed_key(args.seed))
    harness.log(f"built after {clock() - harness.T0:.1f} s")
    layout = layout_ok(bundle, devices)
    state, warm = bundle.state, []
    for i in range(WARMUP_STEPS):
        *state, loss = bundle.step(
            *state, *bundle.batches[i % len(bundle.batches)])
        warm.append(float(loss))
    checked = bundle.check(warm[0])
    setup_s = clock() - harness.T0
    harness.log(f"set-up {setup_s:.1f} s; check {checked}")

    # ---------------------------------------------------------- window
    deadline(args.seconds + 120)
    compiles.armed = True
    state, rec = window(bundle, state, seconds=args.seconds)
    compiles.armed = False
    steps = len(rec["stamps"])
    window_s = rec["stamps"][-1] - rec["t0"]
    edges = [rec["t0"]] + rec["stamps"]
    intervals_ms = [1e3 * (b - a) for a, b in zip(edges, edges[1:])]
    per_chip = steps * bundle.items_per_step / window_s / chips
    finite = all(map(math.isfinite, warm + rec["losses"]))

    e2e = {
        f"{cell.config['item']}_per_s_chip": per_chip,
        "step_ms_p90": harness.percentile(intervals_ms, 90),
        "setup_s": setup_s,
    }
    result = {
        "correct": bool(checked["ok"] and finite and layout
                        and not compiles.events),
        "attempted": steps,
        "failed": sum(not math.isfinite(x) for x in rec["losses"]),
        "checks": {"reference": checked, "losses_finite": finite,
                   "compiles_in_window": compiles.events,
                   "layout_ok": layout},
        "window": {"steps": steps, "seconds": window_s,
                   "step_ms_median": harness.percentile(intervals_ms, 50),
                   "step_ms_max": max(intervals_ms),
                   "first_loss": warm[0], "last_loss": rec["losses"][-1]},
    }

    if args.trace:
        ctx = {"cell": cell, "kind": devices[0].device_kind,
               "platform": devices[0].platform, "items_per_s_chip": per_chip,
               "spans": rec["spans"]}
        state, result["metrics"], busy, result["breakdown"] = traced_layers(
            cell, bundle, state, ctx)
    else:
        busy = {}
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}

    faulthandler.cancel_dump_traceback_later()
    result["device"] = {**harness.device_record(devices), **busy}
    if cell.rehearse:
        result["rehearsal"] = True     # CPU numbers: control flow only
    del state
    mpi.stop()
    return result
