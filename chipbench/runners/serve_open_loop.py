"""The served-model runner: one cell, one process, one open-loop window on
the wall clock through ``serving.Server``.

Set-up (all of it ``setup_s``): the model through the configuration's
``builder``, its weights drawn by the benchmark from the seed in the served
type (``lm_weights``), ONE ``Server`` with one replica on the chip (greedy,
no eos, prefix cache and speculation off), every executable the run's
schedule reaches compiled by serving one request of each of its prompt
buckets (and no others), then the
warm-up stretch of the cell's own traffic, which runs straight into the
window so that it opens on a pool that is already in use.

The window: ``open_loop.serve``.  The schedule (due time, prompt ids, answer
length of every request) is made from the seed before anything runs; the
program receives only requests.  After the window the loop runs on (the
traced stretch with ``--trace 1``, then the drain the traffic file asks
for).  Then ``memory_peak_bytes`` is read, the server is freed, and the
check that decides ``correct`` runs (``served_check``; its time is in no
metric).
"""

from __future__ import annotations

import faulthandler
import gc
import math
import os
import shutil
import sys
import time
import types

from chipbench import harness, lm_weights, open_loop, served_check

TRACE_S = 4.0             # the traced stretch that follows the window: long
#                           enough for ten to twenty prefills at the cells'
#                           rates (a decode step is some thousands of
#                           device events, so not longer)
# where a reader of the raw profile looks (``readers/xplane_scopes``)
TRACE_DIR = os.path.join(harness.ROOT, ".chipbench_trace")
CHECKED_REQUESTS = 36     # the sample the reference goes through: some
#                           hundreds of served tokens where an answer is 27
FAILED_SHARE_LIMIT = 0.01
clock = time.monotonic


def train_runner(cell):
    """``CompileCounter`` and ``deadline`` are the train runner's: one rule
    for each."""
    return harness.load_module(cell.manifest, "runners", "train_step")


def bucket_of(length, srv):
    """The prefill bucket a prompt runs in: the engine's rule (a power of
    two, at least ``prefill_bucket``, at most the slot).  A copy, used only
    to choose what to warm up: were it wrong, the window would compile and
    the run come out not correct."""
    return min(max(srv["prefill_bucket"],
                   1 << max(0, length - 1).bit_length()), srv["slot_tokens"])


def warm_lengths(lengths, srv):
    """The longest prompt of each prefill bucket that ``lengths`` reach:
    the shapes this run's traffic uses and no others."""
    longest = {}
    for n in lengths:
        b = bucket_of(int(n), srv)
        longest[b] = max(longest.get(b, 0), int(n))
    return sorted(longest.values())


def build_server(cell, model, params):
    from torchmpi_tpu import serving

    srv = cell.config["serving"]
    server = serving.Server(
        model, params, replicas=1, slots=srv["slots"],
        slot_tokens=srv["slot_tokens"], prefill_bucket=srv["prefill_bucket"],
        sample=0.0, spec_k=0, prefix_cache=0, slo_ttft_us=0, autoscale=0)
    return serving, server


def compile_everything(serving, server, cell, seed, lengths):
    """Serve one short request of each bucket the traffic reaches,
    together, so that every such prefill, the pooled decode step and the
    slot write are compiled (or loaded from the cache) before the clock
    starts."""
    import numpy as np

    rng = open_loop.rng_for(seed, 9)
    reqs = [serving.Request(
        rid=f"compile{n}", max_new=2, eos_id=None, arrival_s=0.0,
        prompt=rng.integers(0, cell.config["vocab_size"], size=n,
                            dtype=np.int32))
        for n in warm_lengths(lengths, cell.config["serving"])]
    done = server.run_trace(reqs)
    if len(done) != len(reqs) or any(r.error for r in done):
        raise RuntimeError(f"warm-up requests failed: "
                           f"{[r.error for r in done if r.error]}")
    return len(reqs)


def setup(cell, seed, lengths):
    """The model, the benchmark's weights, ONE server and every executable
    that prompts of ``lengths`` reach."""
    import jax

    tr_mod = train_runner(cell)
    tr_mod.deadline(tr_mod.SETUP_DEADLINE_S)
    cfg = cell.config
    model = harness.build_model(cell)
    params = lm_weights.make(model, harness.seed_key(seed),
                             getattr(jax.numpy, cfg["weights_dtype"]))
    jax.block_until_ready(params)
    harness.log(f"weights after {time.perf_counter() - harness.T0:.1f} s")
    serving, server = build_server(cell, model, params)
    buckets = compile_everything(serving, server, cell, seed, lengths)
    harness.log(f"{buckets} buckets and the decode step ready after "
                f"{time.perf_counter() - harness.T0:.1f} s")
    return types.SimpleNamespace(params=params, serving=serving,
                                 server=server,
                                 engine=server.router.live()[0])


def window(cell, ready, schedule, seconds, *, trace=False, compiles=None):
    """The warm-up stretch, the window, (traced) the stretch under the
    profiler, the drain: -> the loop's records and the window's numbers.
    ``compiles`` is armed for the window alone."""
    import jax.profiler

    tr_mod = train_runner(cell)
    cfg, traffic, srv = cell.config, cell.traffic, cell.config["serving"]
    extra = TRACE_S if trace else 0.0
    engine = ready.engine
    base_stats = dict(engine.stats)
    trace_dir = os.path.join(TRACE_DIR, cell.name)
    traced, took = {}, {}

    def open_window():
        if compiles is not None:
            compiles.armed = True
        took["setup_s"] = time.perf_counter() - harness.T0

    def close_window():
        if compiles is not None:
            compiles.armed = False
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            traced["from"] = clock() - t0
            traced["padded_from"] = engine.stats["prefill_tokens"]

    def stop_trace():
        traced["to"] = clock() - t0
        traced["padded_to"] = engine.stats["prefill_tokens"]
        jax.profiler.stop_trace()

    tr_mod.deadline(seconds + extra + traffic["drain_s"]
                    + traffic["warmup_s"] + 200)
    t0 = clock() + traffic["warmup_s"]
    loop = open_loop.serve(
        ready.server, ready.serving, schedule, until=seconds + extra,
        drain_s=traffic["drain_s"], t0=t0, window=cfg["sliding_window"],
        marks=[(0.0, open_window), (seconds, close_window)]
        + ([(seconds + extra, stop_trace)] if trace else []),
        annotate=jax.profiler.TraceAnnotation if trace else None)
    stats = open_loop.reduce(
        loop.records, seconds=seconds, count=traffic["count"],
        spans=loop.spans, steps=loop.steps, slots=srv["slots"],
        slot_tokens=srv["slot_tokens"])
    counters = {k: engine.stats[k] - base_stats[k] for k in engine.stats}
    harness.log(f"window: {stats['attempted']} attempted, "
                f"{stats['failed']} failed, {stats['out_tokens']} tokens; "
                f"the run's counters {counters}; loop ended "
                f"{loop.ended:.1f} s after the window opened")
    return types.SimpleNamespace(
        loop=loop, records=loop.records, stats=stats, counters=counters,
        setup_s=took["setup_s"], seconds=seconds, trace_dir=trace_dir,
        traced=traced)


def served(cell, seed, seconds, *, trace=False, compiles=None):
    """Set-up and the loop: everything up to the moment the window's
    records exist and ``memory_peak_bytes`` has been read."""
    import jax

    devices = jax.devices()
    seconds = float(seconds)
    schedule = open_loop.schedule(
        cell.traffic, seed, seconds=seconds, vocab=cell.config["vocab_size"],
        extra_s=TRACE_S if trace else 0.0)
    ready = setup(cell, seed, [r.prompt.size for r in schedule])
    s = window(cell, ready, schedule, seconds, trace=trace,
               compiles=compiles)
    s.device = harness.device_record(devices)   # before the reference runs
    s.params = ready.params
    del ready                   # the pool cache goes; the weights stay
    gc.collect()
    return s


def judge(cell, seed, s, compile_events=()):
    """The comparison that decides ``correct``: -> (the reference's record,
    each number compared beside its limit, the verdict)."""
    cfg = cell.config
    picked = served_check.sample(s.records, seed, CHECKED_REQUESTS)
    checked = served_check.gaps(cell, s.params, picked)
    every = [t for r in s.records.values() for t in r.tokens]
    compared = {
        "logit_gap": [checked["widest_gap"], cfg["tolerance"]["logit_gap"]],
        "failed_share": [s.stats["failed"] / max(1, s.stats["attempted"]),
                         FAILED_SHARE_LIMIT],
        "compiles_in_window": [len(compile_events), 0],
        "ids_outside_vocab": [
            sum(not 0 <= t < cfg["vocab_size"] for t in every), 0],
        "answers_not_max_new": [s.stats["short_answers"], 0],
        "requests_checked": [len(picked), 1],
    }
    correct = (compared["logit_gap"][0] <= compared["logit_gap"][1]
               and compared["failed_share"][0] < FAILED_SHARE_LIMIT
               and len(picked) >= 1
               and all(compared[k][0] == 0 for k in (
                   "compiles_in_window", "ids_outside_vocab",
                   "answers_not_max_new")))
    return checked, compared, bool(correct)


def layer_metrics(cell, s, devices):
    """The traced stretch's profile and every per-layer reader of the
    cell; a reader that finds nothing to read returns None and its metric
    is left out."""
    from chipbench import xplane, xplane_serve

    trace, modules = xplane_serve.load(xplane.newest(s.trace_dir))
    lo, hi = s.traced["from"], s.traced["to"]
    steps = s.loop.steps
    at = [i for i, t in enumerate(steps["at"]) if lo <= t < hi]
    ctx = {"cell": cell, "kind": devices[0].device_kind,
           "platform": devices[0].platform, "serve": s.stats,
           "records": s.records, "seconds": s.seconds, "trace": trace,
           "traced_steps": len(at), "spans": s.loop.spans,
           "modules": modules,
           "traced": {
               "steps": len(at), "seconds": hi - lo,
               "live_tokens_per_step": (sum(steps["attended"][i]
                                            for i in at) / max(1, len(at))),
               "prefills": sum(
                   r.admit is not None and lo <= r.admit < hi
                   for r in s.records.values()),
               # as the engine counts them: bucket padding included
               "prefill_padded_ktok": (s.traced["padded_to"]
                                       - s.traced["padded_from"]) / 1e3}}
    layer = {}
    for m in cell.per_layer:
        reader = harness.load_module(cell.manifest, "readers", m["reader"])
        value = reader.read(ctx, **m["args"])
        if value is not None:
            layer[m["name"]] = {"value": value, "unit": m["unit"]}
    return (layer, xplane.breakdown(trace), xplane.device_busy(trace),
            ctx["traced"])


def run(cell, args):
    import jax

    from torchmpi_tpu.utils import compilecache

    tr_mod = train_runner(cell)
    devices = jax.devices()
    harness.log(f"{cell.name}: {devices[0].platform} "
                f"{devices[0].device_kind} x{len(devices)}; compile cache "
                f"at {compilecache.enable_persistent_cache()}")
    compiles = tr_mod.CompileCounter()
    s = served(cell, args.seed, args.seconds, trace=bool(args.trace),
               compiles=compiles)
    tr_mod.deadline(300)
    t_chk = clock()
    checked, compared, correct = judge(cell, args.seed, s, compiles.events)
    stats = s.stats
    # an end-to-end metric is one of the window's numbers by its own name
    # (``itl_ms_p99``, ...), a rate a chip, or the set-up
    e2e = {**stats, "setup_s": s.setup_s,
           "out_tokens_per_s_chip": stats["out_tokens_per_s"] / len(devices)}
    result = {
        "correct": correct,
        "attempted": stats["attempted"], "failed": stats["failed"],
        "window": {k: v for k, v in stats.items()
                   if v is None or abs(v) != math.inf},
        "checks": {"reference": checked, "check_s": clock() - t_chk,
                   "compiles_in_window": compiles.events,
                   "counters": s.counters},
    }
    device = s.device
    if args.trace:
        (result["metrics"], result["breakdown"], busy,
         result["checks"]["traced"]) = layer_metrics(cell, s, devices)
        device = {**device, **busy}
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    faulthandler.cancel_dump_traceback_later()
    result["device"] = device
    if cell.rehearse:
        result["rehearsal"] = True     # CPU numbers: control flow only
    result["compared"] = compared      # last: each number beside its limit
    for name, (value, lim) in compared.items():
        print(f"compared {name}: {value} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    return result
