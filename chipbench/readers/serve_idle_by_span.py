"""The device's idle share of the traced stretch, split by the phase the
HOST was in, as the program names its own phases (``tm.serve.*``:
``torchmpi_tpu/serving/engine.SPANS``, each a
``jax.profiler.TraceAnnotation`` on the ``/host:CPU`` plane, on the device
events' clock).

Every idle nanosecond of a chip (``xplane.gaps`` of the events
``xplane_serve.load`` cut to the window) goes to the INNERMOST program span
that covers it: the one that started last among those open at that instant,
on the thread that wrote ``tm.serve.tick``.  A nanosecond under no such
span goes to ``outside`` (the benchmark's own loop: stamps, the feeder's
poll, the wait for an arrival).  The value is the share of the window, in
percent and averaged over the chips, of the idle time whose innermost
span's name matches ``span`` (a regular expression; ``^outside$`` for the
rest): the names are a partition, so the shares of rules that match each
name once add up to ``device_idle_pct`` of the same run.

No device plane (a rehearsal on the CPU) or no ``tm.serve.*`` span in the
profile (a program without them: the parent): no number, and no error.  The
whole split is logged once a run, with each span's count and mean.
"""

import bisect
import collections
import gzip
import re

from chipbench import harness, xplane

PREFIX = "tm.serve."
TICK = "tm.serve.tick"
OUTSIDE = "outside"


def program_spans(path, host_plane):
    """``[(start_ns, end_ns, name)]`` of the ``tm.serve.*`` events on the
    host threads that wrote ``tm.serve.tick``."""
    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    out = []
    for plane in data.planes:
        if plane.name != host_plane:
            continue
        for line in plane.lines:
            mine = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith(PREFIX)]
            if any(name == TICK for _, _, name in mine):
                out += mine
    return sorted(out)


def innermost(spans):
    """Spans, nested or not -> disjoint ``[(start, end, name)]`` in order:
    every covered instant under the span that started last among those
    open there."""
    edges = sorted([(t, 0, i) for i, (_, t, _) in enumerate(spans)]
                   + [(s, 1, i) for i, (s, _, _) in enumerate(spans)])
    out, open_, at = [], set(), None
    for point, opens, i in edges:       # at one instant, ends come first
        if open_ and point > at:
            top = max(open_, key=lambda k: (spans[k][0], k))
            name = spans[top][2]
            if out and out[-1][2] == name and out[-1][1] == at:
                out[-1] = (out[-1][0], point, name)
            else:
                out.append((at, point, name))
        at = point
        (open_.add if opens else open_.discard)(i)
    return out


def idle_ns_by_span(devices, window, spans):
    """``{name: idle ns, summed over the chips}``, ``outside`` among the
    names: a partition of the chips' idle time inside the window."""
    cover = innermost(spans)
    starts = [s for s, _, _ in cover]
    idle = collections.Counter()
    for events in devices.values():
        for lo, hi in xplane.gaps(events, window):
            left = hi - lo
            i = max(0, bisect.bisect_right(starts, lo) - 1)
            while i < len(cover) and cover[i][0] < hi:
                s, t, name = cover[i]
                ns = min(hi, t) - max(lo, s)
                if ns > 0:
                    idle[name] += ns
                    left -= ns
                i += 1
            idle[OUTSIDE] += left
    return dict(idle)


def split(trace, spans):
    """``{name: % of the window idle under it}`` and the table that is
    logged: a row a name with the spans inside the window, their mean and
    the idle share."""
    lo, hi = trace.window
    scale = 100.0 / ((hi - lo) * len(trace.devices))
    shares = {name: ns * scale for name, ns in
              idle_ns_by_span(trace.devices, trace.window, spans).items()}
    inside = collections.defaultdict(list)
    for s, t, name in spans:
        if s >= lo and t <= hi:
            inside[name].append(t - s)
    rows = [{"span": name, "n": len(inside[name]),
             "mean_ms": (1e-6 * sum(inside[name]) / len(inside[name])
                         if inside[name] else None),
             "idle_pct": shares.get(name, 0.0)}
            for name in sorted(set(shares) | set(inside))]
    return shares, rows


KEY = "idle_by_span"        # where a run's ctx keeps its split


def shares_of(ctx):
    """The split of this run's trace, made (and logged) once a run: the
    twelve metrics of a cell read it from the run's own ``ctx``."""
    trace = ctx["trace"]
    if not trace.devices or trace.window[1] <= trace.window[0]:
        return None
    if KEY not in ctx:
        manifest = ctx["cell"].manifest
        spans = program_spans(
            harness.load_module(manifest, "readers",
                                "xplane_scopes").raw_trace(ctx),
            harness.load_module(manifest, "readers",
                                "program_span").HOST_PLANE)
        ctx[KEY] = None
        if spans:
            ctx[KEY], rows = split(trace, spans)
            harness.log("idle by program span (% of the window), "
                        f"{sum(ctx[KEY].values()):.3f} in all:")
            for row in rows:
                harness.log(f"  idle_by_span {row}")
    return ctx[KEY]


def read(ctx, span):
    shares = shares_of(ctx)
    if shares is None:
        return None
    rx = re.compile(span)
    return sum((v for name, v in shares.items() if rx.search(name)), 0.0)
