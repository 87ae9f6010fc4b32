"""``xplane.load`` for a served window: the same device lines, the serving
loop's host spans (``open_loop.serve``: ``tick`` with ``admit`` and ``step``
inside it, ``stamp``, ``wait_arrival``), and the window they span.  The
reductions are ``xplane``'s own.  ``load`` also returns the "XLA Modules" line
of each chip: one event an execution of a compiled program, named
``jit_<function>(<fingerprint>)``, which is how the device time of the pooled
decode step or of a prefill is found (half of a step's operations, the
copies of the pool cache, carry no ``tf_op``)."""

import collections

from chipbench import xplane

LOOP_SPANS = ("tick", "wait_arrival")           # what the window is made of
MODULES_LINE = "XLA Modules"
LABELS = ("admit", "step", "stamp", "wait_arrival")   # what a gap is named by


def load(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, in_flight, modules = {}, {}, {}
    host = collections.defaultdict(list)
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith(xplane.DEVICE_PLANE):
                into = {xplane.OPS_LINE: devices, MODULES_LINE: modules,
                        xplane.ASYNC_LINE: in_flight}.get(line.name)
                if into is not None:
                    into[plane.name] = [
                        xplane.Event(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     dict(e.stats))
                        for e in line.events]
            else:
                for e in line.events:
                    if e.name in LOOP_SPANS + LABELS:
                        host[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    trace = make_trace(devices, dict(host), in_flight)
    return trace, {d: xplane.clip(es, trace.window)
                   for d, es in modules.items()}


def make_trace(devices, host, in_flight=None):
    """The window runs from the first to the last of the loop's own spans
    (without any, the extent of the device events); the host spans kept
    are the labels."""
    loop = [s for name in LOOP_SPANS for s in host.get(name, ())]
    if loop:
        window = (min(s for s, _ in loop), max(e for _, e in loop))
    else:
        window = xplane.trace_window(devices, {})
    return xplane.Trace(
        {d: xplane.clip(es, window) for d, es in devices.items()},
        {k: v for k, v in host.items() if k in LABELS}, window,
        {d: xplane.clip(es, window)
         for d, es in (in_flight or {}).items()})
