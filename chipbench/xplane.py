"""From a profiler trace (``.xplane.pb``) to events, and from events to
numbers.  Two halves, tested apart:

- ``load(path)``: file -> ``Trace`` (device operation events per device,
  host annotation spans), with nothing but ``jax.profiler.ProfileData``;
- pure functions on events: ``union_s``, ``sum_by_name``, ``exposed_s``,
  ``device_busy``, ``breakdown``.

What a v5e trace holds (looked at by hand, PR 23): one plane a chip,
``/device:TPU:<n>``, with the lines "Steps", "XLA Modules", "XLA Ops" (one
event per executed HLO instruction, back to back; the event's NAME is the
instruction's whole HLO text, ``%fusion.14 = (f32[256]{...}, ...) fusion(...)``)
and "Async XLA Ops" (one event from each ``*-start`` to its ``*-done``:
copies, slices and collectives in flight beside the ops line); the host's
threads on ``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans land
on the same clock.

An event is ``(name, start_ns, end_ns, stats)``.  The traced window runs
from the end of the host's ``wait_prime`` span (the pipeline is primed: one
step done, one in flight) to the end of the last ``wait_loss`` span;
``make_trace`` cuts every device event to it, once.
"""

from __future__ import annotations

import collections
import glob
import gzip
import os
import re

Event = collections.namedtuple("Event", "name start end stats")
Trace = collections.namedtuple("Trace", "devices host window in_flight",
                               defaults=({},))
# devices: {plane name: [Event]} of the "XLA Ops" lines; host: {span name:
# [(start, end)]} in ns; window: (start_ns, end_ns); in_flight: {plane
# name: [Event]} of the "Async XLA Ops" lines

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
HOST_SPANS = ("dispatch", "wait_prime", "wait_loss", "wait_drain")


def newest(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path):
    """File (``.xplane.pb``, or gzipped ``.xplane.pb.gz``) -> Trace."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, in_flight, host = {}, {}, collections.defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                into = {OPS_LINE: devices, ASYNC_LINE: in_flight}.get(
                    line.name)
                if into is not None:
                    into[plane.name] = [
                        Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                              dict(e.stats))
                        for e in line.events]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return make_trace(devices, dict(host), in_flight)


def make_trace(devices, host, in_flight=None):
    """Events and host spans -> Trace, the events cut to the window."""
    window = trace_window(devices, host)
    return Trace({d: clip(es, window) for d, es in devices.items()}, host,
                 window, {d: clip(es, window)
                          for d, es in (in_flight or {}).items()})


def trace_window(devices, host):
    """[end of wait_prime, end of the last wait_loss]; without host spans,
    the extent of the device events."""
    if host.get("wait_prime") and host.get("wait_loss"):
        return (max(e for _, e in host["wait_prime"]),
                max(e for _, e in host["wait_loss"]))
    evs = [e for es in devices.values() for e in es]
    if not evs:
        return (0, 0)
    return (min(e.start for e in evs), max(e.end for e in evs))


def clip(events, window):
    """Events cut to the window; those outside it dropped."""
    lo, hi = window
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t, e.stats))
    return out


def union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    merged = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def union_s(events):
    """Seconds covered by at least one event."""
    return sum(t - s for s, t in union((e.start, e.end)
                                       for e in events)) / 1e9


def sum_by_name(events):
    """{event name: summed seconds}."""
    total = collections.Counter()
    for e in events:
        total[e.name] += (e.end - e.start) / 1e9
    return dict(total)


def exposed_s(events, others):
    """Seconds of ``events`` during which none of ``others`` runs."""
    cover = union((e.start, e.end) for e in others)
    out, i = 0, 0
    for s, t in union((e.start, e.end) for e in events):
        hidden = 0
        while i < len(cover) and cover[i][1] <= s:
            i += 1
        j = i
        while j < len(cover) and cover[j][0] < t:
            hidden += min(t, cover[j][1]) - max(s, cover[j][0])
            j += 1
        out += (t - s) - hidden
    return out / 1e9


def gaps(events, window):
    """Idle ``(start, end)`` stretches of one device inside the window."""
    out, at = [], window[0]
    for s, t in union((e.start, e.end) for e in events):
        if s > at:
            out.append((at, s))
        at = max(at, t)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def host_label(host, start, end):
    """What the host was doing for most of ``[start, end]``."""
    best, best_ns = "other", 0
    for name, spans in host.items():
        ns = sum(max(0, min(end, t) - max(start, s)) for s, t in spans)
        if ns > best_ns:
            best, best_ns = name, ns
    return best


def short(name, width=100):
    """An HLO instruction's text cut to a label: its name, opcode and
    shapes, without layouts."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def device_busy(trace):
    """``busy_s`` (averaged over the devices) and ``window_s``."""
    window_s = (trace.window[1] - trace.window[0]) / 1e9
    busy = [union_s(es) for es in trace.devices.values()]
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "window_s": window_s}


def stem(name):
    """``%convert_reduce_fusion.38 = ...`` -> ``%convert_reduce_fusion``:
    the instruction's name without its number, which is how XLA names the
    instructions of one kind."""
    head = name.split(" = ", 1)[0]
    base, _, number = head.rpartition(".")
    return base if base and number.isdigit() else head


def breakdown(trace, top=10):
    """Where the device's time went (seconds summed over devices): the
    ``top // 2`` kinds of instruction that took most, each as
    ``<stem>.* (<n> instructions)``, then the single instructions that
    took most; and the longest idle gaps by what the host was doing."""
    ops, kinds, members = (collections.Counter(), collections.Counter(),
                           collections.defaultdict(set))
    idle = []
    for es in trace.devices.values():
        ops.update(sum_by_name(es))
        idle += [(host_label(trace.host, s, t), (t - s) / 1e9)
                 for s, t in gaps(es, trace.window)]
    for name, seconds in ops.items():
        kinds[stem(name)] += seconds
        members[stem(name)].add(name)
    by_kind = [[f"{k}.* ({len(members[k])} instructions)", s]
               for k, s in kinds.most_common(top // 2)]
    single = [[short(n), s] for n, s in ops.most_common(top - len(by_kind))]
    return {"device_ops": by_kind + single,
            "idle_gaps": [[n, s] for n, s in
                          sorted(idle, key=lambda g: -g[1])[:top]]}
