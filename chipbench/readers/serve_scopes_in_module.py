"""``readers/xplane_scopes_named.py`` for ONE of the served path's compiled
programs: device milliseconds a decode step of the events that ran INSIDE an
execution of the program whose name matches ``module`` (the chip's "XLA
Modules" line of the traced stretch: ``jit__slot_step_jit``, the pooled
decode step) and whose operation's ``tf_op`` matches ``op_name`` OR whose
name matches ``name``, less those whose name matches ``not_name``; over the
decode steps the loop took in the same stretch.  A prefill runs the same
scopes in another program: its events are left out.  No execution or no
match (a program without the scopes), no number.
"""

import bisect
import re

from chipbench import harness


def read(ctx, module, op_name=None, name=None, not_name=None):
    trace = ctx["trace"]
    steps = (ctx.get("traced") or {}).get("steps")
    if not trace.devices or not steps:
        return None
    scopes = harness.load_module(ctx["cell"].manifest, "readers",
                                 "xplane_scopes")
    meta = scopes._meta(scopes.raw_trace(ctx))
    program, by_op, by_name, never = (
        re.compile(p) if p else None
        for p in (module, op_name, name, not_name))
    inside = 0
    for plane, events in trace.devices.items():
        runs = sorted((e.start, e.end)
                      for e in (ctx.get("modules") or {}).get(plane, ())
                      if program.search(e.name))
        starts = [s for s, _ in runs]
        ops = meta.get(plane, {})
        verdict = {}
        for e in events:
            at = bisect.bisect_right(starts, e.start) - 1
            if at < 0 or e.start >= runs[at][1]:
                continue
            if e.name not in verdict:
                tf_op = ops.get(e.name, {}).get("tf_op") or ""
                verdict[e.name] = bool(
                    not (never and never.search(e.name))
                    and ((by_op and by_op.search(tf_op))
                         or (by_name and by_name.search(e.name))))
            inside += (e.end - e.start) * verdict[e.name]
    per_step = inside / 1e6 / steps / len(trace.devices)
    harness.log(f"inside {module!r}: op_name {op_name!r} or name {name!r}, "
                f"not {not_name!r}: {per_step:.3f} ms a decode step")
    return per_step or None
