"""Device milliseconds a step and chip under a scope the program's own
names define, from the device trace.

An event of the window counts when its operation's ``tf_op`` (the HLO
``op_name``: the path of JAX transforms, flax modules and primitive the
instruction was traced under, which ``xplane_meta`` reads from the file's
event metadata) matches ``op_name`` and does not match ``not_op_name``,
and, where ``kernel`` is given, when its name (on a TPU the instruction's
whole HLO text) carries the Pallas kernel identity
``"tm_kernel":"<kernel>"`` that ``torchmpi_tpu.ops`` passes as
``pallas_call(metadata=...)``.  All three are regular expressions; the
rule that finds a scope lives in that metric's own file under
``layer_metrics/``.  JAX's transform names split a step with no scope
added to the program: ``jvp(`` outside ``transpose(`` is the forward
pass, ``transpose(jvp(`` the backward pass (XLA fuses the optimizer's
update into the weight-gradient fusions, so it is in there).  No match,
no number: a rehearsal on the CPU has no device plane, and a program
without the identities has no kernel to find.
"""

import functools
import os
import re

from chipbench import harness, xplane, xplane_meta


def raw_trace(ctx):
    """The profile file the runner wrote for this cell's traced window."""
    cell = ctx["cell"]
    runner = harness.load_module(cell.manifest, "runners",
                                 cell.config["runner"])
    return xplane.newest(os.path.join(runner.TRACE_DIR, cell.name))


@functools.lru_cache(maxsize=2)
def _meta(path):
    return xplane_meta.load(path)


KERNEL = r'"tm_kernel":"(?:%s)"'


def scope_s(devices, meta, op_name=None, not_op_name=None, kernel=None):
    """Seconds (summed over devices) of the events in scope, of the events
    whose operation has a ``tf_op``, and of all events."""
    want, unwanted, ident = (
        re.compile(p) if p else None
        for p in (op_name, not_op_name, kernel and KERNEL % kernel))

    def in_scope(name, tf_op):
        return ((not want or want.search(tf_op))
                and not (unwanted and unwanted.search(tf_op))
                and (not ident or ident.search(name)))

    inside = named = total = 0
    for plane, events in devices.items():
        tf_ops = {name: stats.get("tf_op") or ""
                  for name, stats in meta.get(plane, {}).items()}
        scope = {name for name, tf_op in tf_ops.items()
                 if in_scope(name, tf_op)}
        for e in events:
            ns = e.end - e.start
            total += ns
            named += ns * bool(tf_ops.get(e.name))
            inside += ns * (e.name in scope)
    return inside / 1e9, named / 1e9, total / 1e9


def read(ctx, op_name=None, not_op_name=None, kernel=None):
    trace = ctx["trace"]
    if not trace.devices or not ctx["traced_steps"]:
        return None
    seconds, named, total = scope_s(trace.devices, _meta(raw_trace(ctx)),
                                    op_name, not_op_name, kernel)
    per_step = 1e3 * seconds / ctx["traced_steps"] / len(trace.devices)
    rule = {"kernel": kernel, "op_name": op_name, "not_op_name": not_op_name}
    harness.log(f"{ {k: v for k, v in rule.items() if v} }: "
                f"{per_step:.3f} ms a step; tf_op on "
                f"{100.0 * named / total:.2f}% of the window's device time")
    return per_step or None
