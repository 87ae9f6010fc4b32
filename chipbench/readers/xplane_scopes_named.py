"""``readers/xplane_scopes.py`` where the compiler's fusions move a
kernel's label: device milliseconds a step and chip of the events whose
operation's ``tf_op`` matches ``op_name`` OR whose name (on a TPU the
instruction's HLO text) matches ``name``, less those whose name matches
``not_name``.

The TPU compiler turns ``lax.ragged_dot`` into a grouped-matmul kernel
named ``%ragged-dot-none.<n>`` (with a small ``%ragged-dot-metadata``
beside it) and fuses the elementwise operation after it INTO the kernel,
whose ``op_name`` is then that operation's: the product under ``experts``
that feeds ``combine``'s select reads ``.../combine/jit(_where)/select_n``,
and the weight-gradient products read ``jit(wrapped)/add``, the optimizer's
update.  So the grouped products are found by name and the scopes give up
what is named so.  No match, no number.
"""

import re

from chipbench import harness


def read(ctx, op_name=None, name=None, not_name=None):
    trace = ctx["trace"]
    if not trace.devices or not ctx["traced_steps"]:
        return None
    scopes = harness.load_module(ctx["cell"].manifest, "readers",
                                 "xplane_scopes")
    meta = scopes._meta(scopes.raw_trace(ctx))
    by_op, by_name, never = (re.compile(p) if p else None
                             for p in (op_name, name, not_name))
    inside = 0
    for plane, events in trace.devices.items():
        ops = meta.get(plane, {})
        verdict = {}
        for e in events:
            if e.name not in verdict:
                tf_op = ops.get(e.name, {}).get("tf_op") or ""
                verdict[e.name] = bool(
                    not (never and never.search(e.name))
                    and ((by_op and by_op.search(tf_op))
                         or (by_name and by_name.search(e.name))))
            inside += (e.end - e.start) * verdict[e.name]
    per_step = inside / 1e6 / ctx["traced_steps"] / len(trace.devices)
    harness.log(f"op_name {op_name!r} or name {name!r}, not {not_name!r}: "
                f"{per_step:.3f} ms a step")
    return per_step or None
