"""Device milliseconds a step spends in collective operations, per chip,
from the device trace: the union of the collectives' intervals (on the ops
line, and in flight from ``-start`` to ``-done`` on the async line), or
(``exposed``) the part of it during which no other operation runs on that
device.  A chip alone has no collectives
in its step, and the metric is then left out."""

import re

from chipbench import xplane

# the opcode of an HLO instruction's text, not an operand's name
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?\(")


def read(ctx, exposed=False, pattern=None):
    trace = ctx["trace"]
    rx = re.compile(pattern) if pattern else COLLECTIVE
    total, found = 0.0, False
    for plane, es in trace.devices.items():
        # a collective on the ops line, or in flight beside it from its
        # -start to its -done ("Async XLA Ops")
        coll = [e for e in es + trace.in_flight.get(plane, [])
                if rx.search(e.name)]
        found = found or bool(coll)
        if exposed:
            total += xplane.exposed_s(
                coll, [e for e in es if not rx.search(e.name)])
        else:
            total += xplane.union_s(coll)
    if not found or not ctx["traced_steps"]:
        return None
    return 1e3 * total / ctx["traced_steps"] / len(trace.devices)
