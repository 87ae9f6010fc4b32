"""Device milliseconds of one of the served path's compiled programs, from
the traced stretch: the executions on the chip's "XLA Modules" line whose
name matches ``module`` (``jit__slot_step_jit``, the pooled decode step;
``jit__slot_prefill_jit``, a prefill), over a count the loop took in the
same stretch (``per``: ``steps``, the decode steps; ``prefill_padded_ktok``,
the thousands of prompt tokens prefilled as the engine counts them, bucket
padding included: a prefill's time follows its bucket, not its prompt).  A program's execution spans all its
operations, the copies that carry no ``tf_op`` too.  No match, no number."""

import re

from chipbench import harness


def device_s(ctx, module):
    rx = re.compile(module)
    modules = ctx.get("modules") or {}
    runs = [e for es in modules.values() for e in es if rx.search(e.name)]
    if not runs:
        return None
    seconds = sum(e.end - e.start for e in runs) / 1e9 / len(modules)
    harness.log(f"{module!r}: {len(runs)} executions, {seconds:.4f} s")
    return seconds


def read(ctx, module, per):
    count = (ctx.get("traced") or {}).get(per)
    seconds = device_s(ctx, module) if count else None
    return 1e3 * seconds / count if seconds else None
