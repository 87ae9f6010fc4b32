"""A kernel's share of its roofline, from the device trace.

The kernel's events are those on the devices' "XLA Ops" lines whose name,
or whose value of any stat in ``stats``, matches ``pattern`` (a regular
expression; the rule that finds a kernel lives in that metric's own file
under ``layer_metrics/``).  Their device seconds per step and chip are set
against the least time the chip could take for the operations and bytes
``flops.py`` counts for one step from the cell's shapes:
``max(flops / peak_flops, bytes / peak_bw)``.  ``bound_by`` in the
harness's log line says which applies.  No match, no number.
"""

import re

from chipbench import flops, harness

STATS = ("tf_op", "name", "long_name", "hlo_op", "deduplicated_name")


def matches(event, rx, stats=STATS):
    return bool(rx.search(event.name) or any(
        rx.search(str(event.stats[k])) for k in stats if k in event.stats))


def shapes(cell):
    """What one chip's step hands the kernels."""
    cfg, tr = cell.config, cell.traffic
    batch, seq = tr["batch_per_chip"], tr["seq"]
    return dict(cfg, batch=batch, seq=seq, rows=batch * (seq - 1),
                layers=cfg["num_hidden_layers"])


def read(ctx, pattern, flops_fn, bytes_fn):
    trace = ctx["trace"]
    rx = re.compile(pattern)
    seconds = sum(
        (e.end - e.start) / 1e9
        for es in trace.devices.values()
        for e in es if matches(e, rx))
    if not seconds or not ctx["traced_steps"]:
        return None
    per_step = seconds / ctx["traced_steps"] / len(trace.devices)
    peak = flops.peak_for(ctx["kind"])
    sizes = shapes(ctx["cell"])
    need_f = getattr(flops, flops_fn)(**sizes)
    need_b = getattr(flops, bytes_fn)(**sizes)
    t_f = need_f / peak["bf16_flops_per_s"]
    t_b = need_b / peak["hbm_bytes_per_s"]
    harness.log(f"{pattern!r}: {1e3 * per_step:.3f} ms a step; needs "
                f"{need_f / 1e12:.3f} TFLOP ({1e3 * t_f:.3f} ms) and "
                f"{need_b / 1e9:.3f} GB ({1e3 * t_b:.3f} ms): bound_by "
                f"{'compute' if t_f >= t_b else 'memory'}")
    return 100.0 * max(t_f, t_b) / per_step
