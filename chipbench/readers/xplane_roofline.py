"""``readers/xplane_ops.py`` for kernels found by their identity and counted
by a module of their own: the device seconds a step of the events whose
name matches ``pattern`` (a regular expression on the instruction's text)
or carries the Pallas identity ``"tm_kernel":"<kernel>"``, against
``max(flops / peak, bytes / bandwidth)`` of the operations and bytes that
``chipbench/<module>.py``'s ``flops_fn`` and ``bytes_fn`` count from the
cell's shapes.  ``counted`` names a counter of the cell's step file
(``readers/step_counts.py``): its mean over the traced steps, one value a
layer, joins the shapes under that name, so that work which follows the
data is counted as it was and not as it was expected.  No match, no
number."""

import importlib
import re

from chipbench import flops, harness


def read(ctx, module, flops_fn, bytes_fn, pattern=None, kernel=None,
         counted=None):
    ops = harness.load_module(ctx["cell"].manifest, "readers", "xplane_ops")
    scopes = harness.load_module(ctx["cell"].manifest, "readers",
                                 "xplane_scopes")
    trace = ctx["trace"]
    rx = re.compile(pattern if pattern else scopes.KERNEL % kernel)
    seconds = sum((e.end - e.start) / 1e9 for es in trace.devices.values()
                  for e in es if rx.search(e.name))
    if not seconds or not ctx["traced_steps"]:
        return None
    per_step = seconds / ctx["traced_steps"] / len(trace.devices)
    peak = flops.peak_for(ctx["kind"])
    counts = importlib.import_module(f"chipbench.{module}")
    sizes = ops.shapes(ctx["cell"])
    if counted:
        sizes[counted] = harness.load_module(
            ctx["cell"].manifest, "readers", "step_counts").per_step(
                ctx, counted)
        if not sizes[counted]:
            return None
    need_f = getattr(counts, flops_fn)(**sizes)
    need_b = getattr(counts, bytes_fn)(**sizes)
    t_f = need_f / peak["bf16_flops_per_s"]
    t_b = need_b / peak["hbm_bytes_per_s"]
    harness.log(f"{rx.pattern!r}: {1e3 * per_step:.3f} ms a step; needs "
                f"{need_f / 1e12:.3f} TFLOP ({1e3 * t_f:.3f} ms) and "
                f"{need_b / 1e9:.3f} GB ({1e3 * t_b:.3f} ms): bound_by "
                f"{'compute' if t_f >= t_b else 'memory'}")
    return 100.0 * max(t_f, t_b) / per_step
