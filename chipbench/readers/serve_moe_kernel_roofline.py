"""The share of its roofline that the pooled decode step's expert kernel
reaches: the device time a decode step of the events inside the step's
executions that carry the Pallas identity ``"tm_kernel":"<kernel>"``
(``readers/serve_scopes_in_module``), against the bytes the LIVE rows
require of it: one routed expert's matrices (the configuration's ``flops``
module, ``matmul_params(...)["expert"]``, at the file's ``weights_dtype``)
for each held expert a layer that a live token chose (the program's own
count, ``tm_moe_experts_touched_total`` over ``tm_moe_decode_steps_total``,
as ``readers/serve_moe_decode_roofline`` reads it), over the chip's HBM
bandwidth.  The kernel reads at least those bytes (an idle slot's choices
may add an expert), so the share passes 100 only where it skips an expert
that a live row chose.  The products' operations are a few rows a weight,
far under the chip's ridge: bound by memory.  A program without the kernel,
or without the counters, gives no number."""

import importlib

from chipbench import flops, harness
from chipbench.readers.serve_decode_roofline import WIDTH
from chipbench.readers.serve_moe_decode_roofline import touched_per_step


def read(ctx, module, kernel):
    traced = ctx.get("traced") or {}
    touched = touched_per_step()
    if not traced.get("steps") or ctx["platform"] == "cpu" or not touched:
        return None
    manifest = ctx["cell"].manifest
    scopes = harness.load_module(manifest, "readers", "xplane_scopes")
    per_step = harness.load_module(
        manifest, "readers", "serve_scopes_in_module").read(
            ctx, module, name=scopes.KERNEL % kernel)
    if not per_step:
        return None
    cfg = ctx["cell"].config
    f = cfg["flops"]
    counts = importlib.import_module(f"chipbench.{f['module']}")
    expert = counts.matmul_params(**{k: cfg[k] for k in f["sizes"]})["expert"]
    need = WIDTH[cfg["weights_dtype"]] * expert * touched
    least_ms = 1e3 * need / flops.peak_for(ctx["kind"])["hbm_bytes_per_s"]
    harness.log(f"{kernel!r}: {per_step:.3f} ms a decode step; "
                f"{touched:.1f} held experts touched; needs "
                f"{need / 1e9:.3f} GB ({least_ms:.3f} ms): bound_by memory")
    return 100.0 * least_ms / per_step
