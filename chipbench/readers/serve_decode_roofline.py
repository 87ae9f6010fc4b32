"""The pooled decode step's share of its roofline.  The step is bound by
bandwidth (one token a slot: two operations a weight byte): the bytes it
REQUIRES (``flops_serve.decode_step_bytes``: the bfloat16 weights once and
the live tokens' keys and values at the file's ``cache_dtype``, each slot's
context capped by the window; not the reserved slots) over the chip's HBM
bandwidth, over the device time a step takes in the traced stretch
(``readers/serve_module_ms``).  It counts the same work whatever implements
the step."""

import importlib

from chipbench import flops, harness

WIDTH = {"float32": 4, "bfloat16": 2, "float16": 2, "float8_e4m3fn": 1,
         "int8": 1}


def read(ctx, module):
    traced = ctx.get("traced") or {}
    if not traced.get("steps") or ctx["platform"] == "cpu":
        return None
    per_step = harness.load_module(
        ctx["cell"].manifest, "readers", "serve_module_ms").read(
            ctx, module, "steps")
    if not per_step:
        return None
    cfg = ctx["cell"].config
    f = cfg["flops"]
    counts = importlib.import_module(f"chipbench.{f['module']}")
    need = counts.decode_step_bytes(
        traced["live_tokens_per_step"],
        weight_bytes=WIDTH[cfg["weights_dtype"]],
        cache_bytes=WIDTH[cfg["cache_dtype"]],
        **{k: cfg[k] for k in f["sizes"]})
    least_ms = 1e3 * need / flops.peak_for(ctx["kind"])["hbm_bytes_per_s"]
    harness.log(f"decode step: {per_step:.3f} ms on the device; needs "
                f"{need / 1e9:.3f} GB ({least_ms:.3f} ms): bound_by memory")
    return 100.0 * least_ms / per_step
