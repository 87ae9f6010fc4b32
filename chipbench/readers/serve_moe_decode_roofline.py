"""The pooled decode step's share of its roofline where the model has
sparse experts.  The step is bound by bandwidth; the bytes it REQUIRES
(the configuration's ``flops`` module, ``decode_step_bytes``): every weight
outside the routed experts once, a routed expert's weights for each expert
a layer that a live token CHOSE, and the live tokens' cache at the file's
``cache_dtype``; over the chip's HBM bandwidth, over the device time a step
takes in the traced stretch (``readers/serve_module_ms``).  The experts
touched a step are the program's own count (``tm_moe_experts_touched_total``
over ``tm_moe_decode_steps_total``: every decode step of the process, the
warm-up's fewer live slots included, so the share reads a little low rather
than high).  A program without the counters gives no number."""

import importlib

from chipbench import flops, harness
from chipbench.readers.serve_decode_roofline import WIDTH


def touched_per_step():
    """Experts a decode step touched, summed over the layers, or None."""
    try:
        from torchmpi_tpu import obs
    except ImportError:
        return None
    steps = obs.registry().counter_total("tm_moe_decode_steps_total")
    if not steps:
        return None
    return obs.registry().counter_total(
        "tm_moe_experts_touched_total") / steps


def read(ctx, module):
    traced = ctx.get("traced") or {}
    touched = touched_per_step()
    if not traced.get("steps") or ctx["platform"] == "cpu" or not touched:
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
        traced["live_tokens_per_step"], touched,
        weight_bytes=WIDTH[cfg["weights_dtype"]],
        cache_bytes=WIDTH[cfg["cache_dtype"]],
        **{k: cfg[k] for k in f["sizes"]})
    least_ms = 1e3 * need / flops.peak_for(ctx["kind"])["hbm_bytes_per_s"]
    harness.log(f"decode step: {per_step:.3f} ms on the device; "
                f"{touched:.1f} experts touched; needs {need / 1e9:.3f} GB "
                f"({least_ms:.3f} ms): bound_by memory")
    return 100.0 * least_ms / per_step
