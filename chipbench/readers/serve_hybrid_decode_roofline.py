"""The pooled decode step's share of its roofline where the model is a
hybrid of state-space mixers, attention and expert layers that hold a
SHARE of their experts.  The step is bound by bandwidth; the bytes it
REQUIRES (the configuration's ``flops`` module, ``decode_step_bytes``):
every weight outside the routed experts once, a routed expert's weights for
each HELD expert a layer that a live token chose, each live slot's
recurrent state once read and once written, and the live tokens' keys and
values at the file's ``cache_dtype``; over the chip's HBM bandwidth, over
the device time a step takes in the traced stretch
(``readers/serve_module_ms``).  The held experts touched and the live slots
a step are the program's own counts (``tm_moe_experts_touched_total`` and
``tm_moe_decode_routes_total`` / k, a layer, over
``tm_moe_decode_steps_total``: every decode step of the process, the
warm-up's fewer live slots included, so the share reads a little low rather
than high).  The same work whatever implements the step.  A program without
the counters gives no number."""

import importlib

from chipbench import flops, harness
from chipbench.readers.serve_decode_roofline import WIDTH
from chipbench.readers.serve_moe_decode_roofline import touched_per_step


def live_slots_per_step(k):
    """Live slots a decode step, from the routes the step counted (``k`` a
    live slot and layer), or None."""
    try:
        from torchmpi_tpu import obs
    except ImportError:
        return None
    registry = obs.registry()
    steps = registry.counter_total("tm_moe_decode_steps_total")
    layers = sum(r["name"] == "tm_moe_decode_routes_total"
                 for r in registry.snapshot())
    if not steps or not layers:
        return None
    return (registry.counter_total("tm_moe_decode_routes_total")
            / (k * layers * steps))


def read(ctx, module):
    traced = ctx.get("traced") or {}
    cfg = ctx["cell"].config
    touched = touched_per_step()
    live = live_slots_per_step(cfg["num_experts_per_tok"])
    if (not traced.get("steps") or ctx["platform"] == "cpu" or not touched
            or not live):
        return None
    per_step = harness.load_module(
        ctx["cell"].manifest, "readers", "serve_module_ms").read(
            ctx, module, "steps")
    if not per_step:
        return None
    f = cfg["flops"]
    counts = importlib.import_module(f"chipbench.{f['module']}")
    need = counts.decode_step_bytes(
        traced["live_tokens_per_step"], touched, live,
        weight_bytes=WIDTH[cfg["weights_dtype"]],
        cache_bytes=WIDTH[cfg["cache_dtype"]],
        **{k: cfg[k] for k in f["sizes"]})
    least_ms = 1e3 * need / flops.peak_for(ctx["kind"])["hbm_bytes_per_s"]
    harness.log(f"decode step: {per_step:.3f} ms on the device; "
                f"{touched:.1f} held experts touched, {live:.1f} live "
                f"slots; needs {need / 1e9:.3f} GB ({least_ms:.3f} ms): "
                f"bound_by memory")
    return 100.0 * least_ms / per_step
