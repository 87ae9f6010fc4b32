"""The pooled decode step's share of its HBM roofline where the model mixes
window layers, whose keys and values are a ring of ``sliding_window`` rows a
slot, with global layers, whose cache holds the whole context, and holds a
SHARE of its experts (``k-exaone-236b-a23b-serve``).  The step is bound by
bandwidth; the bytes it REQUIRES (the configuration's ``flops`` module,
``decode_step_bytes``): every weight outside the routed experts once, a
routed expert's weights for each HELD expert a layer that a live token chose,
each live slot's live ring rows in every window layer, and the global
layers' keys and values of the live tokens, at the file's ``cache_dtype``;
over the chip's HBM bandwidth, over the device time a step takes in the
traced stretch (``readers/serve_module_ms``).

Counted from the work, not from what implements it: the rows a step reads
beyond these (a global layer's reserved and unused positions, a ring row no
query may see) are not required.  The live slots and their depths are the
window's own (``open_loop.reduce``: ``live_slots_per_step`` and
``live_tokens_per_step``, the live sessions and the sum of their depths at
each decode step inside the window).  A live slot's ring holds
``min(depth + 1, window)`` live rows a layer: ``window`` for every slot
whose depth has reached it, which is every slot of a traffic whose shortest
prompt is longer than the window; the engine's own count of the same rows
over the whole run is ``stats["window_rows_live"]``, on every line under
``checks.counters``.  The held experts touched a step are the program's
count (``tm_moe_experts_touched_total`` over ``tm_moe_decode_steps_total``:
every decode step of the process, the warm-up's fewer live slots included,
so the share reads a little low rather than high).  No traced step, no
counters, no chip: no number.
"""

import importlib

from chipbench import flops, harness
from chipbench.readers.serve_decode_roofline import WIDTH
from chipbench.readers.serve_moe_decode_roofline import touched_per_step


def read(ctx, module):
    traced = ctx.get("traced") or {}
    serve = ctx.get("serve") or {}
    live = serve.get("live_slots_per_step")
    tokens = serve.get("live_tokens_per_step")
    touched = touched_per_step()
    if (not traced.get("steps") or ctx["platform"] == "cpu" or not live
            or not touched):
        return None
    cfg = ctx["cell"].config
    per_step = harness.load_module(
        ctx["cell"].manifest, "readers", "serve_module_ms").read(
            ctx, module, "steps")
    if not per_step:
        return None
    f = cfg["flops"]
    counts = importlib.import_module(f"chipbench.{f['module']}")
    sizes = {k: cfg[k] for k in f["sizes"]}
    rings = sum(w for w, _ in counts.layers_of(**sizes))
    ring_rows = rings * min(live * cfg["sliding_window"], tokens + live)
    need = counts.decode_step_bytes(
        tokens, ring_rows, touched, weight_bytes=WIDTH[cfg["weights_dtype"]],
        cache_bytes=WIDTH[cfg["cache_dtype"]], **sizes)
    least_ms = 1e3 * need / flops.peak_for(ctx["kind"])["hbm_bytes_per_s"]
    harness.log(f"decode step: {per_step:.3f} ms on the device; {live:.1f} "
                f"live slots, {tokens:.0f} live tokens, {touched:.1f} held "
                f"experts touched; needs {need / 1e9:.3f} GB "
                f"({least_ms:.3f} ms): bound_by memory")
    return 100.0 * least_ms / per_step
