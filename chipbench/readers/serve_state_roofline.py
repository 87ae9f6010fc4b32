"""A share of the HBM roofline for a served model whose layers carry a
per-slot recurrent state (``jamba2-3b-serve``), counted from the WORK and not
from what implements it.  ``what`` chooses the work and the time:

``"state"``: the recurrent updates of one pooled decode step REQUIRE each
    live slot's recurrent state once read and once written, every mixer
    layer (the configuration's ``flops`` module,
    ``ssm_state_bytes_per_slot``); the time is the device time a step of the
    events under ``op_name`` inside the program ``module``
    (``readers/serve_scopes_in_module``: the scope ``ssm_step``).
``"step"``: the whole pooled step requires every weight once, each live
    slot's state read and written and the live tokens' keys and values at
    the file's ``cache_dtype`` (``decode_step_bytes``); the time is the
    device time a step of the program (``readers/serve_module_ms``).

Over the chip's HBM bandwidth.  The live slots a step are the window's own
(``open_loop.reduce``: the live sessions at each decode step inside the
window, ``live_slots_per_step`` among the window's numbers; every slot in a
saturated cell, as in the traced stretch that follows it).  The program's
count of the same thing, ``ReplicaEngine.stats["live_slot_steps"]`` over
``stats["steps"]``, covers the warm-up's fill too and is on every line under
``checks.counters``, for an operator.  No traced step, no match or no chip:
no number.
"""

import importlib

from chipbench import flops, harness
from chipbench.readers.serve_decode_roofline import WIDTH


def read(ctx, module, what, op_name=None):
    traced = ctx.get("traced") or {}
    live = (ctx.get("serve") or {}).get("live_slots_per_step")
    if not traced.get("steps") or ctx["platform"] == "cpu" or not live:
        return None
    cfg = ctx["cell"].config
    f = cfg["flops"]
    counts = importlib.import_module(f"chipbench.{f['module']}")
    sizes = {k: cfg[k] for k in f["sizes"]}
    cache_bytes = WIDTH[cfg["cache_dtype"]]

    def reader(name):
        return harness.load_module(ctx["cell"].manifest, "readers", name)

    if what == "state":
        per_step = reader("serve_scopes_in_module").read(
            ctx, module, op_name=op_name)
        need = 2.0 * live * counts.ssm_state_bytes_per_slot(
            cache_bytes=cache_bytes, **sizes)
    elif what == "step":
        per_step = reader("serve_module_ms").read(ctx, module, "steps")
        need = counts.decode_step_bytes(
            live, traced["live_tokens_per_step"],
            weight_bytes=WIDTH[cfg["weights_dtype"]],
            cache_bytes=cache_bytes, **sizes)
    else:
        raise ValueError(f"unknown work {what!r} (state, step)")
    if not per_step:
        return None
    least_ms = 1e3 * need / flops.peak_for(ctx["kind"])["hbm_bytes_per_s"]
    harness.log(f"{what}: {per_step:.3f} ms a decode step on the device; "
                f"{live:.1f} live slots; needs {need / 1e9:.3f} GB "
                f"({least_ms:.3f} ms): bound_by memory")
    return 100.0 * least_ms / per_step
