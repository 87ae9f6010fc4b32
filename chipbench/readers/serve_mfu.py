"""The whole served window's share of the chip's peak: the operations that
every prompt and every output token stamped inside the window REQUIRE
(``flops_serve.window_flops``: attention over the live context included, no
bucket padding, no reserved cache) over the window's seconds, over the bf16
peak.  It bounds what any kernel's roofline can claim."""

import importlib

from chipbench import flops


def read(ctx):
    if ctx["platform"] == "cpu" or not ctx.get("records"):
        return None     # a rehearsal: no chip, so no peak to be a share of
    f = ctx["cell"].config["flops"]
    counts = importlib.import_module(f"chipbench.{f['module']}")
    sizes = {k: ctx["cell"].config[k] for k in f["sizes"]}
    need = counts.window_flops(ctx["records"].values(), ctx["seconds"],
                               **sizes)
    peak = flops.peak_for(ctx["kind"])["bf16_flops_per_s"]
    return 100.0 * need / ctx["seconds"] / peak
