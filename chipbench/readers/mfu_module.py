"""``readers/mfu.py`` for a configuration whose operation count lives in a
module of its own: the configuration's ``flops`` names the ``module`` under
``chipbench/``, the ``function`` in it and the ``sizes`` it takes."""

import importlib

from chipbench import flops


def flops_per_item(cell):
    f = cell.config["flops"]
    sizes = {k: cell.config[k] for k in f["sizes"]}
    if "seq" in cell.traffic:
        sizes["seq"] = cell.traffic["seq"]
    module = importlib.import_module(f"chipbench.{f['module']}")
    return getattr(module, f["function"])(**sizes)


def read(ctx):
    if ctx["platform"] == "cpu":
        return None     # a rehearsal: no chip, so no peak to be a share of
    peak = flops.peak_for(ctx["kind"])["bf16_flops_per_s"]
    return (100.0 * flops_per_item(ctx["cell"]) * ctx["items_per_s_chip"]
            / peak)
