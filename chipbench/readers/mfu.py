"""Model FLOP/s utilization: the operations the forward and backward
passes require per image or token (``flops.py``, recomputation not
counted) times the untraced window's throughput per chip, over the chip's
published bf16 peak."""

from chipbench import flops, harness


def read(ctx):
    if ctx["platform"] == "cpu":
        return None     # a rehearsal: no chip, so no peak to be a share of
    peak = flops.peak_for(ctx["kind"])["bf16_flops_per_s"]
    return (100.0 * harness.flops_per_item(ctx["cell"])
            * ctx["items_per_s_chip"] / peak)
