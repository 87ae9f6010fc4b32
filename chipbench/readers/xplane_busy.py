"""The device's idle share of the traced window: 1 - the union of the
device-operation intervals over the window, averaged over the chips."""

from chipbench import xplane


def read(ctx):
    busy = xplane.device_busy(ctx["trace"])
    if not busy["window_s"] or not ctx["trace"].devices:
        return None
    return 100.0 * (1.0 - busy["busy_s"] / busy["window_s"])
