"""The ratio of two of the PROGRAM's counters, summed over their series,
from ``torchmpi_tpu.obs``'s registry in this process (the cell's step file
puts them there during its check, outside the window).  A program without
the counters gives no number."""


def read(ctx, numerator, denominator):
    try:
        from torchmpi_tpu import obs
    except ImportError:
        return None
    below = obs.registry().counter_total(denominator)
    if not below:
        return None
    return obs.registry().counter_total(numerator) / below
