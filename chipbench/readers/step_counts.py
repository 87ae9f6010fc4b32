"""What the cell's own train step counted on the device and handed back
beside the loss, a step and layer, over the traced window's steps: the mean
of ``counter`` (a name the step file's ``counted`` gives, one value a
layer) divided by the items of a step.  The step file keeps the counts on
the device while the window runs; they are fetched here, after it.  A step
file that counts nothing gives no number."""

from chipbench import harness


def per_step(ctx, counter):
    """The counter's mean over the traced steps, one value a layer, or
    None."""
    cell = ctx["cell"]
    step = harness.load_module(cell.manifest, "steps", cell.config["step"])
    counts = getattr(step, "counted", None)
    # the traced window's steps and the two its loop primes and drains
    counts = counts and counts(ctx["traced_steps"] + 2)
    return counts and counts.get(counter)


def read(ctx, counter):
    layers = per_step(ctx, counter)
    if not layers:
        return None
    tr = ctx["cell"].traffic
    harness.log(f"{counter} a step, by layer: {layers}")
    return sum(layers) / len(layers) / (tr["batch_per_chip"] * tr["seq"])
