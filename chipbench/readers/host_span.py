"""Mean milliseconds of one of the benchmark's own host spans per step,
over the untraced window (``dispatch``: the call into the step, enqueue
only; ``wait_loss``: blocked on the previous step's loss)."""


def read(ctx, span):
    spans = ctx["spans"].get(span)
    if not spans:
        return None
    return 1e3 * sum(t - s for s, t in spans) / len(spans)
