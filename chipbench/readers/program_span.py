"""Mean milliseconds of one of the PROGRAM's own host spans inside the
traced window, from the ``/host:CPU`` plane of the profile the runner
wrote (``xplane.load`` keeps only the benchmark's four span names).

``tm.step`` is ``parallel/gradsync.throttle_dispatch``'s
``StepTraceAnnotation`` around the call into the jitted step (the
enqueue), with ``step_num`` counting up; ``tm.step.throttle`` its wait on
a full in-flight window.  A program without the span gives no number.
"""

from chipbench import harness

HOST_PLANE = "/host:CPU"


def host_spans(path, span):
    """``[(start_ns, end_ns, stats)]`` of the host events named ``span``,
    in order of their start."""
    import gzip

    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    return sorted(
        ((e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
         for plane in data.planes if plane.name == HOST_PLANE
         for line in plane.lines for e in line.events if e.name == span),
        key=lambda s: s[:2])


def span_ms(spans, window):
    """Mean milliseconds of the spans that lie inside the window."""
    lo, hi = window
    inside = [t - s for s, t, _ in spans if s >= lo and t <= hi]
    return 1e-6 * sum(inside) / len(inside) if inside else None


def read(ctx, span):
    scopes = harness.load_module(ctx["cell"].manifest, "readers",
                                 "xplane_scopes")
    spans = host_spans(scopes.raw_trace(ctx), span)
    value = span_ms(spans, ctx["trace"].window)
    if value is not None:
        nums = [st["step_num"] for _, _, st in spans if "step_num" in st]
        harness.log(f"{span!r}: {len(spans)} spans in the profile"
                    + (f", step_num {nums[0]}..{nums[-1]}" if nums else ""))
    return value
