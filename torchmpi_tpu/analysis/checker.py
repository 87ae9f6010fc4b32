"""The analyzer entry points: trace, walk, run rules, report.

``check(fn, *args)`` is the whole pipeline: trace ``fn`` to a
``ClosedJaxpr`` (``jax.make_jaxpr``), walk it into a collective-event stream
(:mod:`events`), collect the trace-time fusion/ZeRO layout records, and
run the rule registry (:mod:`rules`).  Everything is trace-time only:
nothing here ever runs device code or touches the step's runtime cost.

``assert_clean`` is the pytest-facing wrapper; the opt-in runtime hook
(``Config.analysis``) lives in :mod:`torchmpi_tpu.analysis.hook`.
"""

from __future__ import annotations

import sys
from typing import Any, List, Optional, Sequence, Tuple

from .events import trace_events
from .findings import (ERROR, Finding, format_findings, has_errors,
                       severity_rank, sort_findings)
from .rules import RuleContext, run_rules, unbound_axis_finding

AxisEnv = Sequence[Tuple[str, int]]


def _effective_config(config):
    if config is not None:
        return config
    from .. import runtime

    return runtime.effective_config()


def _capture_records(records: List[dict]):
    """Listener installed on the fusion layer during tracing: every
    fused-collective / ZeRO layout record lands in ``records``."""
    def listen(rec: dict) -> None:
        records.append(rec)
    return listen


def trace_fn(fn, *args, axis_env: Optional[AxisEnv] = None,
             _records_out: Optional[List[dict]] = None,
             **kwargs) -> Tuple[Any, List[dict]]:
    """Trace ``fn`` to a ClosedJaxpr, collecting fusion/ZeRO records.

    Raises whatever tracing raises — ``check`` is the surface that
    converts unbound-axis failures into findings.  ``_records_out``
    (internal) receives the records captured BEFORE a trace failure, so
    ``check`` can still report record-only rules (C2's residual
    mismatch emits its record and then raises)."""
    import jax

    from .. import fusion

    records: List[dict] = [] if _records_out is None else _records_out
    prev = fusion.set_trace_listener(_capture_records(records))
    try:
        closed = jax.make_jaxpr(
            fn, axis_env=list(axis_env) if axis_env else None
        )(*args, **kwargs)
    finally:
        fusion.set_trace_listener(prev)
    return closed, records


def _is_unbound_axis_error(exc: BaseException) -> bool:
    msg = str(exc)
    return ("unbound axis name" in msg
            or "axis name" in msg and "not found" in msg
            or "is not bound" in msg)


def check(fn, *args, rules: Optional[Sequence[str]] = None,
          axis_env: Optional[AxisEnv] = None, config=None,
          label: str = "", **kwargs) -> List[Finding]:
    """Statically analyze one step function; returns sorted findings.

    ``fn`` is traced with ``jax.make_jaxpr`` on ``args`` (arrays or
    ``jax.ShapeDtypeStruct``s — no device execution happens).  Trace it
    the way it runs: a function that calls ``shard_map`` itself needs no
    extras; per-device code written for use *inside* ``shard_map`` needs
    ``axis_env=[("axis", size), ...]`` to bind its axis names.

    ``rules`` selects a subset of the registry (default: all).
    ``config`` overrides the effective runtime config consulted by the
    perf rules.  A trace failure caused by an unbound axis name is
    converted into the D2 finding it really is; other trace errors
    propagate.
    """
    partial: List[dict] = []
    try:
        closed, records = trace_fn(fn, *args, axis_env=axis_env,
                                   _records_out=partial, **kwargs)
    except NameError as e:
        # Convert only when the caller selected D2 (or ran all rules):
        # with D2 excluded, fabricating the finding would sneak an
        # unselected rule past assert_clean — re-raise instead, which
        # also keeps the trace failure loud rather than hidden.
        if _is_unbound_axis_error(e) and (rules is None or "D2" in rules):
            return [unbound_axis_finding(e, label)]
        raise
    except ValueError as e:
        # A structural-validation raise mid-trace: the EF residual
        # mismatch (gradsync/zero — docs/HIERARCHICAL.md) emits its C2
        # record BEFORE raising, so the captured records can still name
        # the site with provenance the bare exception lacks.  Only that
        # exact raise converts (compress.ResidualMismatchError, looked
        # up via sys.modules so analysis never imports the codec
        # module): a generic ValueError later in a trace that earlier
        # caught-and-survived a mismatch must propagate loud, not be
        # masked by the stale record.
        _codec = sys.modules.get("torchmpi_tpu.compress")
        if (_codec is not None
                and isinstance(e, _codec.ResidualMismatchError)
                and (rules is None or "C2" in rules)):
            ctx = RuleContext(
                events=(),
                records=[r for r in partial
                         if r.get("kind") == "dcn_residual"],
                config=_effective_config(config), label=label)
            found = [f for f in run_rules(ctx, ("C2",))
                     if f.severity == ERROR]
            if found:
                return sort_findings(found)
        raise
    bound = [a for a, _ in (axis_env or ())]
    return check_jaxpr(closed, records=records, bound_axes=bound,
                       rules=rules, config=config, label=label)


def check_jaxpr(closed_jaxpr, *, records: Sequence[dict] = (),
                bound_axes: Sequence[str] = (),
                rules: Optional[Sequence[str]] = None,
                config=None, label: str = "") -> List[Finding]:
    """Run the rules over an already-traced ClosedJaxpr."""
    from .slices import trace_slice_events

    events = trace_events(closed_jaxpr, bound_axes=bound_axes)
    ctx = RuleContext(events=events, records=list(records),
                      config=_effective_config(config), label=label,
                      slice_events=trace_slice_events(closed_jaxpr))
    return sort_findings(run_rules(ctx, rules))


def assert_clean(fn, *args, rules: Optional[Sequence[str]] = None,
                 axis_env: Optional[AxisEnv] = None, config=None,
                 fail_on: str = ERROR, label: str = "",
                 **kwargs) -> List[Finding]:
    """Pytest helper: run :func:`check` and raise ``AssertionError`` if
    any finding is at least ``fail_on`` severe (default: errors only —
    perf warnings don't fail a correctness suite).  Returns the full
    finding list so callers can still inspect the quieter ones."""
    findings = check(fn, *args, rules=rules, axis_env=axis_env,
                     config=config, label=label, **kwargs)
    threshold = severity_rank(fail_on)
    bad = [f for f in findings if severity_rank(f.severity) <= threshold]
    if bad:
        raise AssertionError(
            f"collective-consistency analysis of "
            f"{label or getattr(fn, '__name__', fn)!r} found "
            f"{len(bad)} problem(s):\n{format_findings(bad)}")
    return findings


__all__ = [
    "check", "check_jaxpr", "assert_clean", "trace_fn",
    "Finding", "format_findings", "has_errors",
]
