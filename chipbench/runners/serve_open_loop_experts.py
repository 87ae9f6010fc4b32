"""``serve_open_loop`` for a served model with sparse experts: the same
runner, the same loop, window, sample and reference comparison, with two
names of that module bound anew when this one is imported (the benchmark's
tools load ``serve_open_loop`` by name, so binding serves them too, without
a second copy of either):

- ``lm_weights`` -> ``lm_weights_experts``: the same contract with rules
  for an expert layer's leaves and for blocks of different shapes.
- ``judge`` -> :func:`judge` below: the same comparison
  (``served_check.gaps`` over the same sample) read by the statistics that
  can tell this model's precisions apart.

Why other statistics.  A token's six experts are a DISCRETE choice: where
the sixth and seventh scores lie within bfloat16's rounding of the stream,
the program in bfloat16 and the float32 reference choose differently, the
token's logits then differ by a whole expert's output (its weight is 2.5 / 6,
not a softmax's tail), and the WIDEST gap over five thousand served tokens
reads 2.9 to 4.1 for the program and 3.8 to 4.4 for the float8 control (my
chip runs, PR 31): no limit lies between.  The same served path computed in
float32 reads 0.0 on the CPU at the rehearsal's sizes.  What does tell them
apart, steadily, is how OFTEN a served token is not the reference's first
choice: 0.178 to 0.190 of the tokens for the program over six runs, 0.737 to
0.739 for the control.  So ``correct`` asks, beside what ``serve_open_loop``
asks of every served cell:

- ``off_the_top_share``: served tokens that are not the reference's first
  choice, over all served tokens of the sample, within its limit;
- ``worst_request_off_share``: the same share for ONE request, the worst of
  those with at least ``request_min_tokens`` served tokens: a slot read at
  the wrong depth or another slot's cache row spoils one request, not the
  sample;
- ``logit_gap`` where the configuration gives it a limit (the rehearsal's
  sizes, computed in float32); where the limit is null it is recorded beside
  the others and judged by nobody.
"""

from __future__ import annotations

import math
import types

from chipbench import harness, lm_weights_experts

base = harness.load_module(harness.load_manifest(), "runners",
                           "serve_open_loop")
_judge = base.judge


def judge(cell, seed, s, compile_events=()):
    tol = cell.config["tolerance"]
    limit = tol["logit_gap"]
    # a null limit: recorded, not judged (the base judge wants a number)
    asked = types.SimpleNamespace(**{**vars(cell), "config": {
        **cell.config, "tolerance": {
            **tol, "logit_gap": math.inf if limit is None else limit}}})
    checked, compared, correct = _judge(asked, seed, s, compile_events)
    compared["logit_gap"][1] = limit
    return (checked, *shares(checked, compared, correct, tol))


def shares(checked, compared, correct, tol):
    """``compared`` with the two shares beside their limits, and the
    verdict with them in it."""
    requests = checked["requests"]
    long_enough = [r["off_the_top"] / r["served"] for r in requests
                   if r["served"] >= tol["request_min_tokens"]]
    mine = {
        "off_the_top_share": [
            sum(r["off_the_top"] for r in requests)
            / max(1, checked["served_tokens"]), tol["off_the_top_share"]],
        "worst_request_off_share": [max(long_enough, default=0.0),
                                    tol["worst_request_off_share"]]}
    compared = {**mine, **compared}
    return compared, bool(correct and all(v <= lim for v, lim in
                                          mine.values()))


base.lm_weights = lm_weights_experts
base.judge = judge

setup, window, served, layer_metrics, run = (
    base.setup, base.window, base.served, base.layer_metrics, base.run)
CHECKED_REQUESTS, TRACE_DIR = base.CHECKED_REQUESTS, base.TRACE_DIR
