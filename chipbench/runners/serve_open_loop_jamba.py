"""``serve_open_loop`` for a served model of Mamba-1 mixers and attention
under a tied head (``jamba2-3b-serve``): the same runner, the same loop,
window, sample and reference comparison, with two names of that module bound
anew when this one is imported, as ``serve_open_loop_hybrid`` binds its own
(the benchmark's tools load ``serve_open_loop`` by name, so binding serves
them too).  ``serve_open_loop_hybrid`` is imported FIRST, so that whichever
of the thin runners a process imports first, the bindings nest in one order.

- ``lm_weights`` -> :class:`ByModel`: a model with a Mamba-1 mixer in its
  ``layer_pattern`` (the letter ``m``) gets ``lm_weights_jamba``'s rules,
  every other model what was bound before (a ``nemotron_h`` hybrid
  ``lm_weights_hybrid``'s, any other ``lm_weights_experts``').
- ``judge`` -> :func:`judge`: a configuration whose ``tolerance`` has a
  ``long_gap_when_off_the_top`` is judged here, any other by what was bound
  before.

What is judged here.  The model is dense, so the window's sample is judged
by ``serve_open_loop``'s own judge and its ONE limit, ``logit_gap``, which
tells the float8 control from the program and nothing finer.  The recurrent
state is two thirds of a slot and most of a step's bytes, and the file states
its type (``cache_dtype``): a state held one precision below adds rounding
noise that GROWS over an answer's decode steps (a slow channel remembers
hundreds of roundings) beside the bfloat16 stream's own, which does not.  A
maximum over the 4,000 tokens of short answers cannot see a twelfth more
noise; a MEAN over the tokens late in long answers can, once the weights'
own share of near-ties is divided out: how OFTEN a served token is not the
reference's first choice follows the seed's weights (0.121-0.131 over five
seeds), how FAR below the best it then lies follows the noise alone.  So
``correct`` also asks:

- ``long_gap_when_off_the_top``: over the ``long_requests`` longest finished
  answers of the WARM-UP stretch (hundreds of decode steps each, contexts
  past a thousand tokens, which the window's own sample never reaches), from
  the answer's position ``long_from_position`` on: the mean gap by which a
  served token that is NOT the reference's first choice lies below that
  choice, within its limit;
- ``long_tokens_checked``: the served tokens that mean was taken from, at
  least ``long_min_tokens``: a mean over fewer cannot tell the two states
  apart and is not called correct.
"""

from __future__ import annotations

import numpy as np

from chipbench import harness, lm_weights_jamba, served_check

hybrid = harness.load_module(harness.load_manifest(), "runners",
                             "serve_open_loop_hybrid")
base, experts = hybrid.base, hybrid.experts


class ByModel:
    """What ``serve_open_loop`` calls as ``lm_weights``: ``make`` for a
    model with an ``m`` in its ``layer_pattern`` is ``lm_weights_jamba``'s,
    for any other model what was bound ``before``."""

    def __init__(self, before):
        self.before = before

    def make(self, model, key, dtype):
        mine = "m" in (getattr(model, "layer_pattern", None) or "")
        return (lm_weights_jamba if mine else self.before).make(
            model, key, dtype)


def long_answers(records, n, start):
    """The ``n`` longest finished answers of the warm-up stretch that reach
    past their position ``start``."""
    return sorted((r for r in records.values()
                   if r.phase == "warm" and r.finished is not None
                   and len(r.tokens) > start),
                  key=lambda r: (-len(r.tokens), r.rid))[:n]


def gaps_when_off_the_top(cell, params, recs, start, control=False):
    """Over ``recs``, each answer from its position ``start`` on: -> the
    served tokens, those that are not the reference's first choice, and the
    mean gap of those below that choice (0.0 where there is none).  With
    ``control`` the token judged is the one the reference one precision
    below puts first, as ``served_check.gaps`` has it."""
    ref = harness.load_module(cell.manifest, "reference",
                              cell.config["reference"]["module"])
    kw = served_check.reference_kwargs(cell.config)
    tokens = off = 0
    total = 0.0
    for r in recs:
        served = np.clip(np.asarray(r.tokens, np.int32), 0,
                         cell.config["vocab_size"] - 1)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), served])[:-1]
        rows = np.arange(r.prompt_len - 1 + start, seq.size)
        lg = np.asarray(ref.logits(params, seq, rows, **kw))
        judged = served[start:]
        if control:
            judged = np.asarray(ref.logits(params, seq, rows, lower=True,
                                           **kw)).argmax(-1)
        gap = lg.max(-1) - lg[np.arange(rows.size), judged]
        tokens += rows.size
        off += int((gap > 0).sum())
        total += float(gap.sum())
    return {"requests": len(recs), "served_tokens": tokens,
            "off_the_top": off, "gap_when_off_the_top": total / max(1, off)}


def judge(cell, seed, s, compile_events=()):
    tol = cell.config["tolerance"]
    if "long_gap_when_off_the_top" not in tol:
        return _before(cell, seed, s, compile_events)
    checked, compared, correct = experts._judge(cell, seed, s,
                                                compile_events)
    start = tol["long_from_position"]
    far = gaps_when_off_the_top(
        cell, s.params, long_answers(s.records, tol["long_requests"], start),
        start)
    checked["long_answers"] = far
    mine = {"long_gap_when_off_the_top": [far["gap_when_off_the_top"],
                                          tol["long_gap_when_off_the_top"]],
            "long_tokens_checked": [far["served_tokens"],
                                    tol["long_min_tokens"]]}
    ok = (mine["long_gap_when_off_the_top"][0]
          <= tol["long_gap_when_off_the_top"]
          and far["served_tokens"] >= tol["long_min_tokens"])
    return checked, {**compared, **mine}, bool(correct and ok)


_before = base.judge
base.lm_weights = ByModel(base.lm_weights)
base.judge = judge

setup, window, served, layer_metrics, run = (
    base.setup, base.window, base.served, base.layer_metrics, base.run)
CHECKED_REQUESTS, TRACE_DIR = base.CHECKED_REQUESTS, base.TRACE_DIR
