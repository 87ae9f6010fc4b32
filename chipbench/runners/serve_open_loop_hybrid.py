"""``serve_open_loop`` for a served hybrid (state-space mixers, attention and
latent expert layers: ``nemotron3-super-120b-a12b-serve``): the same runner,
the same loop, window, sample and reference comparison, with two names of
that module bound anew when this one is imported, as
``serve_open_loop_experts`` binds its own (the benchmark's tools load
``serve_open_loop`` by name, so binding serves them too):

- ``lm_weights`` -> :class:`ByModel`: a hybrid's weights by
  ``lm_weights_hybrid`` (the same contract with rules for a mixer's leaves,
  a narrower selection bias and blocks of three shapes), every other
  model's by what was bound before, so that a process which has imported
  this runner still draws the other served cells' weights as they were.
- ``judge`` -> ``serve_open_loop_experts``' judge, as it is: the comparison
  of the same sample read by the two shares of tokens off the reference's
  top, and ``logit_gap`` judged only where the configuration gives it a
  limit.  This model's 22nd and 23rd of 512 scores lie closer than
  Instella's 6th and 7th of 64, for the same reason.
"""

from __future__ import annotations

from chipbench import harness, lm_weights_hybrid

experts = harness.load_module(harness.load_manifest(), "runners",
                              "serve_open_loop_experts")
base = experts.base
judge, shares = experts.judge, experts.shares   # bound to ``base`` there



class ByModel:
    """What ``serve_open_loop`` calls as ``lm_weights``: ``make`` for a
    model with a ``layer_pattern`` is ``lm_weights_hybrid``'s, for any other
    model what was bound ``before``."""

    def __init__(self, before):
        self.before = before

    def make(self, model, key, dtype):
        mine = getattr(model, "layer_pattern", None)
        return (lm_weights_hybrid if mine else self.before).make(
            model, key, dtype)


base.lm_weights = ByModel(base.lm_weights)

setup, window, served, layer_metrics, run = (
    base.setup, base.window, base.served, base.layer_metrics, base.run)
CHECKED_REQUESTS, TRACE_DIR = base.CHECKED_REQUESTS, base.TRACE_DIR
