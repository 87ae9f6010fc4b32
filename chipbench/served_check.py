"""What decides ``correct`` for a served model: once the window has closed, a
sample of the requests it finished (drawn from the seed, the longest among
them) goes through the plain reference once, prompt and served tokens
teacher-forced, and the number compared is the WIDEST GAP by which a served
token's reference logit lies below the reference's best at its position.
Greedy tokens only.  A token that the timed path (bucketed prefill, then the
pooled decode step through the slot cache, batched with whatever shared the
pool) got right lies at the top or within rounding of it; a token altered
where it is produced, a cache row read at the wrong depth or a lower
precision lies far below.

The control reads the same number for the token that the reference, computed
one precision below the configuration's (``lowered``: float8_e4m3fn
weights), puts first at each of the same positions.  It need not decode.
"""

from __future__ import annotations

import numpy as np

from chipbench import harness, open_loop


def sample(records, seed, n):
    """``n`` finished requests of the window, the longest first, the others
    drawn from the seed."""
    done = sorted((r for r in records.values()
                   if r.phase == "window" and r.finished is not None
                   and r.tokens),
                  key=lambda r: (-(r.prompt_len + len(r.tokens)), r.rid))
    if len(done) <= n:
        return done
    rest = done[1:]
    pick = open_loop.rng_for(seed, 7).permutation(len(rest))[:n - 1]
    return [done[0]] + [rest[i] for i in sorted(pick)]


def reference_kwargs(config):
    return dict(depth=config["num_hidden_layers"],
                window=config["sliding_window"],
                rope_base=config["rope_theta"], eps=config["norm_epsilon"])


def gaps(cell, params, recs, control=False):
    """The widest gap over ``recs`` -> a record with one entry a request.
    With ``control`` the token judged at each position is the one that the
    reference one precision below puts first, not the served one."""
    ref = harness.load_module(cell.manifest, "reference",
                              cell.config["reference"]["module"])
    kw = reference_kwargs(cell.config)
    vocab = cell.config["vocab_size"]
    per, worst = [], 0.0
    for r in recs:
        served = np.asarray(r.tokens, np.int32)
        p, n = r.prompt_len, served.size
        inside = (served >= 0) & (served < vocab)
        served = np.where(inside, served, 0)    # an id outside: gap inf
        seq = np.concatenate([np.asarray(r.prompt, np.int32), served])[:-1]
        rows = np.arange(p - 1, p + n - 1)
        lg = np.asarray(ref.logits(params, seq, rows, **kw))
        judged = served
        if control:
            judged = np.asarray(ref.logits(params, seq, rows, lower=True,
                                           **kw)).argmax(-1)
        gap = np.where(inside, lg.max(-1) - lg[np.arange(n), judged], np.inf)
        at = int(gap.argmax())
        per.append({"rid": r.rid, "prompt": p, "served": n,
                    "gap": float(gap[at]), "at": at,
                    "off_the_top": int((gap > 0).sum())})
        worst = max(worst, float(gap[at]))
    return {"widest_gap": worst, "requests": per,
            "served_tokens": sum(x["served"] for x in per)}
