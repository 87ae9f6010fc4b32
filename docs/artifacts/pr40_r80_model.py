"""A timing model of ``sc2-3b-serve-r80``'s open loop: the synchronous step
(dispatch, read, book in one call) against the one-deep pipeline (the next
step dispatched before this one is read), on the cell's own schedule.

Nothing runs on a device: each admission and step costs the milliseconds
below (the prefill by bucket as PERF.md section 5 has it, a decode step of
9.76 ms, the host's phases as the traced runs split them), admissions come
first in a tick, and a token is stamped when its tick ends, as
``chipbench/open_loop.serve`` stamps it.  What it shows is a count: how many
of the 1,880 gaps between two tokens of one request hold a 4096-bucket
prefill (a gap of about 200 ms) when the decode loop is faster and so fewer
sessions are in flight when such a prompt arrives.  ``itl_ms_p99`` is the
19th longest gap, so where that count falls below 19 the p99 drops to the
next tier.

    python3 docs/artifacts/pr40_r80_model.py [seed ...]
"""

import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from chipbench import harness, open_loop  # noqa: E402

PREFILL_MS = {64: 18.9, 128: 21.2, 256: 23.3, 512: 31.2, 1024: 50.2,
              2048: 91.6, 4096: 192.1}
STEP_MS = 9.76
ADMIT_PRE_MS, ADMIT_POST_MS = 6.0, 1.0    # operands + dispatch / read tail
STEP_PRE_MS, STEP_POST_MS = 3.0, 1.4      # operands + dispatch / read + book
SLOTS = 5
LONG_MS = 180.0


def bucket(n):
    return min(max(64, 1 << max(0, n - 1).bit_length()), 4096)


def stamps(seed, pipelined, seconds=30.0):
    """Every request's token stamps (ms) under one order of the step."""
    cell = harness.resolve(harness.load_manifest(), "sc2-3b-serve-r80")
    todo = sorted((r for r in open_loop.schedule(
        cell.traffic, seed, seconds=seconds, vocab=cell.config["vocab_size"])
        if 0.0 <= r.due < seconds), key=lambda r: r.due)
    t = dev = 0.0
    pending, open_, unread, out = [], [], [], {}
    i = 0
    while i < len(todo) or pending or open_:
        while i < len(todo) and todo[i].due * 1e3 <= t:
            pending.append(todo[i])
            i += 1
        if not pending and not open_:
            t = todo[i].due * 1e3
            continue
        admitted, stepped = [], []
        while pending and len(open_) < SLOTS:
            r = pending.pop(0)
            t += ADMIT_PRE_MS
            dev = max(dev, t) + PREFILL_MS[bucket(r.prompt.size)]
            t = dev + ADMIT_POST_MS
            s = {"rid": r.rid, "left": r.max_new - 1, "ahead": 0}
            admitted.append(s)
            if s["left"] > 0:
                open_.append(s)
        if open_ and not pipelined:
            t += STEP_PRE_MS
            dev = max(dev, t) + STEP_MS
            t = dev + STEP_POST_MS
            for s in open_:
                s["left"] -= 1
                stepped.append(s)
        elif open_:
            def dispatch():
                nonlocal t, dev
                live = [s for s in open_ if s["left"] > s["ahead"]]
                t += STEP_PRE_MS
                dev = max(dev, t) + STEP_MS
                for s in live:
                    s["ahead"] += 1
                unread.append((dev, live))
            if not unread:
                dispatch()
            if any(s["left"] > s["ahead"] for s in open_):
                dispatch()
            done, owners = unread.pop(0)
            t = max(t, done) + STEP_POST_MS
            for s in owners:
                s["ahead"] -= 1
                s["left"] -= 1
                stepped.append(s)
        open_ = [s for s in open_ if s["left"] > 0]
        for s in admitted + stepped:
            out.setdefault(s["rid"], []).append(t)
    return out


def summary(seed, pipelined):
    gaps = sorted(b - a for st in stamps(seed, pipelined).values()
                  for a, b in zip(st, st[1:]))
    p99 = gaps[int((len(gaps) - 1) * 0.99)]
    return {"gaps": len(gaps), "p50": round(statistics.median(gaps), 2),
            "p99": round(p99, 2), "long": sum(g > LONG_MS for g in gaps)}


if __name__ == "__main__":
    seeds = [int(s) for s in sys.argv[1:]] or list(range(2150040400,
                                                         2150040420))
    for seed in seeds:
        print(seed, "synchronous", summary(seed, False),
              "pipelined", summary(seed, True))
