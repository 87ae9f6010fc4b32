#!/usr/bin/env python3
"""From the sweep's rows (``tools/serve_sweep.py``: several orders a rate)
to the two rates.  A rate is sustained if in NO order the queue when the
window closes is deeper than the slot count, and every request due in the
window finishes within the drain.  (The share of the offered output tokens
that the window gives back is kept beside it and decides nothing: over 30 s
it carries the luck of where the few long answers fall, 0.85 to 0.98 at a
rate where the queue is empty.)  The knee is the highest sustained rate
below the first that is not; ``open-r80`` gets 0.8 of it, ``open-sat``
1.25, as requests a second, and ``open-r80.json`` keeps a row a rate beside
the number.  Needs no chip.

    python3 chipbench/tools/serve_knee.py <the sweep's .jsonl>
"""

import json
import os
import statistics
import sys

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
RULE = ("the highest swept rate, below the first that fails, at which in "
        "every order queue_at_close <= slots and unfinished_after_drain == 0")
SPREAD = ("ttft_ms_p50", "ttft_ms_p90", "itl_ms_p95", "itl_ms_p99")


def kept_share(rows):
    return statistics.mean(r["out_tokens_per_s"] / r["offered_tokens_per_s"]
                           for r in rows)


def sustained(rows):
    return (max(r["queue_at_close"] for r in rows) <= rows[0]["slots"]
            and not any(r["unfinished_after_drain"] for r in rows))


def by_rate(rows):
    rates = sorted({r["rate_per_s"] for r in rows})
    return [(rate, [r for r in rows if r["rate_per_s"] == rate])
            for rate in rates]


def knee(rows):
    k = None
    for rate, mine in by_rate(rows):
        if not sustained(mine):
            break
        k = rate
    if k is None:
        raise SystemExit("the lowest swept rate is not sustained")
    return k


def summary(rate, rows):
    """One row a rate: the share kept, the deepest queue, and each tail as
    the orders read it."""
    out = {"rate_per_s": rate, "orders": len(rows),
           "offered_tokens_per_s": round(statistics.mean(
               r["offered_tokens_per_s"] for r in rows), 2),
           "out_tokens_per_s": [round(r["out_tokens_per_s"], 1)
                                for r in rows],
           "kept_share": round(kept_share(rows), 4),
           "queue_at_close": [r["queue_at_close"] for r in rows],
           "unfinished_after_drain": [r["unfinished_after_drain"]
                                      for r in rows],
           "batch_occupancy_pct": [round(r["batch_occupancy_pct"], 1)
                                   for r in rows]}
    for key in SPREAD:
        out[key] = [None if r[key] is None else round(r[key], 1)
                    for r in rows]
    return out


def main(path):
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    k = knee(rows)
    record = {
        "knee_per_s": k, "seconds": rows[0]["seconds"],
        "slots": rows[0]["slots"],
        "seeds": sorted({r["seed"] for r in rows}), "rule": RULE,
        "rows": [summary(rate, mine) for rate, mine in by_rate(rows)]}
    for name, share in (("open-r80", 0.8), ("open-sat", 1.25)):
        file = os.path.join(TRAFFIC, name + ".json")
        with open(file) as f:
            traffic = json.load(f)
        traffic["rate_per_s"] = round(share * k, 3)
        traffic["knee"] = (record if name == "open-r80" else
                           {"knee_per_s": k, "rows": "traffic/open-r80.json"})
        with open(file, "w") as f:
            json.dump(traffic, f, indent=2)
            f.write("\n")
        print(name, traffic["rate_per_s"])
    print("knee", k)


if __name__ == "__main__":
    main(sys.argv[1])
