#!/usr/bin/env python3
"""The knee of a served cell, found ONCE by a sweep on the chip: the cell's
own configuration and lengths at rising rates (requests a second, from
``--from`` in steps of ``--step``), each rate under ``--orders`` orders of
its population (seeds ``--seed``, ``--seed`` + 1, ...: the order the cells
themselves run in), a window of ``--seconds`` each, ONE process and ONE
server (between two windows the loop ticks on until every slot is free).
It stops after two rates in a row that are not sustained
(``serve_knee.sustained``), or at ``--to``.  A row a window: what was
offered and what came back, the queue when the window closed, the tails.
``key=value`` arguments override ``serving`` entries of the configuration
(``slots=7``: how the slot count was chosen).  Refuses another platform than
the chip's; appends its JSON lines to ``chiprun_out/``.

    python3 chipbench/tools/serve_sweep.py --workload sc2-3b-serve-r80 \\
        --seed 2030000011 --orders 3 --seconds 30 --from 2.5 --step 0.5
"""

import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import serve_knee  # noqa: E402  (beside this file)
from chipbench import harness, open_loop  # noqa: E402

KEPT = ("attempted", "failed", "offered_tokens_per_s", "out_tokens_per_s",
        "served_tokens_per_s", "prompt_tokens_admitted", "decode_steps",
        "ttft_ms_p50", "ttft_ms_p90", "itl_ms_mean", "itl_ms_p50",
        "itl_ms_p95", "itl_ms_p99",
        "generator_late_ms_p95", "queue_wait_ms_p90", "batch_occupancy_pct",
        "prefill_share_pct", "cache_tokens_used_over_reserved")


def flush(server):
    """Tick on, admitting nothing, until every slot is free."""
    while any(e.active for e in server.router.live()):
        server._tick(collections.deque())


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="sc2-3b-serve-r80")
    p.add_argument("--seed", type=int, default=2030000011)
    p.add_argument("--orders", type=int, default=3)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--from", dest="lo", type=float, default=2.5)
    p.add_argument("--to", dest="hi", type=float, default=8.0)
    p.add_argument("--step", type=float, default=0.5)
    p.add_argument("--out", default="chiprun_out/pr30/sweep.jsonl")
    p.add_argument("--rehearse", action="store_true",
                   help="the rehearsal's sizes on the CPU: control flow only")
    p.add_argument("overrides", nargs="*")
    args = p.parse_args()

    import jax

    from torchmpi_tpu.utils import compilecache

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        raise SystemExit("the sweep measures on the chip")
    compilecache.enable_persistent_cache()
    manifest = harness.load_manifest()
    cell = harness.resolve(manifest, args.workload, rehearse=args.rehearse)
    for kv in args.overrides:
        k, v = kv.split("=")
        cell.config["serving"][k] = int(v)
    runner = harness.load_module(manifest, "runners", "serve_open_loop")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    slots = cell.config["serving"]["slots"]
    spec = cell.traffic["prompt_tokens"]
    # every bucket between the shortest and the longest prompt of the file
    reach = [spec["min"], spec["max"]] + [
        1 << b for b in range(spec["max"].bit_length())
        if spec["min"] < 1 << b < spec["max"]]
    ready = runner.setup(cell, args.seed, reach)
    rate, misses = args.lo, 0
    while rate <= args.hi + 1e-9 and misses < 2:
        cell.traffic = {**cell.traffic, "rate_per_s": rate}
        rows = []
        for seed in range(args.seed, args.seed + args.orders):
            schedule = open_loop.schedule(
                cell.traffic, seed, seconds=args.seconds,
                vocab=cell.config["vocab_size"])
            s = runner.window(cell, ready, schedule, args.seconds)
            mine = [r for r in s.records.values()
                    if 0 <= r.due < args.seconds]
            row = {
                "rate_per_s": rate, "seconds": args.seconds, "slots": slots,
                "seed": seed,
                "queue_at_close": sum(
                    r.admit is None or r.admit >= args.seconds
                    for r in mine),
                "unfinished_after_drain": s.stats["failed"],
                **{k: s.stats[k] for k in KEPT}}
            row = {k: (None if v == float("inf") else v)
                   for k, v in row.items()}
            rows.append(row)
            print(json.dumps(row), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            flush(ready.server)
        misses = 0 if serve_knee.sustained(rows) else misses + 1
        rate = round(rate + args.step, 6)


if __name__ == "__main__":
    main()
