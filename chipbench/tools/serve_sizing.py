#!/usr/bin/env python3
"""One saturated window of a served cell at ANOTHER slot count, in a process
of its own (the memory counters are the process's): how the slot count of a
configuration is chosen (the largest whose ``memory_peak_bytes`` reads under
15 GB).  ``key=value`` arguments override ``serving`` entries.  The cell's
own runner, traffic and rate; no check.  Refuses another platform than the
chip's; appends its JSON line to ``--out``.

    python3 chipbench/tools/serve_sizing.py --workload imoe-16b-serve-conv-sat \\
        --seconds 12 --seed 2931000001 slots=20
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import harness  # noqa: E402

KEPT = ("attempted", "failed", "out_tokens_per_s", "served_tokens_per_s",
        "decode_steps", "itl_ms_p50", "itl_ms_p99", "ttft_ms_p50",
        "batch_occupancy_pct", "prefill_share_pct",
        "cache_tokens_used_over_reserved")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=2931000001)
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--rate", type=float, default=None)
    p.add_argument("--out", default="chiprun_out/sizing.jsonl")
    p.add_argument("overrides", nargs="*")
    args = p.parse_args()

    import jax

    from torchmpi_tpu.utils import compilecache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the readings are the chip's")
    compilecache.enable_persistent_cache()
    manifest = harness.load_manifest()
    cell = harness.resolve(manifest, args.workload)
    for kv in args.overrides:
        k, v = kv.split("=")
        cell.config["serving"][k] = int(v)
    if args.rate is not None:
        cell.traffic = {**cell.traffic, "rate_per_s": args.rate}
    runner = harness.load_module(manifest, "runners", cell.config["runner"])
    s = runner.served(cell, args.seed, args.seconds)
    row = {"workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "rate_per_s": cell.traffic["rate_per_s"],
           "serving": cell.config["serving"], "setup_s": s.setup_s,
           "memory_peak_bytes": s.device["memory_peak_bytes"],
           "bytes_in_use": (s.device["memory_stats"] or {}).get(
               "bytes_in_use"),
           "bytes_limit": (s.device["memory_stats"] or {}).get(
               "bytes_limit"),
           **{k: s.stats[k] for k in KEPT}}
    row = {k: (None if v == float("inf") else v) for k, v in row.items()}
    print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
