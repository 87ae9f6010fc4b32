#!/usr/bin/env python3
"""The two readings a served cell's ``logit_gap`` limit is set between, on
the chip at the cell's own size and load, many seeds in one process: the
widest gap of the PROGRAM's served tokens over the sample a run checks, and
(``--control-seeds``) the widest gap of the tokens that the reference one
precision below (float8_e4m3fn weights) puts first at the same positions.
A short window (``--seconds``) at the cell's own rate, long enough to finish
the mix's longest requests.  Refuses another platform than the chip's;
appends its JSON lines to ``chiprun_out/``; exit 1 unless every control
reads over the file's limit and every program run under it.

    python3 chipbench/tools/serve_precision.py --workload sc2-3b-serve-r80 \\
        --seconds 15 --control-seeds 3 2030000101 2030000102 ...
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import harness, served_check  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="sc2-3b-serve-r80")
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--control-seeds", type=int, default=3,
                   help="how many of the seeds also read the control")
    p.add_argument("--out", default="chiprun_out/pr30/precision.jsonl")
    p.add_argument("seeds", nargs="+", type=int)
    args = p.parse_args()

    import jax

    from torchmpi_tpu.utils import compilecache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the readings are the chip's")
    compilecache.enable_persistent_cache()
    manifest = harness.load_manifest()
    cell = harness.resolve(manifest, args.workload)
    runner = harness.load_module(manifest, "runners", "serve_open_loop")
    limit = cell.config["tolerance"]["logit_gap"]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    ok = True
    for i, seed in enumerate(args.seeds):
        s = runner.served(cell, seed, args.seconds)
        picked = served_check.sample(s.records, seed,
                                     runner.CHECKED_REQUESTS)
        t = time.monotonic()
        program = served_check.gaps(cell, s.params, picked)
        row = {"workload": args.workload, "seed": seed,
               "seconds": args.seconds, "limit": limit,
               "served_tokens": program["served_tokens"],
               "longest": picked[0].prompt_len + len(picked[0].tokens),
               "program": program["widest_gap"],
               "program_off_the_top": sum(r["off_the_top"]
                                          for r in program["requests"]),
               "reference_s": time.monotonic() - t}
        ok &= row["program"] <= limit
        if i < args.control_seeds:
            control = served_check.gaps(cell, s.params, picked, control=True)
            row["control"] = control["widest_gap"]
            row["control_off_the_top"] = sum(r["off_the_top"]
                                             for r in control["requests"])
            row["control_per_request"] = [r["gap"]
                                          for r in control["requests"]]
            ok &= row["control"] > limit
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        del s, picked
        gc.collect()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
