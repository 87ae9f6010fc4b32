#!/usr/bin/env python3
"""What the two ``assumed`` settings of ``smallthinker-21b-a3b`` that no
source gives (``embedding_std`` and the optimizer's ``learning_rate``) do
to the load of the sixteen experts held and to the step time, ON THE CHIP
at the cell's sizes: the readings behind PERF.md section 6 and the file's
``assumed``.  Each argument is one setting, ``key=value`` pairs joined by
commas, run for every ``--seed``:

    chiprun --chips 1 --timeout 1800 -- python3 \\
        chipbench/tools/smallthinker_load.py --steps 80 --seed 11 --seed 12 \\
        embedding_std=0.019764,learning_rate=1e-6 learning_rate=3e-4 \\
        learning_rate=1e-5 learning_rate=1e-6

One JSON line a setting and seed (the cell's own train step, one step in
flight as in the window; the routes every layer held in the first and the
last step and their range between, of ``tokens x k`` routes a layer; the
ring has four batches, so a multiple of four ``--steps`` ends on the batch
it began with), to
standard output and appended to ``chiprun_out/smallthinker_load.jsonl``.
A platform other than a TPU of ``peaks.json`` is refused.  The lines
PERF.md quotes are ``chipbench/tools/readings/pr26_load.jsonl``.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import torchmpi_tpu as mpi  # noqa: E402
from chipbench import harness  # noqa: E402
from chipbench.tools.smallthinker_precision import (CELL, SIZES,  # noqa: E402
                                                    chip)
from torchmpi_tpu.utils import compilecache  # noqa: E402

OUT = os.path.join(harness.ROOT, "chiprun_out", "smallthinker_load.jsonl")


def run(manifest, mesh, setting, seed, steps):
    cell = harness.resolve(manifest, CELL)
    cfg = cell.config
    for key, value in setting.items():
        where = cfg["optimizer"]["kwargs"] if key == "learning_rate" else cfg
        where[key] = value
    step = harness.load_module(manifest, "steps", cfg["step"])
    prog = step.programs(cell, mesh)
    k_init, k_data = jax.random.split(harness.seed_key(seed))
    state = jax.jit(prog.init, out_shardings=NamedSharding(mesh, P()))(k_init)
    ring = jax.jit(prog.batches, out_shardings=NamedSharding(
        mesh, P(mesh.axis_names)))(k_data)
    counts, losses, stamps = [], [], []
    *state, pending, counted = prog.step(*state, *ring[0])
    counts.append(counted)
    for i in range(1, steps + 1):           # one step in flight
        *state, nxt, counted = prog.step(*state, *ring[i % len(ring)])
        counts.append(counted)
        losses.append(float(pending))
        stamps.append(time.perf_counter())
        pending = nxt
    losses.append(float(pending))
    del state, ring
    held = np.stack([np.asarray(c) for c in counts])[:, :, 0]  # [steps, L]
    # the first interval holds the compile: leave it and the next out
    ms = [1e3 * (b - a) for a, b in zip(stamps[2:], stamps[3:])]
    routes = cell.traffic["seq"] * cfg["moe_num_active_primary_experts"]
    return {"setting": {"embedding_std": cfg["embedding_std"],
                        **cfg["optimizer"]["kwargs"]},
            "sizes": {k: cfg[k] for k in SIZES}, "seq": cell.traffic["seq"],
            "seed": seed, "steps": len(losses), "routes_a_layer": routes,
            "routes_held_first_step": held[0].tolist(),
            "routes_held_last_step": held[-1].tolist(),
            "routes_held_min": held.min(0).tolist(),
            "routes_held_max": held.max(0).tolist(),
            "step_ms_median": harness.percentile(ms, 50),
            "step_ms_p90": harness.percentile(ms, 90),
            "step_ms_first_ten": harness.percentile(ms[:10], 50),
            "step_ms_last_ten": harness.percentile(ms[-10:], 50),
            "loss_first": losses[0], "loss_last": losses[-1]}


def main(argv):
    device = chip()
    compilecache.enable_persistent_cache()   # a later seed finds the first's
    seeds, steps, settings = [], 80, []
    args = iter(argv)
    for a in args:
        if a == "--seed":
            seeds.append(int(next(args)))
        elif a == "--steps":
            steps = int(next(args))
        else:
            settings.append({k: json.loads(v) for k, v in (
                pair.split("=", 1) for pair in a.split(","))})
    mesh = mpi.init()
    manifest = harness.load_manifest()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    for setting in settings or [{}]:
        for seed in seeds or [1]:
            line = json.dumps({
                "device_kind": device.device_kind,
                "platform": device.platform,
                **run(manifest, mesh, setting, seed, steps)})
            print(line, flush=True)
            with open(OUT, "a") as f:
                f.write(line + "\n")
    mpi.stop()


if __name__ == "__main__":
    main(sys.argv[1:])
