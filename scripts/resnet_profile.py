#!/usr/bin/env python
"""ResNet-50 train-step profile: capture + top-time-sink table.

VERDICT r4 #3: the headline trains at MFU 0.317 with no committed
breakdown of where the other 68% goes.  This script runs the exact
stage-D train step (same recipes/batch/image as bench.py), captures a
``jax.profiler`` trace of warm steps, and reduces the busiest device
lane to a category/op time table — the evidence a layout/fusion/input
fix must be justified against, or the ceiling statement if the
remainder is conv-inherent.

Run on a LIVE window (the watcher invokes it after the cheaper bank
steps): ``python scripts/resnet_profile.py``.  On a non-TPU platform it
shrinks to smoke shapes so the capture+parse pipeline stays testable.
Artifacts: ``docs/artifacts/resnet_profile_<stamp>.{json,md}``.
"""

import argparse
import collections
import glob
import gzip
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
ART = os.path.join(REPO, "docs", "artifacts")


def log(*a):
    print(time.strftime("[%H:%M:%S]"), *a, file=sys.stderr, flush=True)


def categorize(name: str) -> str:
    n = name.lower()
    if n.startswith(("convolution", "conv")) or ".conv" in n:
        return "convolution"
    if "all-reduce" in n or "allreduce" in n:
        return "all-reduce"
    if n.startswith("fusion"):
        return "fusion (elementwise/BN/loss)"
    if n.startswith(("copy", "transpose", "convert", "bitcast", "reshape")):
        return "data movement"
    if n.startswith(("dot", "cublas", "gemm")):
        return "matmul"
    if n.startswith(("reduce", "scatter", "gather", "select", "dynamic")):
        return "reduce/scatter/gather"
    return "other"


def analyze(trace_glob: str) -> dict:
    """Reduce the busiest device lane of the newest trace to category +
    per-op totals (same perfetto-JSON surface benchmarks/
    overlap_analyze.py parses)."""
    paths = sorted(glob.glob(trace_glob, recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return {"error": f"no trace under {trace_glob}"}
    path = paths[-1]
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    ev = [e for e in data.get("traceEvents", [])
          if e.get("ph") == "X" and e.get("dur") is not None
          and not e.get("name", "").startswith("end:")]
    lanes = collections.defaultdict(list)
    for e in ev:
        lanes[(e.get("pid"), e.get("tid"))].append(e)
    if not lanes:
        return {"error": "no complete events in trace", "trace": path}

    # Prefer the lane that looks like the XLA device-op stream (most
    # time in recognizable op categories); the merely-busiest lane can
    # be the Python host thread (PjitFunction/fence frames), which says
    # nothing about where device time goes.
    def xla_score(l):
        return sum(e["dur"] for e in l
                   if categorize(e["name"]) != "other")

    lane = max(lanes.values(), key=xla_score)
    if xla_score(lane) == 0:
        lane = max(lanes.values(),
                   key=lambda l: sum(e["dur"] for e in l))
    total_us = sum(e["dur"] for e in lane)
    by_op = collections.Counter()
    by_cat = collections.Counter()
    for e in lane:
        by_op[e["name"]] += e["dur"]
        by_cat[categorize(e["name"])] += e["dur"]
    top_ops = [{"op": n[:120], "ms": round(us / 1e3, 3),
                "pct": round(100.0 * us / total_us, 2)}
               for n, us in by_op.most_common(10)]
    cats = [{"category": c, "ms": round(us / 1e3, 3),
             "pct": round(100.0 * us / total_us, 2)}
            for c, us in by_cat.most_common()]
    return {"trace": path, "lane_busy_ms": round(total_us / 1e3, 3),
            "lane_events": len(lane), "categories": cats,
            "top_ops": top_ops}


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--force-full", action="store_true",
                   help="full stage-D shapes even off-TPU")
    args = p.parse_args()

    # The same smoke knob bench.py honors forces a simulated CPU mesh.
    cpu_n = int(os.environ.get("TORCHMPI_TPU_BENCH_CPU", "0"))
    if cpu_n:
        from torchmpi_tpu.utils.simulation import force_cpu_devices

        force_cpu_devices(cpu_n)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import ResNet50
    from torchmpi_tpu.utils import compilecache, tracing
    from torchmpi_tpu.utils.metrics import fence

    compilecache.enable_persistent_cache()
    mesh = mpi.init()
    n_dev = mpi.device_count()
    platform = jax.devices()[0].platform
    full = platform == "tpu" or args.force_full
    BATCH, IMAGE = (128, 224) if full else (4, 64)
    batch = BATCH * n_dev
    log(f"platform={platform} devices={n_dev} batch/chip={BATCH} "
        f"image={IMAGE}")

    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P(mesh.axis_names))
    init_dev = None
    if platform != "cpu":
        try:
            init_dev = jax.local_devices(backend="cpu")[0]
        except RuntimeError:
            pass

    model = ResNet50(dtype=jnp.bfloat16)
    with jax.default_device(init_dev):
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, IMAGE, IMAGE, 3)),
                               train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)
    dp_step = mpi.recipes.make_bn_dp_train_step(model, tx, mesh=mesh)
    params, opt_state, batch_stats = mpi.recipes.replicate_bn_state(
        params, opt_state, batch_stats, mesh=mesh)
    images = jax.device_put(
        np.random.RandomState(0).rand(batch, IMAGE, IMAGE, 3)
        .astype(np.float32), shard)
    labels = jax.device_put(
        np.random.RandomState(1).randint(0, 1000, size=batch)
        .astype(np.int32), shard)

    log("warmup/compile...")
    for _ in range(2):
        params, opt_state, batch_stats, loss = dp_step(
            params, opt_state, batch_stats, images, labels)
    fence(loss)

    stamp = time.strftime("%Y%m%d_%H%M%S")
    trace_dir = os.path.join("/tmp", f"resnet_trace_{stamp}")
    log(f"tracing {args.steps} warm steps -> {trace_dir}")
    t0 = time.time()
    with tracing.trace(trace_dir):
        for _ in range(args.steps):
            params, opt_state, batch_stats, loss = dp_step(
                params, opt_state, batch_stats, images, labels)
        fence(loss)
    wall = time.time() - t0

    rec = analyze(os.path.join(trace_dir, "**", "*.trace.json.gz"))
    rec.update({"platform": platform, "devices": n_dev,
                "batch_per_chip": BATCH, "image": IMAGE,
                "steps": args.steps,
                "wall_s": round(wall, 3),
                "img_s_chip": round(batch * args.steps / wall / n_dev, 1),
                "stamp": stamp})
    # Committed artifacts are hardware evidence; CPU smoke output stays
    # in /tmp so a pipeline test can't masquerade as a profile.
    out_dir = ART if full else "/tmp"
    os.makedirs(out_dir, exist_ok=True)
    out_json = os.path.join(out_dir, f"resnet_profile_{stamp}.json")
    with open(out_json, "w") as f:
        json.dump(rec, f, indent=1)
    # Markdown table for the committed evidence.
    out_md = os.path.join(out_dir, f"resnet_profile_{stamp}.md")
    with open(out_md, "w") as f:
        f.write(f"# ResNet-50 train-step profile ({stamp})\n\n"
                f"platform={platform} devices={n_dev} "
                f"batch/chip={BATCH} image={IMAGE} steps={args.steps} "
                f"throughput={rec['img_s_chip']} img/s/chip\n\n")
        if "categories" in rec:
            f.write("| category | ms | % of lane |\n|---|---|---|\n")
            for c in rec["categories"]:
                f.write(f"| {c['category']} | {c['ms']} | {c['pct']} |\n")
            f.write("\n| top op | ms | % |\n|---|---|---|\n")
            for o in rec["top_ops"][:5]:
                f.write(f"| `{o['op'][:80]}` | {o['ms']} | {o['pct']} |\n")
    print(json.dumps(rec))
    log(f"wrote {out_json} and {out_md}")


if __name__ == "__main__":
    main()
