"""Capture a profiler trace of backprop-overlapped gradient sync.

The artifact for SURVEY.md §8.4.3 / VERDICT round-1 item 8 / ROADMAP
item 1: the trace shows per-bucket allreduce launches interleaved with
backward compute (communication/computation overlap — the property the
reference's async per-layer hooks bought).

Two schedules:

- default: the *bucketed* post-backward sync (``n_buckets`` independent
  collectives inside one jit; XLA is free to overlap them).
- ``--overlap``: the *backprop-overlapped* schedule
  (``Config.gradsync_overlap="auto"`` — docs/OVERLAP.md): each
  reverse-parameter-order bucket's allreduce fires INSIDE the backward
  pass via ``gradsync.make_overlapped_grad_fn``, and the script turns
  on the obs flight recorder, reads back the per-bucket grads/launch
  events, and emits an **assertable summary line**::

      OVERLAP-SUMMARY {"schedule": "overlapped", "interleaved": true, ...}

  ``interleaved`` is the CPU-sim-checkable invariant (bucket 0's launch
  recorded before the last bucket's grads exist); the wall-clock win
  itself is hardware-only, as ever.

Run on hardware::

    python benchmarks/overlap_trace.py [--overlap] [--buckets 4]
        [--trace-dir DIR]

then open the trace.json.gz under ``<dir>/plugins/profile/`` in
ui.perfetto.dev or tensorboard.  On the simulated CPU mesh
(``--devices 8``) the trace validates the capture path and the summary
validates the schedule; overlap *timing* is only meaningful on real
chips.  ``--model resnet20`` keeps the CPU-sim run light (the tier-1
``overlap-smoke`` CI job drives exactly that).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def overlap_summary(obs, schedule: str) -> dict:
    """Fold the flight ring's overlap events into the assertable
    verdict: per-bucket first grads/launch seqs and whether the
    first-fired bucket's launch preceded the last-fired bucket's
    cotangents (the overlap invariant)."""
    ov = [(e[0], e[3], e[4]) for e in obs.recorder().events()
          if e[2] == "overlap"]  # (seq, stage, bucket)
    first_launch, first_grads = {}, {}
    for seq, stage, bucket in ov:
        d = first_launch if stage == "launch" else first_grads
        d.setdefault(bucket, seq)
    if not first_launch or not first_grads:
        return {"schedule": schedule, "interleaved": False, "buckets": 0,
                "note": "no overlap events recorded"}
    last = max(first_grads)
    interleaved = (last >= 1
                   and first_launch.get(0, 1 << 62) < first_grads[last])
    return {"schedule": schedule, "interleaved": bool(interleaved),
            "buckets": last + 1,
            "first_launch_seq": first_launch.get(0),
            "last_bucket_grads_seq": first_grads[last]}


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--devices", type=int, default=0)
    p.add_argument("--buckets", type=int, default=4)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--batch-per-chip", type=int, default=16)
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--model", choices=("resnet50", "resnet20"),
                   default="resnet50",
                   help="resnet20 keeps CPU-sim smoke runs light")
    p.add_argument("--overlap", action="store_true",
                   help="backprop-overlapped schedule "
                        "(gradsync_overlap=auto) + flight-recorder "
                        "summary (docs/OVERLAP.md)")
    p.add_argument("--trace-dir", default="/tmp/torchmpi_tpu_overlap_trace")
    args = p.parse_args()
    if args.devices:
        from torchmpi_tpu.utils.simulation import force_cpu_devices

        force_cpu_devices(args.devices)

    import glob

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import ResNet20, ResNet50
    from torchmpi_tpu.utils import tracing
    from torchmpi_tpu.utils.metrics import fence

    cfg = mpi.Config()
    if args.overlap:
        cfg.gradsync_overlap = "auto"
        # The flight recorder is the evidence channel for the summary.
        if cfg.obs == "off":
            cfg.obs = "metrics"
    mesh = mpi.init(cfg)
    n_dev = mpi.device_count()
    n_classes = 1000 if args.model == "resnet50" else 10
    model = (ResNet50(dtype=jnp.bfloat16) if args.model == "resnet50"
             else ResNet20(num_classes=n_classes))
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, args.image_size, args.image_size,
                                      3)), train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    if args.overlap:
        # Let --buckets govern the overlapped schedule too: bound each
        # bucket to ~1/buckets of the gradient payload (otherwise a
        # small model fits one tuning-plan bucket and there is nothing
        # to interleave).
        total = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                    for l in jax.tree.leaves(params))
        mpi.set_config(gradsync_overlap_bytes=max(
            1, -(-total // max(1, args.buckets))))
    dp_step = mpi.recipes.make_bn_dp_train_step(
        model, tx, mesh=mesh, n_buckets=args.buckets,
        overlap="auto" if args.overlap else "off")
    params, opt_state, batch_stats = mpi.recipes.replicate_bn_state(
        params, tx.init(params), batch_stats, mesh=mesh)
    batch = args.batch_per_chip * n_dev
    shard = NamedSharding(mesh, P(mesh.axis_names))
    X = jax.device_put(np.random.RandomState(0).rand(
        batch, args.image_size, args.image_size, 3).astype(np.float32),
        shard)
    Y = jax.device_put(np.random.RandomState(1).randint(
        0, n_classes, size=batch).astype(np.int32), shard)

    # compile outside the trace so the capture is steps only
    params, opt_state, batch_stats, loss = dp_step(params, opt_state,
                                                   batch_stats, X, Y)
    fence(loss)
    if args.overlap:
        from torchmpi_tpu import obs

        obs.reset()  # summarize the traced steps only
    with tracing.trace(args.trace_dir) as d:
        for _ in range(args.steps):
            params, opt_state, batch_stats, loss = dp_step(
                params, opt_state, batch_stats, X, Y)
        fence(loss)
    artifacts = glob.glob(os.path.join(d, "**", "*.json.gz"),
                          recursive=True)
    print(f"trace captured: {artifacts or d} "
          f"(model={args.model}, buckets={args.buckets}, "
          f"devices={n_dev}, "
          f"schedule={'overlapped' if args.overlap else 'bucketed'})")
    if args.overlap:
        from torchmpi_tpu import obs

        print("OVERLAP-SUMMARY " + json.dumps(
            overlap_summary(obs, "overlapped")))
    mpi.stop()


if __name__ == "__main__":
    main()
