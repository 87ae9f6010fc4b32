"""Knob autotuner: measure, then recommend a ``Config`` for this platform.

The reference shipped hand-tuned constants (chunk sizes, size cutovers for
stock-vs-custom collectives — SURVEY.md §6.6's chunk/buffer-size setters);
this harness derives them empirically instead:

1. allreduce backend per size — sweep xla vs pallas (vs hierarchical on
   multi-slice meshes) and find the measured ``custom_min_bytes`` cutover;
2. ``chunk_bytes`` — sweep the streaming-ring subchunk size at a
   gradient-sized payload;
3. ``gradsync_buckets`` — sweep bucket counts on the ResNet-20 DP step
   (reuses scaling_bench's sweep at a single mesh size);
4./5. Pallas kernel tilings (flash attention, fused xent) on real TPU.

Measurement discipline (single-trial timings cannot resolve knob deltas
below the per-dispatch jitter — ten contradictory recommendations are
worse than one with error bars): every
candidate is timed over ``--rounds`` (default 5) fenced rounds and scored
by the MEDIAN; the per-candidate jitter (half the inter-quartile range)
is printed with every measurement; and a NOISE GATE keeps the
config-default value unless a challenger beats it by more than the
combined jitter of the two.  A re-run therefore agrees with itself:
within-noise knobs stay at their defaults instead of flapping.  The
discipline itself lives in ``torchmpi_tpu.tuning.measure`` (structured
``TimedResult`` from ``utils/metrics.timed`` + ``noise_gate``) — the
same library the online ``backend="auto"`` selector uses; this harness
just drives it over the full knob grid.

Prints one JSON line per measurement plus a final ``recommend`` line that
can be applied directly::

    rec = json.loads(last_line)["config"]
    mpi.init(mpi.Config(**rec))

The recommend line carries ``evidence`` per knob: chosen vs default
medians, the delta, and the jitter the delta had to clear.

``--plan-out PATH`` additionally writes the backend sweep into a
versioned tuning-plan file — one entry per (op, size bucket) at this
platform/mesh — that
``mpi.init(Config(backend="auto", tuning_plan_path=PATH))`` replays
directly, so the offline sweep seeds the online plan DB.  (The plan
drives selection only where a backend resolves to ``"auto"``; a plan
path alone loads the file and logs that it is inactive.)

On the CPU-simulated mesh the absolute numbers are meaningless but the
harness (and its JSON contract) is identical to what runs on a real slice.

Run: ``python benchmarks/autotune.py [--devices 8] [--quick] [--rounds 5]
[--plan-out plans.json]``
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROUNDS = 5  # set from --rounds in main()


def _measure(fn, iters, fence):
    """Structured TimedResult (median/jitter/rounds attached) over
    ROUNDS fenced timing rounds of ``iters`` dispatches after one
    warm/compile call — tuning.measure's discipline at this module's
    round count."""
    from torchmpi_tpu.tuning import measure as tmeasure

    return tmeasure.measure(fn, iters=iters, rounds=ROUNDS, fence=fence)


def _ms(res):
    from torchmpi_tpu.tuning import measure as tmeasure

    return tmeasure.result_ms(res)


def _gate(cands, default_key):
    """Noise-gated argmin (tuning.measure.noise_gate): the config
    default wins unless a challenger beats it beyond the pair's
    combined jitter — the anti-flap rule that makes re-runs agree."""
    from torchmpi_tpu.tuning import measure as tmeasure

    return tmeasure.noise_gate(cands, default_key)


def main():
    import functools
    global print, ROUNDS
    print = functools.partial(print, flush=True)
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--devices", type=int, default=0,
                   help="force N simulated CPU devices")
    p.add_argument("--dcn", type=int, default=None)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--rounds", type=int, default=5,
                   help="timing rounds per candidate (median scored)")
    p.add_argument("--quick", action="store_true",
                   help="tiny sweep (CI smoke)")
    p.add_argument("--plan-out", default=None, metavar="PATH",
                   help="write the backend sweep as a tuning-plan file "
                        "(loadable via Config.tuning_plan_path / "
                        "backend='auto')")
    args = p.parse_args()
    ROUNDS = args.rounds
    if args.devices:
        from torchmpi_tpu.utils.simulation import force_cpu_devices

        force_cpu_devices(args.devices)

    import numpy as np

    import torchmpi_tpu as mpi
    from torchmpi_tpu.ops import ring
    from torchmpi_tpu.utils.metrics import fence

    mesh = mpi.init(mpi.Config(dcn_size=args.dcn, custom_min_bytes=0))
    n = mpi.device_count()
    is_cpu = list(mesh.devices.flat)[0].platform == "cpu"
    if is_cpu:
        from jax.experimental.pallas import tpu as pltpu

        ring.set_interpret(pltpu.InterpretParams())

    defaults = mpi.Config()  # the values the noise gate protects
    rec = {}
    evidence = {}

    # -- 1. backend cutover ------------------------------------------------
    sizes = ([1 << 14, 1 << 17] if args.quick
             else [1 << 14, 1 << 17, 1 << 20, 1 << 24])
    cutover = None
    last = {}
    plan_sweep = []  # (per_rank_bytes, cands) per size, for --plan-out
    for nbytes in sizes:
        x = np.random.RandomState(0).rand(n, nbytes // 4).astype(np.float32)
        cands = {}
        backends = ["xla", "pallas"]
        if mesh.shape.get("dcn", 1) > 1:
            backends.append("hierarchical")  # the multi-slice 2-level path
        for backend in backends:
            if backend == "pallas" and is_cpu and nbytes > 1 << 14:
                continue  # interpreter too slow at size
            try:
                mpi.collectives.clear_cache()
                cands[backend] = _measure(
                    lambda b=backend: mpi.allreduce(x, backend=b),
                    args.iters, fence)
            except Exception as e:  # noqa: BLE001 — record and continue
                print(json.dumps({"phase": "backend", "bytes": nbytes,
                                  "backend": backend,
                                  "error": str(e)[:120]}))
                continue
            print(json.dumps({"phase": "backend", "per_rank_bytes": nbytes,
                              "backend": backend, **_ms(cands[backend])}))
        # Noise-gated per size: pallas must beat xla beyond the pair's
        # jitter to set the cutover here.  Gated on the {xla, pallas}
        # PAIR: a hierarchical win at this size must not mask a
        # beyond-noise pallas-over-xla cutover (code review r4).
        pair = {k: v for k, v in cands.items() if k in ("xla", "pallas")}
        winner, ev = _gate(pair, "xla")
        if winner == "pallas" and cutover is None:
            cutover = nbytes
            evidence["custom_min_bytes"] = {"at_bytes": nbytes, **ev}
        last = cands
        plan_sweep.append((nbytes, cands))
    winner, ev = _gate(last, "xla")
    if winner == "hierarchical":
        # Two-level wins at gradient scale on this multi-slice mesh.
        # custom_min_bytes must be 0: the selector applies the cutover to
        # every non-xla config-default backend, so a huge cutover would
        # silently route everything back to xla.
        rec["backend"] = "hierarchical"
        rec["custom_min_bytes"] = 0
        evidence["backend"] = ev
    elif cutover is not None:
        # The selector compares custom_min_bytes against PER-RANK bytes:
        # the eager plan picks on one rank's aval (`selector.pick`)
        # and the in-axis path picks on the local shard — so the measured
        # per-rank cutover is exactly the right knob value, unscaled.
        rec["backend"] = "pallas"
        rec["custom_min_bytes"] = cutover
    else:
        rec["backend"] = defaults.backend
        rec["custom_min_bytes"] = defaults.custom_min_bytes
        evidence.setdefault("backend", ev)

    # Seed the online plan DB from the sweep: one noise-gated entry per
    # (op, size bucket) at this platform/mesh, in the exact format
    # mpi.init(Config(tuning_plan_path=...)) / backend="auto" replays.
    if args.plan_out:
        from torchmpi_tpu import tuning as tlib

        cache = tlib.PlanCache(args.plan_out)
        for nbytes, cands in plan_sweep:
            if not cands:
                continue
            w, _ev = _gate(cands, "xla")
            cache.put(
                tlib.make_fingerprint("allreduce", nbytes, "float32", mesh),
                tlib.PlanEntry(
                    backend=str(w), source="autotune",
                    median_ms={b: round(r.median * 1e3, 4)
                               for b, r in cands.items()},
                    jitter_ms={b: round(r.jitter * 1e3, 4)
                               for b, r in cands.items()},
                    rounds=ROUNDS))
        saved = cache.save(args.plan_out)
        print(json.dumps({"phase": "plan_out", "path": args.plan_out,
                          "entries": len(cache), "saved": saved}))

    # -- 2. chunk_bytes ----------------------------------------------------
    if not is_cpu:  # streaming ring needs real lowering to mean anything
        payload = 1 << 26  # 64 MiB: gradient-scale
        x = np.random.RandomState(1).rand(n, payload // 4).astype(np.float32)
        cands = {}
        for cb in (1 << 20, 1 << 22, 1 << 24):
            mpi.set_config(chunk_bytes=cb, custom_min_bytes=0)
            try:
                cands[cb] = _measure(
                    lambda: mpi.allreduce(x, backend="pallas"),
                    args.iters, fence)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"phase": "chunk", "chunk_bytes": cb,
                                  "error": str(e)[:120]}))
                continue
            print(json.dumps({"phase": "chunk", "chunk_bytes": cb,
                              **_ms(cands[cb])}))
        if cands:
            chosen, ev = _gate(cands, defaults.chunk_bytes)
            rec["chunk_bytes"] = chosen
            evidence["chunk_bytes"] = ev

    # -- 3. gradsync buckets ----------------------------------------------
    # Sweep under the configuration phases 1-2 actually recommend, not the
    # leftovers of their last sweep iteration.
    mpi.set_config(backend=rec["backend"],
                   custom_min_bytes=rec["custom_min_bytes"],
                   **({"chunk_bytes": rec["chunk_bytes"]}
                      if "chunk_bytes" in rec else {}))
    import jax
    import jax.numpy as jnp
    import optax

    from torchmpi_tpu.models import ResNet20

    model = ResNet20(num_classes=10)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 32, 3)), train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    bsz = (2 if args.quick else 8) * n
    img = np.random.RandomState(2).rand(bsz, 32, 32, 3).astype(np.float32)
    lab = np.random.RandomState(3).randint(0, 10, bsz).astype(np.int32)
    cands = {}
    for nb in ((1, 4) if args.quick else (1, 2, 4, 8, 16)):
        # barrier=True only matters with >1 bucket: it is the lever that
        # keeps buckets distinct through XLA's combiner (see
        # overlap_analyze.py), so measure both scheduling modes.
        for barrier in ((False, True) if nb > 1 else (False,)):
            mpi.set_config(gradsync_buckets=nb, gradsync_barrier=barrier)
            step = mpi.recipes.make_bn_dp_train_step(model, tx, mesh=mesh,
                                                     donate=False)
            p2, o2, b2 = mpi.recipes.replicate_bn_state(
                params, tx.init(params), batch_stats, mesh=mesh)

            def run(p2=p2, o2=o2, b2=b2, step=step):
                return step(p2, o2, b2, img, lab)[3]

            cands[(nb, barrier)] = _measure(run, max(2, args.iters // 2),
                                            fence)
            print(json.dumps({"phase": "buckets", "buckets": nb,
                              "barrier": barrier,
                              **_ms(cands[(nb, barrier)])}))
    chosen, ev = _gate(cands, (defaults.gradsync_buckets,
                               defaults.gradsync_barrier))
    rec["gradsync_buckets"], rec["gradsync_barrier"] = chosen
    evidence["gradsync_buckets"] = ev

    # -- 4. flash-attention block sizes (real TPU only: Mosaic tiling) ----
    # Timed through value_and_grad over flash_attention_grad — the
    # training path the knobs primarily serve — so a tiling that wins the
    # forward but loses the backward kernel cannot be recommended.
    if not is_cpu:
        from torchmpi_tpu.ops.flash import flash_attention_grad

        Bf, Tf, Hf, Df = 2, (1024 if args.quick else 4096), 8, 128
        rngf = np.random.RandomState(4)
        qkv = [jnp.asarray(rngf.randn(Bf, Tf, Hf, Df), jnp.bfloat16)
               for _ in range(3)]
        cands = {}
        # Quick grid includes the beyond-512 candidates (VERDICT r4 #2):
        # the full-block mask-skip specialization shifted the VPU:MXU
        # balance, so the 512x512 plateau must be re-derived.
        grid = ((256, 256), (512, 512), (1024, 512),
                (512, 1024)) if args.quick else \
            ((128, 128), (256, 256), (512, 256), (256, 512), (512, 512),
             (512, 1024), (1024, 512), (1024, 1024), (2048, 512),
             (768, 512))
        for bq, bk in grid:
            try:
                def fwd_bwd(q, k, v, bq=bq, bk=bk):
                    def loss(q, k, v):
                        o = flash_attention_grad(q, k, v, causal=True,
                                                 block_q=bq, block_k=bk)
                        return jnp.sum(o.astype(jnp.float32) ** 2)

                    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

                f = jax.jit(fwd_bwd)
                cands[(bq, bk)] = _measure(lambda: f(*qkv), args.iters,
                                           fence)
            except Exception as e:  # noqa: BLE001 — invalid tiling, skip
                print(json.dumps({"phase": "flash_blocks",
                                  "block_q": bq, "block_k": bk,
                                  "error": str(e)[:120]}))
                continue
            print(json.dumps({"phase": "flash_blocks", "block_q": bq,
                              "block_k": bk, **_ms(cands[(bq, bk)])}))
        if cands:
            chosen, ev = _gate(cands, (defaults.flash_block_q,
                                       defaults.flash_block_k))
            rec["flash_block_q"], rec["flash_block_k"] = chosen
            evidence["flash_blocks"] = ev
        del qkv

    # -- 5. fused-xent block sizes (real TPU only) -------------------------
    if not is_cpu:
        from torchmpi_tpu.ops.xent import fused_linear_cross_entropy

        Nx, Ex, Vx = (2048 if args.quick else 8192), 1024, 32768
        rngx = np.random.RandomState(5)
        xx = jnp.asarray(rngx.randn(Nx, Ex) * 0.05, jnp.bfloat16)
        wx = jnp.asarray(rngx.randn(Ex, Vx) * 0.05, jnp.bfloat16)
        lx = jnp.asarray(rngx.randint(0, Vx, size=Nx), jnp.int32)
        cands = {}
        grid = ((128, 512), (256, 512)) if args.quick else \
            ((128, 512), (128, 1024), (256, 512), (256, 1024), (512, 512))
        for bn, bv in grid:
            try:
                f = jax.jit(lambda x, w, l, bn=bn, bv=bv:
                            fused_linear_cross_entropy(
                                x, w, l, block_n=bn, block_v=bv).mean())
                cands[(bn, bv)] = _measure(lambda: f(xx, wx, lx),
                                           args.iters, fence)
            except Exception as e:  # noqa: BLE001 — invalid tiling, skip
                print(json.dumps({"phase": "xent_blocks", "block_n": bn,
                                  "block_v": bv, "error": str(e)[:120]}))
                continue
            print(json.dumps({"phase": "xent_blocks", "block_n": bn,
                              "block_v": bv, **_ms(cands[(bn, bv)])}))
        if cands:
            chosen, ev = _gate(cands, (defaults.xent_block_n,
                                       defaults.xent_block_v))
            rec["xent_block_n"], rec["xent_block_v"] = chosen
            evidence["xent_blocks"] = ev
        del xx, wx, lx

    print(json.dumps({"recommend": True,
                      "platform": "cpu-sim" if is_cpu else "tpu",
                      "devices": n, "rounds": ROUNDS,
                      "noise_gated": True,
                      "config": rec, "evidence": evidence}))
    mpi.stop()


if __name__ == "__main__":
    main()
