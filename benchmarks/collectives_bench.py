"""Collective micro-benchmark: allreduce + broadcast sweeps across backends.

Reference analog: ``benchmarks/*.lua`` (SURVEY.md §3 C14, reconstructed —
reference mount empty): sweep message sizes, report effective bus bandwidth
(``algbw * 2(n-1)/n`` for allreduce; ``bytes/time`` for broadcast), compare
implementations — the reference compared stock MPI vs NCCL vs its custom
chunked algorithms; here we compare ``xla`` vs ``hierarchical`` vs
``pallas``.  Broadcast is benchmarked next to allreduce because its
pipelined-chain schedule should reach ~2x the allreduce wire efficiency
(~size vs ~2*size bytes moved per device; VERDICT round 1 item 6).

The BASELINE target is this sweep measured from 8 to 256 chips on a real
pod; on the simulated CPU mesh the numbers exercise the same code paths and
validate relative behavior, and on any real multi-chip slice this script
measures the real thing unchanged.

``--pytree`` switches to the fused-pytree mode: a mixed fp32/bf16
parameter-tree allreduce (the gradsync hot path), measured per-leaf
(``fuse_max_bytes=0``) vs fused (dtype-grouped coalescing,
torchmpi_tpu/fusion.py), reporting collective launches/step from the
lowered HLO alongside wall time — the launch-count half is the
statically verifiable win, on CPU or TPU alike.

``--overlap-compare`` measures the gradsync *schedule*: the same
mixed-dtype MLP step with the post-backward sync vs the
backprop-overlapped schedule (``gradsync.make_overlapped_grad_fn`` —
docs/OVERLAP.md), reporting launches/step from the lowered HLO, wall
time, and a gradients-bitwise-equal check — the capturable evidence the
ROADMAP bench watch-item requires for a perf claim (the wall-clock win
itself is hardware-only on the CPU sim).

Run: ``python benchmarks/collectives_bench.py --devices 8 [--dcn 2]``
Or:  ``python benchmarks/collectives_bench.py --devices 8 --pytree``
Or:  ``python benchmarks/collectives_bench.py --devices 8 --overlap-compare``
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _maybe_bank(args, kind, summary):
    """Persist a ``KIND-SUMMARY`` line under ``--bank`` (stamped,
    git-pinned, platform-tagged — benchmarks/banking.py) so the
    verdict outlives the CI log it was grepped from."""
    if not getattr(args, "bank", False):
        return
    from benchmarks import banking

    rec = banking.bank_summary(kind, summary,
                               round=getattr(args, "round", None))
    print(f"# banked {kind} stamp={rec['stamp']} "
          f"commit={rec['commit']} platform={rec['platform']} -> "
          f"{banking.DEFAULT_PATH}", file=sys.stderr)


def _pytree_mode(args, mpi, mesh, sizes):
    """Fused vs per-leaf pytree allreduce: launches/step (from the
    lowered HLO — the statically verifiable win) and wall time."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    import time

    axes = tuple(mesh.axis_names)
    fuse_default = (args.fuse_bytes if args.fuse_bytes is not None
                    else mpi.Config().fuse_max_bytes)
    rng = np.random.RandomState(0)
    for nbytes in sizes:
        # ~equal-bytes leaves alternating fp32/bf16 (a mixed-precision
        # transformer tree's shape: many small tensors, two dtypes).
        per_leaf = max(8, nbytes // max(1, args.leaves) // 4)
        tree = {
            f"p{i:03d}": jnp.asarray(
                rng.randn(per_leaf),
                np.float32 if i % 2 == 0 else jnp.bfloat16)
            for i in range(args.leaves)
        }
        # Report the tree's REAL payload (bf16 leaves are 2 B/elem, so
        # it is ~3/4 of the requested --sizes figure).
        tree_bytes = sum(v.size * v.dtype.itemsize for v in tree.values())
        rows = []
        for mode, fuse_bytes in (("per-leaf", 0), ("fused", fuse_default)):
            mpi.set_config(fuse_max_bytes=fuse_bytes)

            def body(t):
                return mpi.collectives.allreduce_in_axis(t, axes, op="sum")

            fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False))
            launches = fn.lower(tree).as_text().count(
                "stablehlo.all_reduce")
            out = fn(tree)  # compile
            jax.block_until_ready(out)
            t0 = time.time()
            for _ in range(args.iters):
                out = fn(tree)
            jax.block_until_ready(out)
            dt = (time.time() - t0) / args.iters
            rows.append((mode, launches, dt))
            line = {"op": "allreduce_pytree", "mode": mode,
                    "leaves": args.leaves, "bytes": tree_bytes,
                    "fuse_max_bytes": fuse_bytes, "launches": launches,
                    "ms": round(dt * 1e3, 3)}
            if args.json:
                print(json.dumps(line))
            else:
                print(f"allreduce_pytree {mode:9s} {args.leaves:4d} leaves "
                      f"{tree_bytes:>12d} B  {launches:4d} launches/step  "
                      f"{dt*1e3:8.2f} ms")
        (m0, l0, t0_), (m1, l1, t1_) = rows
        if not args.json:
            print(f"# {l0} -> {l1} launches ({l0 / max(1, l1):.0f}x fewer), "
                  f"{t0_ / max(t1_, 1e-12):.2f}x wall-time ratio "
                  f"(per-leaf/fused)")


def _obs_compare_mode(args, mpi, n):
    """Eager-dispatch overhead of the telemetry layer: the same small
    allreduce timed under obs=off / metrics / trace (docs/OBSERVABILITY
    acceptance: off->metrics must sit within the timing noise floor).
    Small payload on purpose — the Python dispatch path is what the obs
    branch sits on; large tensors would bury it under transfer time."""
    import numpy as np

    from torchmpi_tpu.utils import metrics as umetrics

    x = np.random.RandomState(0).rand(n, 1024).astype(np.float32)
    results = {}
    for mode in ("off", "metrics", "trace"):
        mpi.set_config(obs=mode)  # clears the eager jit cache
        mpi.allreduce(x)  # re-warm the executable under this mode
        results[mode] = umetrics.timed(lambda: mpi.allreduce(x),
                                       iters=args.iters, rounds=5)
        r = results[mode]
        line = {"mode": mode, "us_per_dispatch": round(r.median * 1e6, 2),
                "jitter_us": round(r.jitter * 1e6, 2)}
        print(json.dumps(line) if args.json else
              f"obs={mode:8s} {r.median * 1e6:9.2f} us/dispatch "
              f"(jitter {r.jitter * 1e6:.2f} us)")
    mpi.set_config(obs="off")
    base, m = results["off"], results["metrics"]
    delta = m.median - base.median
    floor = base.jitter + m.jitter
    verdict = "WITHIN NOISE" if abs(delta) <= floor else "MEASURABLE"
    print(f"# metrics-vs-off delta {delta * 1e6:+.2f} us "
          f"(noise floor {floor * 1e6:.2f} us): {verdict}",
          file=sys.stderr)
    summary = {
        "off_us": round(base.median * 1e6, 2),
        "metrics_us": round(m.median * 1e6, 2),
        "trace_us": round(results["trace"].median * 1e6, 2),
        "delta_us": round(delta * 1e6, 2),
        "noise_floor_us": round(floor * 1e6, 2),
        "within_noise": bool(abs(delta) <= floor),
    }
    print("OBS-SUMMARY " + json.dumps(summary))
    _maybe_bank(args, "OBS-SUMMARY", summary)


def _faults_compare_mode(args, mpi, n):
    """Dispatch overhead of the fault layer on its instrumented hot
    path: the same small STAGED allreduce (the eager surface that
    carries the ``Config.faults`` branch + policy wrapper) timed under
    faults=off / policy (docs/FAULTS.md acceptance: off->policy must
    sit within the same noise floor --obs-compare establishes for the
    telemetry branch).  Policy-only on purpose — injection would
    measure the injected faults, not the dispatch."""
    import numpy as np

    from torchmpi_tpu.utils import metrics as umetrics

    x = np.random.RandomState(0).rand(n, 1024).astype(np.float32)
    results = {}
    for mode in ("off", "policy"):
        mpi.set_config(faults=mode)
        mpi.allreduce(x, backend="host")  # warm the placement path
        results[mode] = umetrics.timed(
            lambda: mpi.allreduce(x, backend="host"),
            iters=args.iters, rounds=5)
        r = results[mode]
        line = {"mode": mode, "us_per_dispatch": round(r.median * 1e6, 2),
                "jitter_us": round(r.jitter * 1e6, 2)}
        print(json.dumps(line) if args.json else
              f"faults={mode:7s} {r.median * 1e6:9.2f} us/dispatch "
              f"(jitter {r.jitter * 1e6:.2f} us)")
    mpi.set_config(faults="off")
    base, pol = results["off"], results["policy"]
    delta = pol.median - base.median
    floor = base.jitter + pol.jitter
    verdict = "WITHIN NOISE" if abs(delta) <= floor else "MEASURABLE"
    print(f"# policy-vs-off delta {delta * 1e6:+.2f} us "
          f"(noise floor {floor * 1e6:.2f} us): {verdict}",
          file=sys.stderr)
    summary = {
        "off_us": round(base.median * 1e6, 2),
        "policy_us": round(pol.median * 1e6, 2),
        "delta_us": round(delta * 1e6, 2),
        "noise_floor_us": round(floor * 1e6, 2),
        "within_noise": bool(abs(delta) <= floor),
    }
    print("FAULTS-SUMMARY " + json.dumps(summary))
    _maybe_bank(args, "FAULTS-SUMMARY", summary)


def _watchdog_compare_mode(args, mpi, n):
    """Dispatch overhead of the collective watchdog on its instrumented
    hot path: the same small STAGED allreduce (the eager surface whose
    planned replay carries the begin/end in-flight window when armed)
    timed under watchdog=off / warn / break (docs/WATCHDOG.md
    acceptance: off->break must sit within the same noise floor the
    obs/faults branches establish).  No stalls injected — a stall
    would measure the stall, not the monitor."""
    import numpy as np

    from torchmpi_tpu.utils import metrics as umetrics

    x = np.random.RandomState(0).rand(n, 1024).astype(np.float32)
    modes = ("off", "warn", "break")
    # INTERLEAVED passes: measuring each mode in one sequential block
    # lets container load drift between blocks dominate the ~tens-of-us
    # signal (observed: the off/break delta flips sign run to run).
    # Alternating the modes per pass puts every mode under the same
    # drift; the per-mode median-of-passes is then comparable.
    samples = {m: [] for m in modes}
    for _ in range(4):
        for mode in modes:
            mpi.set_config(watchdog=mode)  # clears the plan table
            mpi.allreduce(x, backend="host")  # re-plan under this mode
            samples[mode].append(umetrics.timed(
                lambda: mpi.allreduce(x, backend="host"),
                iters=args.iters, rounds=3))
    mpi.set_config(watchdog="off")

    def med(vals):
        s = sorted(vals)
        return s[len(s) // 2]

    results = {}
    for mode in modes:
        m_us = med([r.median for r in samples[mode]]) * 1e6
        j_us = med([r.jitter for r in samples[mode]]) * 1e6
        results[mode] = (m_us, j_us)
        line = {"mode": mode, "us_per_dispatch": round(m_us, 2),
                "jitter_us": round(j_us, 2)}
        print(json.dumps(line) if args.json else
              f"watchdog={mode:6s} {m_us:9.2f} us/dispatch "
              f"(jitter {j_us:.2f} us)")
    delta = results["break"][0] - results["off"][0]
    floor = results["off"][1] + results["break"][1]
    # One-sided on purpose: this is an OVERHEAD check — a negative
    # delta is measurement noise, not a speedup to report.
    verdict = "WITHIN NOISE" if delta <= floor else "MEASURABLE"
    print(f"# break-vs-off delta {delta:+.2f} us "
          f"(noise floor {floor:.2f} us): {verdict}",
          file=sys.stderr)
    summary = {
        "off_us": round(results["off"][0], 2),
        "warn_us": round(results["warn"][0], 2),
        "break_us": round(results["break"][0], 2),
        "delta_us": round(delta, 2),
        "noise_floor_us": round(floor, 2),
        "within_noise": bool(delta <= floor),
    }
    print("WATCHDOG-SUMMARY " + json.dumps(summary))
    _maybe_bank(args, "WATCHDOG-SUMMARY", summary)


def _guard_compare_mode(args, mpi, n):
    """Dispatch overhead of the guard layer (docs/GUARD.md), in two
    halves.  **wire**: the same small STAGED allreduce (the surface
    that carries the digest compute + verify) timed under
    guard=off/wire.  **numeric**: a jitted in-axis gradient sync timed
    under guard=off/numeric — the fused sum-of-squares tripwire is
    in-graph, so this measures the compiled-step cost, not Python
    dispatch.  Acceptance: overhead recorded on the CPU sim, expected
    small; documented either way (the GUARD-SUMMARY line is what the
    guard-smoke CI job archives)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from torchmpi_tpu.parallel import gradsync
    from torchmpi_tpu.utils import metrics as umetrics

    x = np.random.RandomState(0).rand(n, 1024).astype(np.float32)
    summary = {}
    for mode in ("off", "wire"):
        mpi.set_config(guard=mode)
        mpi.allreduce(x, backend="host")  # warm the placement path
        r = umetrics.timed(lambda: mpi.allreduce(x, backend="host"),
                           iters=args.iters, rounds=5)
        summary[f"wire_{mode}_us"] = round(r.median * 1e6, 2)
        summary[f"wire_{mode}_jitter_us"] = round(r.jitter * 1e6, 2)
        line = {"half": "wire", "mode": mode,
                "us_per_dispatch": summary[f"wire_{mode}_us"],
                "jitter_us": summary[f"wire_{mode}_jitter_us"]}
        print(json.dumps(line) if args.json else
              f"guard={mode:8s} staged {r.median * 1e6:9.2f} us/dispatch "
              f"(jitter {r.jitter * 1e6:.2f} us)")
    mesh = mpi.current_mesh()
    axes = mesh.axis_names
    grads = {"a": jnp.ones((256, 64), jnp.float32),
             "b": jnp.ones((1024,), jnp.float32)}
    for mode in ("off", "numeric"):
        mpi.set_config(guard=mode)
        sync = jax.jit(shard_map(
            lambda g: gradsync.synchronize_gradients(g, axes),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False))
        jax.block_until_ready(sync(grads))  # compile
        r = umetrics.timed(
            lambda: jax.block_until_ready(sync(grads)),
            iters=args.iters, rounds=5)
        summary[f"numeric_{mode}_us"] = round(r.median * 1e6, 2)
        summary[f"numeric_{mode}_jitter_us"] = round(r.jitter * 1e6, 2)
        line = {"half": "numeric", "mode": mode,
                "us_per_step": summary[f"numeric_{mode}_us"],
                "jitter_us": summary[f"numeric_{mode}_jitter_us"]}
        print(json.dumps(line) if args.json else
              f"guard={mode:8s} gradsync {r.median * 1e6:9.2f} us/step "
              f"(jitter {r.jitter * 1e6:.2f} us)")
    mpi.set_config(guard="off")
    for half in ("wire", "numeric"):
        on = "wire" if half == "wire" else "numeric"
        delta = summary[f"{half}_{on}_us"] - summary[f"{half}_off_us"]
        floor = (summary[f"{half}_off_jitter_us"]
                 + summary[f"{half}_{on}_jitter_us"])
        summary[f"{half}_delta_us"] = round(delta, 2)
        summary[f"{half}_verdict"] = ("WITHIN NOISE"
                                      if abs(delta) <= floor
                                      else "MEASURABLE")
        print(f"# {half} {on}-vs-off delta {delta:+.2f} us "
              f"(noise floor {floor:.2f} us): "
              f"{summary[f'{half}_verdict']}", file=sys.stderr)
    print("GUARD-SUMMARY " + json.dumps(summary))
    _maybe_bank(args, "GUARD-SUMMARY", summary)


def _overlap_compare_mode(args, mpi, mesh):
    """Sync vs backprop-overlapped gradient dispatch (docs/OVERLAP.md)
    on the same mixed fp32/bf16 MLP: per-step wall time, all-reduce
    launches from the lowered HLO, and a bitwise-equality check of the
    gradients — the statically verifiable halves of the overlap claim
    on CPU or TPU alike (the wall-clock win itself is hardware-only,
    like overlap_trace.py's timing)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from torchmpi_tpu.parallel import gradsync

    axes = tuple(mesh.axis_names)
    key = jax.random.PRNGKey(0)
    dim = args.overlap_dim
    params = {}
    for i in range(args.overlap_layers):
        dt = jnp.float32 if i % 2 == 0 else jnp.bfloat16
        params[f"l{i:02d}"] = {
            "w": jax.random.normal(key, (dim, dim)).astype(dt)}

    def loss_fn(p, x, y):
        h = x
        for i in range(args.overlap_layers):
            w = p[f"l{i:02d}"]["w"]
            h = jnp.tanh(h.astype(w.dtype) @ w)
        return jnp.mean((h.astype(jnp.float32) - y) ** 2)

    X = np.random.RandomState(0).rand(64, dim).astype(np.float32)
    Y = np.random.RandomState(1).rand(64, dim).astype(np.float32)
    per_bucket = dim * dim * 4  # one fp32 layer per bucket

    def step_sync(p, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        return loss, gradsync.synchronize_gradients(grads, axes)

    def step_overlap(p, x, y):
        return gradsync.make_overlapped_grad_fn(
            loss_fn, p, axes, max_bytes=per_bucket)(p, x, y)

    rows = []
    for mode, step in (("sync", step_sync), ("overlapped", step_overlap)):
        fn = jax.jit(shard_map(step, mesh=mesh,
                               in_specs=(P(), P(axes), P(axes)),
                               out_specs=(P(), P()), check_vma=False))
        launches = fn.lower(params, X, Y).as_text().count(
            "stablehlo.all_reduce")
        out = fn(params, X, Y)  # compile
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(args.iters):
            out = fn(params, X, Y)
        jax.block_until_ready(out)
        dt = (time.time() - t0) / args.iters
        rows.append((mode, launches, dt, out[1]))
        line = {"op": "gradsync", "mode": mode,
                "layers": args.overlap_layers, "launches": launches,
                "ms": round(dt * 1e3, 3)}
        print(json.dumps(line) if args.json else
              f"gradsync {mode:10s} {args.overlap_layers:3d} layers  "
              f"{launches:3d} launches/step  {dt * 1e3:8.2f} ms")
    (_, l0, t0_, g0), (_, l1, t1_, g1) = rows
    bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)))
    print(f"# overlapped-vs-sync: grads bitwise equal: {bitwise}; "
          f"{l0} -> {l1} launches; {t0_ / max(t1_, 1e-12):.2f}x wall-time "
          f"ratio (sync/overlapped — dispatch-structure evidence on "
          f"cpu-sim, wall-clock win is hardware-only)", file=sys.stderr)
    summary = {
        "layers": args.overlap_layers,
        "sync_launches": l0,
        "overlapped_launches": l1,
        "sync_ms": round(t0_ * 1e3, 3),
        "overlapped_ms": round(t1_ * 1e3, 3),
        "grads_bitwise_equal": bool(bitwise),
    }
    print("OVERLAP-SUMMARY " + json.dumps(summary))
    _maybe_bank(args, "OVERLAP-SUMMARY", summary)
    if not bitwise:
        raise SystemExit("overlap-compare: gradients diverged")


def _dcn_compare_mode(args, mpi, mesh):
    """Flat vs two-level vs two-level+codec allreduce on a simulated
    ``(dcn, ici)`` mesh (docs/HIERARCHICAL.md; ROADMAP item 4).

    The wall-clock win is hardware-only (cpu-sim has no bandwidth cliff
    between the emulated slices), so the CPU-assertable evidence is the
    DCN-leg **wire bytes** from the obs counters
    (``tm_dcn_wire_bytes_total`` — what one device actually puts on the
    inter-slice links): two-level moves ``1/ici_n`` of the flat payload,
    the int8 codec another ~1/4 of that.  Also asserted, and emitted as
    a ``DCN-SUMMARY`` JSON line for CI: chunked == unchunked bitwise,
    every mode allclose vs flat, the error-feedback running mean
    converging where single-shot quantization stays biased, and zero
    steady-state re-plans with topology-keyed plan entries.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from torchmpi_tpu import obs, planner
    from torchmpi_tpu.parallel import gradsync
    from torchmpi_tpu.utils.metrics import fence

    axes = tuple(mesh.axis_names)
    n_dcn = int(mesh.shape[axes[0]])
    n_ici = int(mesh.shape[axes[1]])
    if n_dcn <= 1:
        raise SystemExit("--dcn-compare needs a two-level mesh "
                         "(run with --dcn 2)")
    n = n_dcn * n_ici
    nbytes = args.dcn_bytes
    n_elems = nbytes // 4
    x = np.random.RandomState(0).rand(n, n_elems).astype(np.float32)
    mpi.set_config(obs="metrics", custom_min_bytes=0)

    def _wire(codec):
        snap = obs.registry().snapshot()
        return sum(c["value"] for c in snap
                   if c["name"] == "tm_dcn_wire_bytes_total"
                   and (codec is None or c["labels"].get("codec") == codec))

    rows = {}
    flat = None
    modes = [("flat", "xla", "off"), ("two-level", "hierarchical", "off"),
             ("two-level+bf16", "hierarchical", "bf16"),
             ("two-level+int8", "hierarchical", "int8")]
    for tag, backend, codec in modes:
        mpi.set_config(dcn_compress=codec, dcn_compress_min_bytes=0)
        label = codec if codec != "off" else (
            "none" if backend == "hierarchical" else None)
        before = _wire(label) if backend == "hierarchical" else 0
        out = np.asarray(mpi.allreduce(x, backend=backend))  # compile
        t0 = time.time()
        for _ in range(args.iters):
            # Per-iteration fence: overlapping in-flight hierarchical
            # programs can interleave their sibling collectives'
            # blocking rendezvous on the CPU sim (same hazard the
            # steady-state loop below fences; we report per-iteration
            # averages, so the fence costs nothing we measure).
            fence(mpi.allreduce(x, backend=backend))
        dt = (time.time() - t0) / max(1, args.iters)
        # Trace-time counters: the delta across the compile is the
        # per-step DCN wire bytes one device sends (flat has no DCN
        # staging — its whole payload crosses the cliff; analytic).
        wire = (_wire(label) - before if backend == "hierarchical"
                else nbytes)
        if flat is None:
            flat = out
        rel = float(np.max(np.abs(out - flat))
                    / max(1e-12, float(np.max(np.abs(flat)))))
        rows[tag] = dict(wire_bytes=int(wire), ms=round(dt * 1e3, 3),
                         rel_err=rel)
        line = {"mode": tag, "bytes": nbytes, "dcn_wire_bytes": int(wire),
                "ms": round(dt * 1e3, 3), "rel_err_vs_flat": rel}
        print(json.dumps(line) if args.json else
              f"{tag:15s} {nbytes:>10d} B payload  "
              f"{int(wire):>10d} B across dcn  {dt * 1e3:8.2f} ms  "
              f"rel-err vs flat {rel:.2e}")

    # Chunk pipelining: bitwise vs the unchunked schedule.
    mpi.set_config(dcn_compress="off", dcn_chunk_bytes=0)
    base = np.asarray(mpi.allreduce(x, backend="hierarchical"))
    mpi.set_config(dcn_chunk_bytes=max(1, nbytes // n_ici // 4))
    chunked = np.asarray(mpi.allreduce(x, backend="hierarchical"))
    chunk_bitwise = bool(np.array_equal(base, chunked))
    mpi.set_config(dcn_chunk_bytes=4 * 1024 * 1024)

    # Error-feedback residual convergence: running mean of EF-quantized
    # syncs approaches the exact mean; single-shot quantization stays
    # biased (the deep-gradient-compression trade, checkable on cpu-sim).
    mpi.set_config(dcn_compress="int8", dcn_compress_min_bytes=0)
    r = np.random.RandomState(1)
    gvals = r.randn(4096).astype(np.float32)
    gvals[:8] *= 100.0  # outliers -> coarse scale -> visible bias
    grads = {"g": jnp.asarray(gvals)}
    exact = np.asarray(jax.jit(shard_map(
        lambda g: gradsync.synchronize_gradients(g, axes, op="mean"),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))(
        grads)["g"])
    ef = jax.jit(shard_map(
        lambda g, rs: gradsync.synchronize_gradients(
            g, axes, op="mean", residuals=rs),
        mesh=mesh, in_specs=(P(), P(axes)), out_specs=(P(), P(axes)),
        check_vma=False))
    res = gradsync.init_dcn_residuals(grads, axes)
    res0 = gradsync.init_dcn_residuals(grads, axes)
    ef_acc = ss_acc = None
    steps = 6
    for _ in range(steps):
        out_ef, res = ef(grads, res)
        out_ss, _ = ef(grads, res0)
        ef_acc = out_ef["g"] if ef_acc is None else ef_acc + out_ef["g"]
        ss_acc = out_ss["g"] if ss_acc is None else ss_acc + out_ss["g"]
    ef_err = float(jnp.mean(jnp.abs(ef_acc / steps - exact)))
    ss_err = float(jnp.mean(jnp.abs(ss_acc / steps - exact)))
    residual_ok = ef_err < ss_err

    # Steady state: two-level+int8 eager dispatches must all be plan
    # hits (0 re-plans) with topology-keyed entries.  Every iteration is
    # fenced: the hierarchical program runs several subset collectives
    # per execution, and letting async dispatch skew the simulated
    # devices across many in-flight executions deadlocks XLA:CPU's
    # collective rendezvous on small hosts (the loop counts plan hits,
    # not wall time, so the fence costs nothing we report).
    fence(mpi.allreduce(x, backend="hierarchical"))  # warm under int8
    planner.reset_stats()
    for _ in range(args.steady):
        fence(mpi.allreduce(x, backend="hierarchical"))
    st = planner.stats()
    topologies = {row["topology"] for row in planner.describe()}

    wire_none = rows["two-level"]["wire_bytes"]
    wire_int8 = rows["two-level+int8"]["wire_bytes"]
    # The acceptance ratio: int8 moves <= 1/ici_n * ~1/4 of the flat
    # bytes (scale overhead gets a little slack).
    bound = nbytes / n_ici / 4 * 1.05
    summary = {
        "payload_bytes": nbytes, "n_dcn": n_dcn, "n_ici": n_ici,
        "flat_dcn_bytes": nbytes, "two_level_dcn_bytes": wire_none,
        "int8_dcn_bytes": wire_int8,
        "compressed_lt_uncompressed": bool(wire_int8 < wire_none
                                           and wire_none < nbytes),
        "int8_within_bound": bool(wire_int8 <= bound),
        "chunked_bitwise": chunk_bitwise,
        "allclose_vs_flat": bool(
            rows["two-level"]["rel_err"] < 1e-5
            and rows["two-level+bf16"]["rel_err"] < 2e-2
            and rows["two-level+int8"]["rel_err"] < 2e-2),
        "residual_convergence_ok": residual_ok,
        "ef_mean_err": round(ef_err, 6), "ss_mean_err": round(ss_err, 6),
        "steady_steps": args.steady, "hits": st["hits"],
        "misses": st["misses"], "topologies": sorted(topologies),
    }
    print("DCN-SUMMARY " + json.dumps(summary))
    _maybe_bank(args, "DCN-SUMMARY", summary)
    print(f"# dcn-compare: flat {nbytes} B vs two-level {wire_none} B "
          f"(1/{n_ici}) vs int8 {wire_int8} B across dcn; chunked "
          f"bitwise={chunk_bitwise}; EF mean-err {ef_err:.4g} vs "
          f"single-shot {ss_err:.4g}; steady {st['hits']} hits / "
          f"{st['misses']} re-plans", file=sys.stderr)
    mpi.set_config(obs="off", dcn_compress="off")
    failures = [k for k in ("compressed_lt_uncompressed",
                            "int8_within_bound", "chunked_bitwise",
                            "allclose_vs_flat", "residual_convergence_ok")
                if not summary[k]]
    if failures:
        raise SystemExit(f"dcn-compare failed: {failures}")
    if st["misses"]:
        raise SystemExit(f"dcn-compare: {st['misses']} steady-state "
                         f"re-plans (expected zero)")


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--devices", type=int, default=0,
                   help="force N simulated CPU devices")
    p.add_argument("--dcn", type=int, default=None)
    p.add_argument("--sizes", type=str,
                   default="65536,1048576,16777216,67108864",
                   help="comma-separated tensor bytes")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--backends", type=str, default="xla,hierarchical,pallas")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON line per measurement")
    p.add_argument("--pytree", action="store_true",
                   help="fused-pytree mode: per-leaf vs dtype-grouped "
                        "fused allreduce over a mixed-dtype tree, with "
                        "launches/step from the lowered HLO")
    p.add_argument("--leaves", type=int, default=64,
                   help="pytree mode: number of leaves (alternating "
                        "fp32/bf16)")
    p.add_argument("--fuse-bytes", type=int, default=None,
                   help="pytree mode: fuse_max_bytes for the fused rows "
                        "(default: the Config default)")
    p.add_argument("--obs-compare", action="store_true",
                   help="telemetry overhead mode: the same small eager "
                        "allreduce under obs=off/metrics/trace "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--faults-compare", action="store_true",
                   help="fault-layer overhead mode: the same small "
                        "staged allreduce under faults=off/policy "
                        "(docs/FAULTS.md)")
    p.add_argument("--watchdog-compare", action="store_true",
                   help="watchdog overhead mode: the same small staged "
                        "allreduce under watchdog=off/warn/break (the "
                        "armed in-flight window + monitor thread, no "
                        "stalls injected) — docs/WATCHDOG.md")
    p.add_argument("--guard-compare", action="store_true",
                   help="guard overhead mode: the same small staged "
                        "allreduce under guard=off/wire (digest cost) "
                        "and a jitted gradient sync under "
                        "guard=off/numeric (fused tripwire cost) — "
                        "docs/GUARD.md")
    p.add_argument("--steady", type=int, default=100,
                   help="dcn-compare mode: steady-state dispatches to "
                        "assert zero re-plans over")
    p.add_argument("--overlap-compare", action="store_true",
                   help="gradsync schedule mode: sync vs "
                        "backprop-overlapped dispatch on a mixed-dtype "
                        "MLP, with launches/step from the lowered HLO "
                        "and a grads bitwise check (docs/OVERLAP.md)")
    p.add_argument("--dcn-compare", action="store_true",
                   help="two-level mode: flat vs hierarchical vs "
                        "hierarchical+codec on a (dcn, ici) mesh — "
                        "DCN-leg wire bytes from obs counters, "
                        "bitwise/allclose verdicts, error-feedback "
                        "residual convergence, steady-state plan hits "
                        "(docs/HIERARCHICAL.md; needs --dcn >= 2)")
    p.add_argument("--dcn-bytes", type=int, default=1 << 20,
                   help="dcn-compare mode: per-device payload bytes")
    p.add_argument("--overlap-layers", type=int, default=8,
                   help="overlap mode: MLP depth (alternating "
                        "fp32/bf16 layers)")
    p.add_argument("--overlap-dim", type=int, default=128,
                   help="overlap mode: layer width")
    p.add_argument("--bank", action="store_true",
                   help="persist each *-SUMMARY line to "
                        "SUMMARY_BANK.json at the repo root (stamped + "
                        "git-pinned + platform-tagged; "
                        "benchmarks/banking.py) next to the "
                        "BENCH_r*.json round records")
    p.add_argument("--round", type=int, default=None,
                   help="bench round number stamped on banked records "
                        "(the BENCH_r<N> numbering; bench.py's "
                        "micro-ladder pass sets it — defaults to "
                        "TORCHMPI_TPU_BENCH_ROUND when unset)")
    args = p.parse_args()
    if args.devices:
        from torchmpi_tpu.utils.simulation import force_cpu_devices

        force_cpu_devices(args.devices)
    import jax
    import numpy as np

    import torchmpi_tpu as mpi
    from torchmpi_tpu.ops import ring
    from torchmpi_tpu.utils.metrics import allreduce_bus_bandwidth, fence

    mesh = mpi.init(mpi.Config(dcn_size=args.dcn, custom_min_bytes=0))
    n = mpi.device_count()
    is_cpu = list(mesh.devices.flat)[0].platform == "cpu"
    if is_cpu:
        from jax.experimental.pallas import tpu as pltpu

        ring.set_interpret(pltpu.InterpretParams())
    print(f"# mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({'cpu-sim' if is_cpu else 'tpu'})", file=sys.stderr)

    backends = args.backends.split(",")
    sizes = [int(s) for s in args.sizes.split(",")]

    if args.obs_compare:
        _obs_compare_mode(args, mpi, n)
        mpi.stop()
        return

    if args.faults_compare:
        _faults_compare_mode(args, mpi, n)
        mpi.stop()
        return

    if args.watchdog_compare:
        _watchdog_compare_mode(args, mpi, n)
        mpi.stop()
        return

    if args.guard_compare:
        _guard_compare_mode(args, mpi, n)
        mpi.stop()
        return

    if args.overlap_compare:
        _overlap_compare_mode(args, mpi, mesh)
        mpi.stop()
        return

    if args.dcn_compare:
        _dcn_compare_mode(args, mpi, mesh)
        mpi.stop()
        return

    if args.pytree:
        _pytree_mode(args, mpi, mesh, sizes)
        mpi.stop()
        return

    for nbytes in sizes:
        floats_per_rank = nbytes // 4
        x = np.random.RandomState(0).rand(n, floats_per_rank).astype(
            np.float32)
        for backend in backends:
            if backend == "hierarchical" and mesh.shape[mpi.DCN_AXIS] <= 1:
                continue
            if backend == "pallas" and is_cpu and nbytes > 1 << 20:
                continue  # interpreter too slow for big tensors
            try:
                out = mpi.allreduce(x, backend=backend)  # compile
                fence(out)
                t0 = time.time()
                for _ in range(args.iters):
                    out = mpi.allreduce(x, backend=backend)
                fence(out)
                dt = (time.time() - t0) / args.iters
            except Exception as e:  # noqa: BLE001 — report and continue
                print(f"{backend:13s} {nbytes:>12d} B  FAILED: {e}",
                      file=sys.stderr)
                continue
            busbw = allreduce_bus_bandwidth(nbytes, n, dt)
            line = {"op": "allreduce", "backend": backend, "bytes": nbytes,
                    "devices": n, "ms": round(dt * 1e3, 3),
                    "busbw_GBs": round(busbw, 3)}
            if args.json:
                print(json.dumps(line))
            else:
                print(f"{'allreduce':10s} {backend:13s} {nbytes:>12d} B  "
                      f"{dt*1e3:8.2f} ms  busbw {busbw:8.3f} GB/s")

        # Root-ops next to allreduce.  Broadcast: algo bytes = tensor
        # size, so the chain schedule should approach 2x the allreduce
        # busbw line.  Gather/scatter: above the chunk_bytes cutover the
        # chain schedules move O(size) like the reference's
        # MPI_Gather/Scatter, so their time should track broadcast of the
        # same total payload — NOT the allgather row (which moves the
        # gathered payload to EVERY device).  algo bytes = the total
        # payload that must cross the root's link.
        root_ops = [
            ("broadcast", lambda b: mpi.broadcast(x, root=0, backend=b),
             nbytes),
            ("gather", lambda b: mpi.gather(x, root=0, backend=b),
             n * nbytes),
            ("scatter", lambda b: mpi.scatter(x, root=0, backend=b),
             nbytes),
            ("allgather", lambda b: mpi.allgather(x, backend=b),
             n * nbytes),
        ]
        for opname, op_fn, algo_bytes in root_ops:
            for backend in backends:
                # gather/scatter have no pallas registration; allgather
                # DOES (ring_all_gather) and must appear in the
                # comparison.  Same interpreter size guard as allreduce.
                if backend == "pallas" and (
                        opname != "allgather"
                        or (is_cpu and nbytes > 1 << 20)):
                    continue
                if (backend == "hierarchical"
                        and mesh.shape[mpi.DCN_AXIS] <= 1):
                    continue
                if backend == "hierarchical" and opname == "scatter":
                    continue  # delegates to the stock chain; same row
                try:
                    out = op_fn(backend)
                    fence(out)
                    t0 = time.time()
                    for _ in range(args.iters):
                        out = op_fn(backend)
                    fence(out)
                    dt = (time.time() - t0) / args.iters
                except Exception as e:  # noqa: BLE001 — report, continue
                    print(f"{opname:10s} {backend:13s} {nbytes:>12d} B  "
                          f"FAILED: {e}", file=sys.stderr)
                    continue
                bw = algo_bytes / dt / 1e9
                line = {"op": opname, "backend": backend, "bytes": nbytes,
                        "devices": n, "ms": round(dt * 1e3, 3),
                        "busbw_GBs": round(bw, 3)}
                if args.json:
                    print(json.dumps(line))
                else:
                    print(f"{opname:10s} {backend:13s} {nbytes:>12d} B  "
                          f"{dt*1e3:8.2f} ms  busbw {bw:8.3f} GB/s")
    mpi.stop()


if __name__ == "__main__":
    main()
