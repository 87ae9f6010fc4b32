#!/usr/bin/env python
"""Benchmark ladder: ResNet-50 data-parallel training throughput and the
kernel / LM stages beside it, in ONE process.

Measures img/s/chip for the full data-parallel train step (forward, backward,
selector-routed gradient allreduce, BatchNorm cross-replica stats sync, SGD
update) on every visible device — the single-chip number is the denominator
of BASELINE.md's scaling-efficiency target, and on a multi-chip slice the
same script measures the scaled throughput directly.

Stages, cheapest first: A matmul probe, B TransformerLM train step, C
Pallas flash-attention kernel (TPU only), C2 fused linear+xent kernel
(TPU only), B2 the flagship modern-LM step, D the headline ResNet-50
train step, D2 its scanned variant (TPU only).
``TORCHMPI_TPU_BENCH_STAGE=A,D`` runs a subset.  Each completed stage
prints one JSON record on stdout
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N|null, "extra": {...}}
carrying ``platform``, ``device_kind`` and the device count it ran on.
A stage that raises is logged, the remaining stages still run, and the
exit code is non-zero.  Nothing stored ever stands in for a measurement.

The run FAILS when jax finds no TPU.  ``TORCHMPI_TPU_BENCH_CPU=N``
forces an N-device simulated CPU mesh and ``TORCHMPI_TPU_BENCH_PRESET=tiny``
shrinks the shapes: together they smoke the code path in about a minute.
Their numbers are not device metrics.

``vs_baseline``: the upstream repo published no benchmark tables
(BASELINE.json "published": {}; see BASELINE.md), so training stages
report ``null``; kernel/probe stages report the fraction of chip peak.

Data is device-resident (one repeated synthetic batch).  Timing is
``utils.metrics.timed`` — fenced by a device->host readback.
"""

import json
import os
import sys
import time


def log(*a):
    print(time.strftime("[%H:%M:%S]"), *a, file=sys.stderr, flush=True)


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16, 819 GB/s HBM bandwidth per chip).  A device that is
# not in the table is an error, never a default.
PEAKS = {
    # what jax reports for a v5e chip (seen on the chip, PR 21)
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}


def peak_for(device_kind):
    """The PEAKS row for ``device_kind``; KeyError names the table when
    the device is unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} in "
            f"bench.PEAKS (known: {sorted(PEAKS)}); add a sourced row "
            "before benchmarking on it") from None


def cost_model_mfu(lower_fn, dt, peak, platform, analytic_flops=0.0):
    """(TFLOP/s, MFU) from XLA's cost model of a step lowering over the
    measured per-step seconds ``dt`` — the shared helper behind every
    stage's mfu field.  ``lower_fn`` is a thunk returning the lowering
    (not an AOT compile: that would bypass the jit dispatch cache and pay
    the step compile twice); the pre-optimization flops estimate is fine
    for MFU.  When the cost model yields nothing — a lowering's
    ``cost_analysis()`` is empty on the TPU client (seen on a v5e through
    ``chip_smoke.py``, PR 21; the COMPILED program's is not) — falls back
    to ``analytic_flops``, the caller's closed-form matmul/conv FLOP
    count for one step.  Both sources are
    PER-DEVICE FLOPs: the steps here are shard_map-wrapped, so XLA
    lowers and costs the per-shard body, and callers must divide any
    global-program analytic count by the device count themselves.
    Returns (tflops, mfu, source) with ``source`` one of "cost_model" /
    "analytic" / None, recorded in the JSON so an approximate analytic
    MFU is distinguishable from a measured-cost-model one.  (0.0, None,
    None) only when both sources are empty; MFU is only reported on real
    accelerator runs."""
    flops, source = 0.0, "cost_model"
    try:
        ca = lower_fn().cost_analysis()
        flops = float(ca.get("flops", 0.0)) if ca else 0.0
        if not flops > 0:  # catches 0, negatives, and NaN sentinels
            log(f"cost_analysis gave no usable flops ({flops})"
                + ("; using analytic count" if analytic_flops else ""))
    except Exception as e:  # noqa: BLE001 — cost model is best-effort
        log(f"cost_analysis unavailable: {e}"
            + ("; using analytic count" if analytic_flops else ""))
    if not flops > 0:
        flops, source = float(analytic_flops), "analytic"
    if not flops > 0:
        source = None
    tflops = flops / dt / 1e12
    mfu = round(tflops / peak, 4) if platform == "tpu" and flops > 0 else None
    return tflops, mfu, source


def scanned_train_step(step_fn, length, n_carry=2):
    """Wrap a ``(carry..., fixed...) -> (carry..., loss)`` train step
    into one program running ``length`` dependent steps under
    ``lax.scan``, returning the last step's loss — the step-level analog
    of ``metrics.chained()``: per-dispatch host overhead is paid once per
    dispatch, and production training is a scanned loop anyway.  Shared
    by stages B, B2 (carry = (vars, opt)) and D2 (n_carry=3: carry =
    (params, opt, batch_stats)).  MFU bookkeeping for the wrapped
    program: XLA's ``cost_analysis`` counts a scan body ONCE (verified
    empirically — a length-8 scan of a matmul reports ~1x the body
    flops), so pair PER-STEP time with PER-STEP flops when calling
    cost_model_mfu."""
    import jax

    def multi(*args):
        carry0 = tuple(args[:n_carry])
        fixed = args[n_carry:]

        def body(carry, _):
            out = step_fn(*carry, *fixed)
            return tuple(out[:-1]), out[-1]

        carry, losses = jax.lax.scan(body, carry0, None, length=length)
        return (*carry, losses[-1])

    return multi


def main() -> int:
    # Smoke knobs: BENCH_CPU forces an N-device simulated CPU mesh;
    # PRESET=tiny shrinks shapes so the full path executes in seconds.
    # Default = real devices, real shapes.
    cpu_n = int(os.environ.get("TORCHMPI_TPU_BENCH_CPU", "0"))
    if cpu_n:
        from torchmpi_tpu.utils.simulation import force_cpu_devices

        force_cpu_devices(cpu_n)
    tiny = os.environ.get("TORCHMPI_TPU_BENCH_PRESET") == "tiny"

    import traceback

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchmpi_tpu as mpi
    from torchmpi_tpu.models import ResNet50
    from torchmpi_tpu.utils import compilecache
    from torchmpi_tpu.utils import metrics as _metrics
    from torchmpi_tpu.utils.metrics import fence, timed

    dev0 = jax.devices()[0]
    platform0 = dev0.platform
    if platform0 != "tpu" and not cpu_n:
        log(f"no TPU found (platform={platform0!r}): a benchmark number "
            "comes only from the chip.  TORCHMPI_TPU_BENCH_CPU=N smokes "
            "the code path on a simulated CPU mesh instead.")
        return 2
    # bf16 peak of the chip this run is on (None on the CPU smoke, where
    # no fraction-of-peak is reported).
    peak = (peak_for(dev0.device_kind)["bf16_tflops"]
            if platform0 == "tpu" else None)

    cache_dir = compilecache.enable_persistent_cache()
    log(f"persistent compilation cache at {cache_dir}")

    # 128/chip is BASELINE config 3's per-chip batch.
    BATCH_PER_CHIP = 4 if tiny else 128
    IMAGE = 64 if tiny else 224
    STEPS = 3 if tiny else 20
    WARMUP = 1 if tiny else 3
    # TORCHMPI_TPU_BENCH_STAGE names stage keys (comma list of
    # A,B,C,C2,B2,D,D2) to run ONLY those; unset = the whole ladder.
    _only = os.environ.get("TORCHMPI_TPU_BENCH_STAGE")
    only_keys = ({k for k in _only.split(",") if k} if _only else None)

    mesh = mpi.init()
    n_dev = mpi.device_count()
    batch = BATCH_PER_CHIP * n_dev
    log(f"devices={n_dev} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
        f"global_batch={batch} platform={platform0} "
        f"device_kind={dev0.device_kind}")

    def emit(rec):
        extra = rec.setdefault("extra", {})
        extra.setdefault("platform", platform0)
        extra.setdefault("device_kind", dev0.device_kind)
        extra.setdefault("devices", n_dev)
        print(json.dumps(rec), flush=True)

    def of_peak(tflops):
        return round(tflops / peak, 4) if peak else None

    from jax.sharding import NamedSharding, PartitionSpec as P

    shard = NamedSharding(mesh, P(mesh.axis_names))
    KD2 = int(os.environ.get("TORCHMPI_TPU_BENCH_D_SCAN", "4"))

    # --- Stage D / D2: the headline ResNet-50 train step ----------------
    def stage_d(kd=1):
        model = ResNet50(dtype=jnp.bfloat16)
        log("init ResNet-50...")
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, IMAGE, IMAGE, 3)),
                               train=False)
        params, batch_stats = variables["params"], variables["batch_stats"]
        tx = optax.sgd(0.1, momentum=0.9)
        opt_state = tx.init(params)

        dp_step = mpi.recipes.make_bn_dp_train_step(model, tx, mesh=mesh)
        params, opt_state, batch_stats = mpi.recipes.replicate_bn_state(
            params, opt_state, batch_stats, mesh=mesh)

        step_call = dp_step
        if kd > 1:
            # Scanned steady-state variant (stage D2): kd dependent
            # train steps per dispatch via the shared scanned_train_step
            # — the same methodology as stages B/B2.  The single-dispatch
            # headline keeps its own record and metric name; this one is
            # emitted as *_scanned with the depth in its config.
            step_call = jax.jit(
                scanned_train_step(dp_step, kd, n_carry=3),
                donate_argnums=(0, 1, 2))

        # Device-resident synthetic batch, sharded over the mesh.
        images = jax.device_put(
            np.random.RandomState(0).rand(batch, IMAGE, IMAGE, 3)
            .astype(np.float32), shard)
        labels = jax.device_put(
            np.random.RandomState(1).randint(0, 1000, size=batch)
            .astype(np.int32), shard)

        log("compiling + warmup...")
        t0 = time.time()
        for _ in range(WARMUP):
            params, opt_state, batch_stats, loss = step_call(
                params, opt_state, batch_stats, images, labels)
        fence(loss)
        log(f"warmup done in {time.time()-t0:.1f}s; timing rounds of "
            f"{STEPS} dispatches (x{kd} steps each)...")

        rn_state = {"p": params, "o": opt_state, "b": batch_stats}

        def rn_step():
            rn_state["p"], rn_state["o"], rn_state["b"], loss = step_call(
                rn_state["p"], rn_state["o"], rn_state["b"], images, labels)
            rn_state["loss"] = loss  # from the last executed step
            return loss

        dt = timed(rn_step, STEPS, fence) / kd  # per-TRAIN-STEP seconds
        params, opt_state, batch_stats = (rn_state["p"], rn_state["o"],
                                          rn_state["b"])
        loss = rn_state["loss"]

        img_s = batch / dt
        img_s_chip = img_s / n_dev

        # Achieved TFLOP/s + MFU from XLA's own cost model of the
        # per-device step, with an analytic fallback for a backend whose
        # cost analysis is empty: ResNet-50 fwd at 224^2 is ~4.1
        # GMACs/image = 8.2 GFLOP, train step ~3x fwd; conv cost scales
        # with spatial area (IMAGE/224)^2.
        rn_flops = 3.0 * 8.2e9 * (IMAGE / 224.0) ** 2 * batch
        tflops_chip, mfu, flops_src = cost_model_mfu(
            lambda: dp_step.jitted.lower(params, opt_state, batch_stats,
                                         images, labels),
            dt, peak, platform0, analytic_flops=rn_flops / n_dev)

        metric = ("resnet50_dp_train_throughput" if kd <= 1 else
                  "resnet50_dp_train_throughput_scanned")
        log(f"[{metric}] step time {dt*1000:.1f} ms, total "
            f"{img_s:.1f} img/s, loss {float(loss):.3f}, "
            f"{tflops_chip:.4g} TFLOP/s/chip, MFU {mfu}")
        extra = {"global_batch": batch,
                 "step_ms": round(dt * 1000, 2),
                 # per-TRAIN-STEP like step_ms (each timing round
                 # dispatches kd scanned steps).
                 "round_ms": [round(t * 1e3 / kd, 2)
                              for t in _metrics.last_round_times],
                 "dtype": "bfloat16", "image": IMAGE,
                 "tflops_per_chip": round(tflops_chip, 4),
                 "mfu": mfu, "flops_source": flops_src,
                 "peak_tflops": peak}
        if kd > 1:
            extra["scan_steps_per_dispatch"] = kd
        emit({"metric": metric, "value": round(img_s_chip, 1),
              "unit": "img/s/chip", "vs_baseline": None, "extra": extra})

    # --- Stage A: matmul probe — the chip's achievable bf16 peak --------
    def stage_a():
        N = 512 if tiny else 16384
        CHAIN = 4  # dependent matmuls per dispatch: amortizes per-dispatch
        # host overhead, which dominates single-matmul timings.
        x = jnp.ones((N, N), jnp.bfloat16)

        # Scale each product by 1/N so chained squarings stay ~1 instead of
        # overflowing to inf within a few iterations (timing matmuls over
        # inf operands can mask value-dependent behavior on some backends).
        @jax.jit
        def mm(a, b):
            y = a
            for _ in range(CHAIN):
                y = (y @ b) * (1.0 / N)
            return y

        log("stage A: compiling matmul probe...")
        chain = {"y": x}  # dependent chain so dispatches cannot overlap away

        def mm_step():
            chain["y"] = mm(chain["y"], x)
            return chain["y"]

        mm_dt = timed(mm_step, 3 if tiny else 5, fence) / CHAIN
        mm_tflops = 2.0 * N ** 3 / mm_dt / 1e12
        log(f"stage A: {N}x{N} bf16 matmul {mm_dt*1e6:.0f} us, "
            f"{mm_tflops:.1f} TFLOP/s")
        emit({
            "metric": "matmul_bf16_tflops",
            "value": round(mm_tflops, 1),
            "unit": "TFLOP/s",
            "vs_baseline": of_peak(mm_tflops),
            "extra": {"n": N, "peak_tflops": peak},
        })

    # --- Stage B: TransformerLM training throughput ---------------------
    def stage_b():
        Bt = (2 if tiny else 8) * n_dev
        T = 64 if tiny else 512
        from torchmpi_tpu.models import TransformerLM

        # The hardware benchmark trains the flagship attention path; CPU
        # runs keep the dense impl (Pallas would drop to the interpreter
        # there).
        attn = "flash" if platform0 == "tpu" else "local"
        E_B = 64 if tiny else int(os.environ.get(
            "TORCHMPI_TPU_BENCH_B_EMBED", "512"))
        lm = TransformerLM(vocab=8192, embed=E_B,
                           depth=2 if tiny else 4,
                           num_heads=8 if tiny else max(1, E_B // 64),
                           head_dim=8 if tiny else 64, max_len=T,
                           dtype=jnp.bfloat16, attn_impl=attn)
        tok = np.random.RandomState(2).randint(
            0, 8192, size=(Bt, T)).astype(np.int32)
        # Init through a "local"-attention TWIN: attention impls share
        # one parameter tree (impl only changes the score computation),
        # so init never traces a Pallas kernel at its batch-1 shape.
        lm_init = lm if attn == "local" else lm.clone(attn_impl="local")
        lm_vars = lm_init.init(jax.random.PRNGKey(1), tok[:1])
        tx_lm = optax.sgd(0.1)

        def lm_step(v, o, tok):
            def loss_fn(v):
                logits = lm.apply(v, tok).astype(jnp.float32)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1], tok[:, 1:]).mean()

            loss, g = jax.value_and_grad(loss_fn)(v)
            g = mpi.nn.synchronize_gradients(g, mesh.axis_names)
            loss = mpi.collectives.allreduce_in_axis(
                loss, mesh.axis_names, op="mean")
            u, o = tx_lm.update(g, o, v)
            return optax.apply_updates(v, u), o, loss

        # Steady-state program: KB dependent train steps under ONE
        # lax.scan'd dispatch, so per-dispatch host overhead is amortized
        # — production training IS a scanned step loop.
        KB = 2 if tiny else int(os.environ.get(
            "TORCHMPI_TPU_BENCH_B_SCAN", "32"))
        lm_jit = mpi.nn.data_parallel_step(
            scanned_train_step(lm_step, KB), mesh=mesh,
            batch_argnums=(2,))
        lm_opt = tx_lm.init(lm_vars)
        lm_vars = mpi.nn.synchronize_parameters(lm_vars, mesh=mesh)
        lm_opt = mpi.nn.synchronize_parameters(lm_opt, mesh=mesh)
        tok_d = jax.device_put(tok, shard)
        log(f"stage B: compiling transformer-LM step (B={Bt}, T={T})...")
        lm_state = {"v": lm_vars, "o": lm_opt}

        def lm_step_once():
            lm_state["v"], lm_state["o"], loss = lm_jit(
                lm_state["v"], lm_state["o"], tok_d)
            lm_state["loss"] = loss  # from the last executed step
            return loss

        calls_b = 3 if tiny else 5   # each call runs KB steps
        dt_call = timed(lm_step_once, calls_b, fence)
        dt_step = dt_call / KB       # per-train-step seconds
        lm_loss = lm_state["loss"]
        tok_s_chip = Bt * T / dt_step / n_dev
        # MFU from XLA's own cost model of the step lowering (same
        # method as stage D).  Analytic fallback derived from the
        # model's own attributes: matmul params per dense block are
        # qkv+out (4*E^2) + 4x-MLP in/out (8*E^2), plus the untied
        # E*vocab head; the Embed/pos_embed tables are pure gathers and
        # excluded.  fwd FLOPs/token = 2*P_mm plus causal attention
        # 2*T*E per layer (QK^T + AV, halved by the mask); train step =
        # 3x fwd (bwd is ~2x fwd).
        E_lm, L_lm = lm.embed, lm.depth
        p_mm = (L_lm * (4.0 + 8.0) * E_lm * E_lm
                + E_lm * lm.vocab)
        lm_flops = 3.0 * (Bt * T) * (2.0 * p_mm + L_lm * 2.0 * T * E_lm)
        # PER-STEP time with PER-STEP flops: XLA's cost_analysis counts
        # the scan body once (see scanned_train_step), and the analytic
        # count is for one step.
        lm_tflops, lm_mfu, lm_src = cost_model_mfu(
            lambda: lm_jit.jitted.lower(lm_state["v"], lm_state["o"],
                                        tok_d),
            dt_step, peak, platform0, analytic_flops=lm_flops / n_dev)
        log(f"stage B: {tok_s_chip:.0f} tokens/s/chip, "
            f"loss {float(lm_loss):.3f}, "
            f"{lm_tflops:.4g} TFLOP/s/chip, MFU {lm_mfu}")
        emit({
            "metric": "transformer_lm_train_throughput",
            "value": round(tok_s_chip, 1),
            "unit": "tokens/s/chip",
            "vs_baseline": None,
            "extra": {"batch": Bt, "seq": T,
                      "embed": E_B,
                      "step_ms": round(dt_step * 1000, 2),
                      "scan_steps_per_dispatch": KB,
                      # per-TRAIN-STEP like step_ms (each timing round
                      # dispatches KB scanned steps).
                      "round_ms": [round(t * 1e3 / KB, 2)
                                   for t in _metrics.last_round_times],
                      "dtype": "bfloat16",
                      "tflops_per_chip": round(lm_tflops, 4),
                      "mfu": lm_mfu, "flops_source": lm_src,
                      "peak_tflops": peak},
        })

    # --- Stage C (TPU only): the Pallas flash-attention kernel, measured
    # next to XLA's dense attention and checked against it.
    def stage_c():
        from torchmpi_tpu.ops.flash import flash_attention
        from torchmpi_tpu.parallel.sequence import reference_attention

        Bf, Tf, Hf, Df = 4, 4096, 8, 128
        rngf = np.random.RandomState(3)
        qkv = [jnp.asarray(rngf.randn(Bf, Tf, Hf, Df), jnp.bfloat16)
               for _ in range(3)]
        # A CHAIN of dependent invocations inside ONE jit program: the
        # per-dispatch host overhead is paid once and the data
        # dependence (q <- output) stops CSE from collapsing the chain —
        # then divide by the chain depth.
        CHF = 4
        fl_chain = _metrics.chained(
            lambda q, k, v: flash_attention(q, k, v, causal=True),
            depth=CHF)

        fl = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        log("stage C: compiling flash attention kernel...")
        iters_d = 10
        dt_single = timed(lambda: fl(*qkv), iters_d, fence)
        dt_d = timed(lambda: fl_chain(*qkv), iters_d, fence) / CHF
        fl_tflops = 4.0 * Bf * Hf * Tf * Tf * Df * 0.5 / dt_d / 1e12
        dn_chain = _metrics.chained(
            lambda q, k, v: reference_attention(q, k, v, causal=True),
            depth=CHF)
        dn = jax.jit(lambda q, k, v: reference_attention(
            q, k, v, causal=True))
        dense_ms = round(timed(lambda: dn_chain(*qkv), iters_d,
                               fence) / CHF * 1e3, 3)
        # On-device oracle: a Mosaic-lowered kernel can still miscompute
        # at run time — check, don't just time.
        oracle_err = float(jnp.max(jnp.abs(
            fl(*qkv).astype(jnp.float32) - dn(*qkv).astype(jnp.float32))))
        if not oracle_err < 2e-2:
            raise RuntimeError(
                f"flash kernel disagrees with XLA dense attention on "
                f"{platform0}: max|err|={oracle_err}")
        log(f"stage C: flash {dt_d*1e3:.2f} ms/invocation "
            f"(chained x{CHF}; single-dispatch {dt_single*1e3:.2f} "
            f"ms) ({fl_tflops:.1f} TFLOP/s) vs xla-dense {dense_ms} "
            f"ms, oracle max|err|={oracle_err}")
        emit({
            "metric": "flash_attention_tflops",
            "value": round(fl_tflops, 1),
            "unit": "TFLOP/s",
            "vs_baseline": of_peak(fl_tflops),
            "extra": {"batch": Bf, "seq": Tf, "heads": Hf,
                      "head_dim": Df, "causal": True,
                      "dtype": "bfloat16",
                      "chained_per_dispatch": CHF,
                      "flash_ms": round(dt_d * 1e3, 3),
                      "flash_ms_single_dispatch":
                          round(dt_single * 1e3, 3),
                      "xla_dense_ms": dense_ms,
                      "oracle_max_err": oracle_err},
        })

    # --- Stage C2 (TPU only): the fused linear+cross-entropy Pallas
    # kernel, checked against the logits-materializing XLA oracle.
    def stage_c2():
        from torchmpi_tpu.ops.xent import fused_linear_cross_entropy

        Nx, Ex, Vx = 8192, 1024, 32768
        rngx = np.random.RandomState(5)
        xx = jnp.asarray(rngx.randn(Nx, Ex) * 0.05, jnp.bfloat16)
        wx = jnp.asarray(rngx.randn(Ex, Vx) * 0.05, jnp.bfloat16)
        lx = jnp.asarray(rngx.randint(0, Vx, size=Nx), jnp.int32)
        # Chained like stage C.  The loss output cannot feed the input,
        # so CSE is defeated by rolling the labels per link (identical
        # shapes, distinct operands) and summing the per-link losses.
        CHX = 4

        @jax.jit
        def fx_chain(x, w, l):
            tot = jnp.float32(0)
            for _ in range(CHX):
                tot = tot + fused_linear_cross_entropy(x, w, l).sum()
                l = jnp.roll(l, 1)
            return tot
        fx = jax.jit(lambda x, w, l: fused_linear_cross_entropy(x, w, l))
        log("stage C2: compiling fused linear+xent kernel...")
        dt_x_single = timed(lambda: fx(xx, wx, lx), 10, fence)
        dt_x = timed(lambda: fx_chain(xx, wx, lx), 10, fence) / CHX
        # matmul flops dominate: 2*N*E*V fwd (fwd-only here).
        xt_tflops = 2.0 * Nx * Ex * Vx / dt_x / 1e12

        def oracle(x, w, l):
            logits = (x @ w).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            return lse - jnp.take_along_axis(
                logits, l[:, None], axis=1)[:, 0]

        ox = jax.jit(oracle)
        # Elementwise PER-TOKEN comparison: a mean over 8192 tokens
        # would let per-row errors average out and certify a
        # miscomputing kernel as hardware-verified.
        err_x = float(jnp.max(jnp.abs(fx(xx, wx, lx) - ox(xx, wx, lx))))
        if not err_x < 5e-3:
            raise RuntimeError(
                f"fused xent disagrees with XLA oracle on {platform0}: "
                f"max|err|={err_x}")
        log(f"stage C2: fused xent {dt_x*1e3:.2f} ms/invocation "
            f"(chained x{CHX}; single-dispatch {dt_x_single*1e3:.2f} "
            f"ms) ({xt_tflops:.1f} TFLOP/s), oracle "
            f"max|err|={err_x:.2e}")
        emit({
            "metric": "fused_xent_tflops",
            "value": round(xt_tflops, 1),
            "unit": "TFLOP/s",
            "vs_baseline": of_peak(xt_tflops),
            "extra": {"tokens": Nx, "embed": Ex, "vocab": Vx,
                      "dtype": "bfloat16",
                      "chained_per_dispatch": CHX,
                      "fused_ms": round(dt_x * 1e3, 3),
                      "fused_ms_single_dispatch":
                          round(dt_x_single * 1e3, 3),
                      "oracle_max_err": err_x},
        })

    # --- Stage B2: the flagship stack COMPOSED at production-ish dims:
    # Pallas flash attention + GQA + RoPE + sliding window + fused
    # linear+xent head, bf16, in one data-parallel train step (embed
    # 2048, depth 8, T 2048, 32k vocab).  TPU-only at full dims; the tiny
    # preset exercises the composed code path on CPU with the dense loss
    # (the Pallas kernels would drop to the interpreter there).
    def stage_b2():
        from torchmpi_tpu.models import TransformerLM
        from torchmpi_tpu.ops.xent import fused_linear_cross_entropy

        E2 = 128 if tiny else 2048
        L2 = 2 if tiny else 8
        H2 = 4 if tiny else 16
        HKV2 = 2 if tiny else 4      # GQA: 4 q heads per kv head
        HD2 = 32 if tiny else 128
        T2 = 128 if tiny else 2048
        V2 = 512 if tiny else 32768
        W2 = 64 if tiny else 1024    # sliding window
        B2 = (2 if tiny else int(os.environ.get(
            "TORCHMPI_TPU_BENCH_B2_BATCH", "4"))) * n_dev
        attn2 = "flash" if platform0 == "tpu" else "local"
        K2 = 2 if tiny else 8   # scanned train steps per dispatch
        lm2 = TransformerLM(vocab=V2, embed=E2, depth=L2,
                            num_heads=H2, head_dim=HD2,
                            num_kv_heads=HKV2, max_len=T2,
                            window=W2, pos_emb="rope",
                            dtype=jnp.bfloat16, attn_impl=attn2)
        tok2 = np.random.RandomState(3).randint(
            0, V2, size=(B2, T2)).astype(np.int32)
        # Init via the "local"-attention twin (same param tree; see
        # stage B).
        lm2_init = lm2 if attn2 == "local" else lm2.clone(
            attn_impl="local")
        lm2_vars = lm2_init.init(jax.random.PRNGKey(4), tok2[:1])
        tx2 = optax.sgd(0.02)

        def lm2_step(v, o, tok):
            def loss_fn(v):
                h, head = lm2.apply(v, tok, return_prehead=True)
                h = h[:, :-1].reshape(-1, E2)
                lab = tok[:, 1:].reshape(-1)
                if platform0 == "tpu":
                    per_tok = fused_linear_cross_entropy(
                        h.astype(jnp.bfloat16),
                        head.astype(jnp.bfloat16), lab)
                else:
                    logits = (h @ head).astype(jnp.float32)
                    per_tok = optax.\
                        softmax_cross_entropy_with_integer_labels(
                            logits, lab)
                return per_tok.mean()

            loss, g = jax.value_and_grad(loss_fn)(v)
            g = mpi.nn.synchronize_gradients(g, mesh.axis_names)
            loss = mpi.collectives.allreduce_in_axis(
                loss, mesh.axis_names, op="mean")
            u, o = tx2.update(g, o, v)
            return optax.apply_updates(v, u), o, loss

        # Steady-state scanned program — see scanned_train_step.
        lm2_jit = mpi.nn.data_parallel_step(
            scanned_train_step(lm2_step, K2), mesh=mesh,
            batch_argnums=(2,))
        lm2_opt = tx2.init(lm2_vars)
        lm2_vars = mpi.nn.synchronize_parameters(lm2_vars, mesh=mesh)
        lm2_opt = mpi.nn.synchronize_parameters(lm2_opt, mesh=mesh)
        tok2_d = jax.device_put(tok2, shard)
        log(f"stage B2: compiling large-LM step (E={E2}, L={L2}, "
            f"T={T2}, GQA {H2}/{HKV2}, window {W2}, "
            f"fused-xent={platform0 == 'tpu'})...")
        lm2_state = {"v": lm2_vars, "o": lm2_opt}

        def lm2_once():
            lm2_state["v"], lm2_state["o"], loss = lm2_jit(
                lm2_state["v"], lm2_state["o"], tok2_d)
            lm2_state["loss"] = loss
            return loss

        calls_b2 = 1 if tiny else 3
        dt2_call = timed(lm2_once, calls_b2, fence)
        dt2 = dt2_call / K2          # per-train-step seconds
        tok_s2 = B2 * T2 / dt2 / n_dev
        # Analytic FLOPs (same method as stage B): matmul params =
        # per-layer q/out (2*E*H*hd) + kv (2*E*Hkv*hd) + 4x MLP
        # (8*E^2), plus the E*V head; embed table is a gather.
        # Attention: 2 matmuls (QK^T, AV) over an average causal
        # context of min(T, window)-bounded band.  Train = 3x fwd.
        p_mm2 = (L2 * (2.0 * E2 * H2 * HD2 + 2.0 * E2 * HKV2 * HD2
                       + 8.0 * E2 * E2) + E2 * V2)
        avg_ctx = (W2 / 2 * W2 + (T2 - W2) * W2) / T2 if T2 > W2 \
            else T2 / 2
        attn_fl2 = L2 * 4.0 * H2 * HD2 * avg_ctx
        fl2 = 3.0 * (B2 * T2) * (2.0 * p_mm2 + attn_fl2)
        # PER-STEP time with PER-STEP flops: XLA's cost_analysis counts
        # the scan body once (see scanned_train_step), and fl2 is the
        # one-step analytic count.
        tfl2, mfu2, src2 = cost_model_mfu(
            lambda: lm2_jit.jitted.lower(lm2_state["v"],
                                         lm2_state["o"], tok2_d),
            dt2, peak, platform0, analytic_flops=fl2 / n_dev)
        log(f"stage B2: {tok_s2:.0f} tokens/s/chip, "
            f"loss {float(lm2_state['loss']):.3f}, "
            f"{tfl2:.4g} TFLOP/s/chip, MFU {mfu2}")
        emit({
            "metric": "transformer_lm_large_train_throughput",
            "value": round(tok_s2, 1),
            "unit": "tokens/s/chip",
            "vs_baseline": None,
            "extra": {"batch": B2, "seq": T2,
                      "embed": E2, "depth": L2, "vocab": V2,
                      "heads": H2, "kv_heads": HKV2, "window": W2,
                      "pos_emb": "rope", "attn_impl": attn2,
                      "fused_xent": platform0 == "tpu",
                      "step_ms": round(dt2 * 1000, 2),
                      "scan_steps_per_dispatch": K2,
                      # per-TRAIN-STEP like step_ms (each timing round
                      # dispatches K2 scanned steps), so the
                      # min(round_ms) == step_ms audit holds.
                      "round_ms": [round(t * 1e3 / K2, 2)
                                   for t in _metrics.last_round_times],
                      "dtype": "bfloat16",
                      "tflops_per_chip": round(tfl2, 4),
                      "mfu": mfu2, "flops_source": src2,
                      "peak_tflops": peak},
        })

    # (key, stage, does it run here?) — cheapest first.
    on_tpu = platform0 == "tpu"
    ladder = [("A", stage_a, True), ("B", stage_b, True),
              ("C", stage_c, on_tpu), ("C2", stage_c2, on_tpu),
              ("B2", stage_b2, on_tpu or tiny), ("D", stage_d, True),
              ("D2", lambda: stage_d(kd=KD2), on_tpu and KD2 > 1)]
    failed = []
    for key, stage, runs_here in ladder:
        if only_keys is not None and key not in only_keys:
            continue
        if not runs_here:
            log(f"stage {key}: skipped (needs a TPU)")
            continue
        try:
            stage()
        except Exception:  # noqa: BLE001 — report, run the rest, exit != 0
            log(f"stage {key} FAILED:\n{traceback.format_exc()}")
            failed.append(key)
    if failed:
        log(f"stages failed: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
