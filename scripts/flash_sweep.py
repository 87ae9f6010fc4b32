#!/usr/bin/env python
"""On-chip flash-attention block sweep vs XLA dense attention.

Run on one TPU chip.  Prints per-config ms,
causal-credited TFLOP/s, and max|err| vs the library's dense oracle
(parallel.sequence.reference_attention — the same oracle the test suite
validates the kernel against).
"""

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from torchmpi_tpu.ops.flash import flash_attention
from torchmpi_tpu.parallel.sequence import reference_attention
from torchmpi_tpu.utils.metrics import timed

B, T, H, D = 4, 4096, 8, 128
CONFIGS = [(256, 256), (512, 256), (256, 512), (512, 512),
           (512, 1024), (1024, 512)]
# --wide (VERDICT r4 #2): candidates beyond the 512x512 plateau — the
# full-block mask-skip specialization shifts the VPU:MXU balance, so the
# old optimum must be re-derived, and larger blocks amortize per-block
# bookkeeping further (VMEM at 1024x1024: q+acc+2x(k,v) ~ 1.6 MiB, well
# inside scope).
WIDE_EXTRA = [(1024, 1024), (2048, 512), (512, 2048), (1024, 256),
              (768, 512), (512, 768), (2048, 1024)]
# Dependent-chain depth per dispatch: amortizes per-dispatch host
# overhead out of the per-kernel number (it otherwise sits in BOTH
# sides of every flash-vs-dense ratio).
CHAIN = 4


def bench(f, *a, iters=10):
    return timed(lambda: f(*a), iters)


def chained(attn_fn):
    """Dependent-chain jit (q <- out) via the shared harness helper:
    the dispatch floor is paid once and CSE cannot collapse the links."""
    from torchmpi_tpu.utils.metrics import chained as _chained

    return _chained(attn_fn, depth=CHAIN)


def sweep_shape(label, q, k, v, configs, *, window=None):
    """One (shape, window) sweep: dense oracle once, then each block
    config with chained floor-honest timing + on-device oracle check."""
    Bs, Ts, Hs, Ds = q.shape
    dj = jax.jit(functools.partial(reference_attention, causal=True,
                                   window=window))
    od = dj(q, k, v)
    t = bench(chained(functools.partial(reference_attention, causal=True,
                                        window=window)), q, k, v) / CHAIN
    print(f"[{label}] dense: {t*1e3:.2f} ms/invocation (chained x{CHAIN})",
          flush=True)

    if window is None:
        flops = 2 * Bs * Hs * Ts * Ts * Ds * 2 * 0.5  # causal-credited
    else:
        avg_ctx = ((window / 2) * window + (Ts - window) * window) / Ts \
            if Ts > window else Ts / 2
        flops = 2 * Bs * Hs * Ts * avg_ctx * Ds * 2
    best = None
    for bq, bk in configs:
        f1 = functools.partial(flash_attention, causal=True, window=window,
                               block_q=bq, block_k=bk, interpret=False)
        fj = jax.jit(f1)
        try:
            of = fj(q, k, v)
            err = float(jnp.max(jnp.abs(of.astype(jnp.float32)
                                        - od.astype(jnp.float32))))
            t = bench(chained(f1), q, k, v) / CHAIN
            tfl = flops / t / 1e12
            print(f"[{label}] flash {bq}x{bk}: {t*1e3:.2f} ms/invocation "
                  f"(chained x{CHAIN})  {tfl:.1f} TFLOP/s  "
                  f"err {err:.4f}", flush=True)
            if best is None or tfl > best[2]:
                best = (bq, bk, tfl)
        except Exception as e:  # noqa: BLE001 — sweep continues
            print(f"[{label}] flash {bq}x{bk}: FAIL {type(e).__name__}: "
                  f"{str(e)[:120]}", flush=True)
    if best:
        print(f"[{label}] BEST {best[0]}x{best[1]} {best[2]:.1f} TFLOP/s",
              flush=True)
    return best, flops


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--wide", action="store_true",
                   help="extended candidate blocks + the stage-B' "
                        "GQA/window shape")
    args = p.parse_args()

    import torchmpi_tpu as mpi

    # Explicit prescale=False baseline: an exported
    # TORCHMPI_TPU_FLASH_PRESCALE=1 must not make the "direct" side of
    # the A/B run prescaled too (code review r5).
    mpi.init(mpi.Config.from_env(flash_prescale=False))

    def prescale_ab(label, q, k, v, best, flops, window=None):
        """Re-time the winning block config with Config.flash_prescale
        on (the scale folded into q; kernel runs scale=1) — the A/B
        that decides whether to adopt the knob as default."""
        if not best:
            return
        bq, bk, base_tfl = best
        mpi.set_config(flash_prescale=True)
        try:
            f1 = functools.partial(flash_attention, causal=True,
                                   window=window, block_q=bq, block_k=bk,
                                   interpret=False)
            t = bench(chained(f1), q, k, v) / CHAIN
            tfl = flops / t / 1e12
            print(f"[{label}] prescale@{bq}x{bk}: {t*1e3:.2f} ms "
                  f"{tfl:.1f} TFLOP/s (vs {base_tfl:.1f} direct)",
                  flush=True)
        finally:
            mpi.set_config(flash_prescale=False)

    configs = CONFIGS + (WIDE_EXTRA if args.wide else [])
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, T, H, D), jnp.bfloat16)
    k = jnp.asarray(rs.randn(B, T, H, D), jnp.bfloat16)
    v = jnp.asarray(rs.randn(B, T, H, D), jnp.bfloat16)
    best, flops = sweep_shape(f"mha B{B} T{T} H{H}", q, k, v, configs)
    if args.wide:
        # --wide adds the prescale A/B runs and the flagship shape on
        # top of the extended block candidates.
        prescale_ab(f"mha B{B} T{T} H{H}", q, k, v, best, flops)

        # The flagship stage-B' attention shape: GQA 16q/4kv, T=2048,
        # sliding window 1024 — the config whose cost sits inside the
        # headline MFU (VERDICT r4 #2 done-criterion: B' MFU >= 0.62).
        B2, T2, H2, HKV2, W2 = 4, 2048, 16, 4, 1024
        q2 = jnp.asarray(rs.randn(B2, T2, H2, D), jnp.bfloat16)
        k2 = jnp.asarray(rs.randn(B2, T2, HKV2, D), jnp.bfloat16)
        v2 = jnp.asarray(rs.randn(B2, T2, HKV2, D), jnp.bfloat16)
        best2, flops2 = sweep_shape(f"gqa B{B2} T{T2} H{H2}/{HKV2} w{W2}",
                                    q2, k2, v2, configs, window=W2)
        prescale_ab(f"gqa B{B2} T{T2} H{H2}/{HKV2} w{W2}", q2, k2, v2,
                    best2, flops2, window=W2)


if __name__ == "__main__":
    main()
