#!/usr/bin/env python3
"""The two readings each limit of ``smallthinker-21b-a3b``'s ``tolerance``
is set between (PERF.md section 4), ON THE CHIP at the published widths:
what the program gives over some seeds, and what a program ONE PRECISION
BELOW the one the configuration states would hand the check
(``steps/lm_experts_fused_xent.control_forward``: the reference with the
router's operands rounded to bfloat16 where float32 is stated, or the
experts' operands to float8_e4m3fn where bfloat16 is stated).  Every one
goes through ``forward_check`` itself: the program's has to come out ok,
each control's NOT ok, and the exit code is 1 otherwise.

    chiprun --chips 1 --timeout 1800 -- python3 \\
        chipbench/tools/smallthinker_precision.py [--excess-precision] seed ...

One JSON line a seed, with the device and the sizes it ran at, to standard
output and appended to ``chiprun_out/smallthinker_precision.jsonl``.  No
chip, no reading: a platform other than a TPU of ``peaks.json`` is refused,
since a CPU run at the rehearsal's sizes says nothing about these limits.
``--excess-precision`` adds the program's forward pass as XLA compiles it
by default (the step file says what that does to the router's input).
The lines PERF.md's table was made from are
``chipbench/tools/readings/pr26_precision.jsonl``.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import torchmpi_tpu as mpi  # noqa: E402
from chipbench import flops, harness  # noqa: E402
from torchmpi_tpu.utils import compilecache  # noqa: E402

CELL = "st-21b-ep4-t8k"
OUT = os.path.join(harness.ROOT, "chiprun_out", "smallthinker_precision.jsonl")
CONTROLS = {"router_bf16": {"round_router_to": jnp.bfloat16},
            "experts_fp8": {"round_experts_to": jnp.float8_e4m3fn}}
SIZES = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "moe_ffn_hidden_size", "router_width", "experts_held",
         "moe_num_active_primary_experts", "num_hidden_layers", "vocab_size",
         "sliding_window_size", "compute_dtype", "embedding_std")


def chip():
    """The device, if it is a TPU whose peaks the benchmark knows."""
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"{device.platform} {device.device_kind}: these readings "
                 "are the chip's; run through chiprun")
    flops.peak_for(device.device_kind)      # raises on an unknown kind
    return device


def main(argv):
    seeds = [int(a) for a in argv if not a.startswith("--")] or [1, 2, 3]
    device = chip()
    compilecache.enable_persistent_cache()   # a later seed finds the first's
    mpi.init()
    manifest = harness.load_manifest()
    cell = harness.resolve(manifest, CELL)
    cfg = cell.config
    step = harness.load_module(manifest, "steps", cfg["step"])
    t_chk = min(cfg["reference"]["check_tokens"], cell.traffic["seq"])
    where = {"device_kind": device.device_kind, "platform": device.platform,
             "check_tokens": t_chk, "sizes": {k: cfg[k] for k in SIZES},
             "tolerance": {k: v for k, v in cfg["tolerance"].items()
                           if k != "reason"}}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    as_expected = True
    for seed in seeds:
        k_init, k_data = jax.random.split(harness.seed_key(seed))
        params = jax.jit(lambda k: step.draw_params(cell, k))(k_init)
        tokens = jax.random.randint(
            jax.random.fold_in(k_data, 0), (1, cell.traffic["seq"]), 0,
            cfg["vocab_size"])[:, :t_chk]     # the ring's first batch

        def check(got):
            record = step.forward_check(cell, params, tokens, got)
            del record["what"]
            return record

        line = {"seed": seed, **where,
                "program": check(step.program_forward(cell, params, tokens))}
        if "--excess-precision" in argv:
            line["program_excess_precision"] = check(step.program_forward(
                cell, params, tokens, compiler_options=None))
        for name, rounding in CONTROLS.items():
            line[name] = check(step.control_forward(cell, params, tokens,
                                                    **rounding))
        line["as_expected"] = bool(line["program"]["ok"] and not any(
            line[name]["ok"] for name in CONTROLS))
        as_expected &= line["as_expected"]
        text = json.dumps(line)
        print(text, flush=True)
        with open(OUT, "a") as f:
            f.write(text + "\n")
    mpi.stop()
    sys.exit(0 if as_expected else 1)


if __name__ == "__main__":
    main(sys.argv[1:])
