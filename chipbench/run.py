#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once, in this process, on this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced).  With ``--trace 0`` the metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics.  Progress goes to standard error.

The run fails (non-zero exit, no result line) when jax finds no TPU or
another number of chips than the cell asks for.  ``--rehearse`` is the one
way around that: the same files and code at the tiny sizes the data files
carry under ``rehearse``, on as many virtual CPU devices as the cell has
chips, Pallas kernels interpreted.  Its line says ``platform: cpu``; its
numbers say that the control flow works and nothing about speed.

jax is imported only after the arguments are parsed, and only here.
"""

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the window (default: run_seconds)")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on virtual CPU devices (no chip)")
    p.add_argument("--manifest", default=harness.DEFAULT_MANIFEST,
                   help="another BENCHMARK.json, with files of its own in "
                        "a chipbench/ beside it")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    manifest = harness.load_manifest(args.manifest)
    cell = harness.resolve(manifest, args.workload, rehearse=args.rehearse)
    if args.seconds is None:
        args.seconds = manifest["run_seconds"]
    if args.rehearse:
        # Before jax is imported: the rehearsal owns the platform.
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={cell.chips}"
        ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    if args.rehearse:
        # Two interpreted Pallas calls must not overlap on the
        # interpreter's process-global barrier (tests/conftest.py), and
        # the loop keeps a step in flight.
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        harness.log(f"no TPU: jax found platform {platform!r}.  The "
                    "benchmark measures on the chip; --rehearse runs the "
                    "cell's control flow on the CPU.")
        return 2
    if len(devices) != cell.chips:
        harness.log(f"{cell.name} needs exactly {cell.chips} chip(s), jax "
                    f"found {len(devices)}")
        return 2
    runner = harness.load_module(manifest, "runners", cell.config["runner"])
    result = runner.run(cell, args)
    sys.stderr.flush()
    print(harness.contract_line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
