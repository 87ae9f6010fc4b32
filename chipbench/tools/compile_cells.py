#!/usr/bin/env python3
"""Compile each cell's step at REAL size for a described ``v5e:2x2`` and
print the compiler's ``memory_analysis()``.  A scratch script to run by
hand before a chip call (no chip needed, no test: it describes a topology
at top level, which no test file may).  Nothing runs, so it says nothing
about results or times.

    JAX_PLATFORMS=cpu python3 chipbench/tools/compile_cells.py [workload ...] [key=value ...]

``num_hidden_layers=4`` overrides a configuration or traffic key for a
what-if.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

import torchmpi_tpu as mpi  # noqa: E402
from chipbench import harness  # noqa: E402
from torchmpi_tpu.ops import ring  # noqa: E402


def main(argv):
    sets = dict(a.split("=", 1) for a in argv if "=" in a)
    names = [a for a in argv if "=" not in a]
    manifest = harness.load_manifest()
    names = names or [w["name"] for w in manifest["workloads"]]
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    ring.set_interpret(False)     # the runtime's own mesh is the CPU's
    for name in names:
        cell = harness.resolve(manifest, name)
        for k, v in sets.items():
            if k in cell.config:
                cell.config[k] = json.loads(v)
            elif k in cell.traffic:
                cell.traffic[k] = json.loads(v)
        devs = np.asarray(topo.devices[:cell.chips])
        mesh = Mesh(devs.reshape((1, cell.chips)), mpi.WORLD_AXES)
        prog = harness.load_module(manifest, "steps",
                                   cell.config["step"]).programs(cell, mesh)
        key = jax.ShapeDtypeStruct((2,), np.uint32)
        put = lambda spec: lambda s: jax.ShapeDtypeStruct(  # noqa: E731
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec))
        state = jax.tree.map(put(P()), jax.eval_shape(prog.init, key))
        batch = jax.tree.map(put(P(mesh.axis_names)),
                             jax.eval_shape(prog.batches, key)[0])
        t = time.perf_counter()
        compiled = prog.step.jitted.lower(*state, *batch).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        gb = lambda b: round(b / 1e9, 3)  # noqa: E731
        print(json.dumps({
            "cell": name, "set": sets, "compile_s": round(
                time.perf_counter() - t, 1),
            "argument_GB": gb(ma.argument_size_in_bytes),
            "output_GB": gb(ma.output_size_in_bytes),
            "alias_GB": gb(ma.alias_size_in_bytes),
            "temp_GB": gb(ma.temp_size_in_bytes),
            "peak_GB_per_device": gb(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "all_reduce_ops": text.count(" all-reduce("),
        }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
