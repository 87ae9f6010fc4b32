#!/usr/bin/env python3
"""Run one of the served cells' tools (``serve_sweep.py``,
``serve_precision.py``) for a cell whose configuration names ANOTHER runner
than ``serve_open_loop``: those tools load that runner by name, and a runner
such as ``serve_open_loop_experts`` is that same module with what it must
rebind, bound when it is imported.  So: import the cell's own runner first,
then run the tool as ``__main__`` with the remaining arguments.

    python3 chipbench/tools/with_cell_runner.py imoe-16b-serve-conv-sat \\
        serve_sweep.py --workload imoe-16b-serve-conv-sat --from 1.5 ...
"""

import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), HERE]

from chipbench import harness  # noqa: E402


def main():
    workload, tool = sys.argv[1:3]
    manifest = harness.load_manifest()
    cell = harness.resolve(manifest, workload)
    harness.load_module(manifest, "runners", cell.config["runner"])
    sys.argv = [os.path.join(HERE, tool)] + sys.argv[3:]
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
