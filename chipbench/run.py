"""Runs one cell of BENCHMARK.json once and prints the contract's line last.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: load, warm up, drive the window, compare, print, exit.  The
cell names a configuration (``chipbench/configs/<name>.json``, which names
its builder) and a traffic mix (``chipbench/traffic/<name>.json``, which the
one generator in ``job.py`` reads, each operation it names being
``chipbench/ops/<name>.py``); each metric is read by
``chipbench/metrics/<name>.py``.  Nothing here knows a cell, a
configuration, a mix, an operation or a metric by name.

It needs the chips the cell asks for and fails without them, printing no
result.  The rehearsal (``--rehearsal`` together with ``JAX_PLATFORMS=cpu``,
used by chipbench/tests) drives the same code on the CPU and marks its line
``"rehearsal": true``: nothing in such a line is a device number.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--rehearsal", action="store_true")
    parser.add_argument("--keep-trace", default=None, help="copy the .xplane.pb here")
    args = parser.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness

    cell = harness.Cell(args.benchmark, args.workload)
    rehearsal = args.rehearsal and os.environ.get("JAX_PLATFORMS", "") == "cpu"
    found = harness.open_chips(cell, rehearsal)  # the cell's devices
    if found is None:
        return 2
    result = harness.run_cell(
        cell, found, args.seed, args.seconds, bool(args.trace), keep_trace=args.keep_trace
    )
    if rehearsal:
        result = {"rehearsal": True, **result}
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
