"""The control of ``correct``, run by hand on the chip (the benchmark's own
runs never run it):

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed it drives the cell's own job at the cell's own size with
``reference.LowerPrecisionStore`` in the library's place (the reference, one
precision below what the configuration states) and prints the numbers that
were compared beside their limits.  Every seed has to come out not correct.
``--program 1`` also runs the library itself on each seed, in the same
process, for the lower readings.  The same control runs at toy size in
``chipbench/tests/test_accounting.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--program", type=int, default=0)
    parser.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = parser.parse_args()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness, reference

    cell = harness.Cell(args.benchmark, args.workload)
    devices = harness.open_chips(cell, rehearsal=os.environ.get("JAX_PLATFORMS") == "cpu")
    if devices is None:
        return 2
    load = cell.build_load(devices)
    bad = 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        sides = [("control", reference.LowerPrecisionStore)]
        if args.program:
            sides.append(("program", None))
        for side, manager in sides:
            r = harness.run_cell(cell, devices, seed, args.seconds, load=load, make_manager=manager)
            print(json.dumps({"workload": cell.name, "seed": seed, "side": side,
                              "correct": r["correct"], "attempted": r["attempted"],
                              "checks": r["checks"], "notes": r["notes"][:4]}), flush=True)
            if r["correct"] == (side == "control"):
                bad += 1
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
