"""Readings that a cell's limits are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload granite-3-2b.chat \
        --seeds 11,12,13 --seconds 1

For each seed, in one process: the cell's adapter runs the traffic for a
short window at the cell's own sizes, then compares the sampled requests
with the reference, and also reads the fp8 control at the same positions
(the reference put in the program's place one precision below bfloat16).
Prints one JSON line per seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        _, cell = harness.load_cell(harness.ROOT, args.workload, seeds[0],
                                    args.seconds, False)
        harness.import_program(harness.ROOT)
        harness.prepare_jax(harness.ROOT, cell)
    except harness.Refused as e:
        print(f"[calibrate] refused: {e}", file=sys.stderr)
        return 2
    entry = harness.load_module(
        harness.HERE / "entries" / f"{cell.traffic['entry']}.py", "entry")
    for seed in seeds:
        cell.seed, cell.t_start = seed, time.perf_counter()
        run = entry.run(cell, control=True)
        print(json.dumps({"workload": cell.name, "seed": seed, **run["numbers"],
                          "waves": len(run["records"]),
                          "seconds": time.perf_counter() - cell.t_start}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
