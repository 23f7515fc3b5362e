#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds <s>] [--out control.json]

On the chip, at the cell's own size, in one process: for every seed the
program's gap to the plain reference (the lower reading), the control's
(the reference computed in float8, the precision below the
configuration's bfloat16, in the program's place) and, where the kind
has them, the faults planted in the reference. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
# the TPU runtime would log under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    try:
        devices = harness.require_chips(int(cell.workload["chips"]))
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    compiles = harness.CompileWatch()
    out_dir = os.path.join(CHECKOUT, ".bench_runs", args.workload)

    def make_run(seed):
        return harness.Run(cell=cell, seed=int(seed), seconds=args.seconds,
                           trace=False, t_start=time.perf_counter(),
                           devices=devices, compiles=compiles,
                           out_dir=out_dir)

    seeds = [int(s) for s in args.seeds.split(",")]
    rows = cell.kind.control(make_run, seeds)
    text = json.dumps({"workload": args.workload, "device": {
        "kind": devices[0].device_kind, "count": len(devices)},
        "readings": rows})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
