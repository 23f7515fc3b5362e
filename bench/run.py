#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout. The cell's files are found by name
(``bench/harness.py``); the program under test is ``src/repro``. The
run refuses any platform but a TPU, and fewer chips than the cell asks
for: it then exits non-zero and prints no result.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the window and reports the per-layer
metrics, the device's busy time and the breakdown. Every run compares
what the timed path produced with the plain reference and prints each
number compared beside its limit: as the last lines of standard error,
and under ``checks``, the last key of the result line, which is the
last line of standard output.
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


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.find_cell(args.workload)
    try:
        devices = harness.require_chips(int(cell.workload["chips"]))
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    cache = harness.enable_compile_cache()
    out_dir = os.path.join(CHECKOUT, ".bench_runs", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    print(f"[{args.workload}] {len(devices)} x {devices[0].device_kind}; "
          f"seed {args.seed}; compile cache {cache}", file=sys.stderr,
          flush=True)
    run = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START,
                           devices=devices, out_dir=out_dir)
    line = harness.result_line(run)
    harness.print_checks(run)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
