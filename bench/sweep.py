#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate at which the
backlog does not grow over the window.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4,5,6 \\
        [--seconds 20] [--seed 1] [--out sweep.json]

One process on the chip: the cell's set-up once, then one window per
rate with the cell's traffic at that rate (the same set of lengths at
every rate, drawn for the window's length). The backlog at time ``t`` is
the number of requests due by ``t`` that have no first token by ``t``;
it grows when its mean over the window's last quarter exceeds its mean
over the second quarter by more than one request. The knee is the
highest rate below the first that grows or fails a request. The result
and the rate chosen go in the traffic file of the cell by hand.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import dataclasses  # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

import numpy as np  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
# the TPU runtime would log under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness, traffic  # noqa: E402


def backlog(requests, served, t0: float, seconds: float,
            step: float = 0.1) -> np.ndarray:
    """Requests due but without a first token, every ``step`` seconds."""
    due = np.array([t0 + r.due_s for r in requests])
    first = np.array([served[r.rid].first_token_s
                      if r.rid in served and
                      served[r.rid].first_token_s is not None
                      else np.inf for r in requests])
    ts = t0 + np.arange(step, seconds + 1e-9, step)
    return np.array([np.sum((due <= t) & (first > t)) for t in ts])


def grows(b: np.ndarray) -> bool:
    q = len(b) // 4
    return bool(b[3 * q:].mean() > b[q:2 * q].mean() + 1)


def knee_of(rows: list) -> float | None:
    """The highest rate below the first whose backlog grows or that
    fails a request."""
    knee = None
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        if r["grows"] or r["failed"]:
            break
        knee = r["rate_per_s"]
    return knee


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
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
    kind = cell.kind

    def make_run(rate):
        c = dataclasses.replace(cell, traffic={**cell.traffic,
                                               "rate_per_s": rate})
        return harness.Run(cell=c, seed=args.seed, seconds=args.seconds,
                           trace=False, t_start=time.perf_counter(),
                           devices=devices, compiles=compiles,
                           out_dir=os.path.join(CHECKOUT, ".bench_runs",
                                                args.workload))

    rates = [float(r) for r in args.rates.split(",")]
    st = kind.setup(make_run(rates[0]))
    rows = []
    for rate in rates:
        run = make_run(rate)
        st.requests = traffic.serve_requests(run.traffic, run.seconds,
                                             run.seed, st.cfg.vocab_size)
        st.served, st.submit_s = {}, {}
        kind.window(run, st)
        st.engine.run_until_drained()
        b = backlog(st.requests, st.served, run.window_t0, run.seconds)
        row = {"rate_per_s": rate, "requests": len(st.requests),
               "failed": run.failed, **run.end_to_end,
               "backlog_q2": float(b[len(b) // 4:len(b) // 2].mean()),
               "backlog_q4": float(b[3 * (len(b) // 4):].mean()),
               "backlog_end": int(b[-1]), "grows": grows(b),
               "window_compiles": run.window_compiles,
               **run.counters.get("serving", {})}
        print(json.dumps(row), file=sys.stderr, flush=True)
        rows.append(row)
    knee = knee_of(rows)
    text = json.dumps({"workload": args.workload, "seconds": args.seconds,
                       "seed": args.seed, "knee_per_s": knee,
                       "device": {"kind": devices[0].device_kind},
                       "rows": rows})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
