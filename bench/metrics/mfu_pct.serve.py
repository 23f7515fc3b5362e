"""The model FLOPs of the prefills and decoded tokens that finished in
the window (no padding, ``bench/flops.py``), over the window and the
chips' bf16 peak."""
from bench.metrics._mfu import mfu_pct


def read(run):
    return mfu_pct(run)
