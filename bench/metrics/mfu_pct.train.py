"""The training step's model FLOPs per token (``bench/flops.py``, no
recomputation) times the window's tokens per second, over the chips'
bf16 peak."""
from bench.metrics._mfu import mfu_pct


def read(run):
    return mfu_pct(run)
