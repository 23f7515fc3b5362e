"""Gigabytes the segment runtime copied between chips per training step:
the runtime's own counter (``RuntimeStats.transfer_bytes``) after each
step of the window, averaged over those steps."""


def read(run):
    steps = run.counters.get("transfer_bytes_per_step")
    return sum(steps) / len(steps) / 1e9 if steps else None
