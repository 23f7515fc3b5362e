"""Model FLOPs of the window's work over the time and the chips' peak."""
from bench import flops


def mfu_pct(run):
    work = run.counters.get("window_flops")
    if not work:
        return None
    chips = len(run.devices)
    return 100.0 * work / (run.window_s * chips
                           * flops.peak(run.devices[0].device_kind))
