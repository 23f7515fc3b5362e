"""The device's idle share of the traced window, averaged over chips."""


def idle_pct(run):
    t = run.trace_summary
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
