"""Median host milliseconds of the engine ticks that only decoded: the
paged decode step through ``plan.execute`` for every active request."""
from bench import stats


def read(run):
    ticks = [e - s for n, s, e, a in run.spans.rows
             if n == "tick" and not a.get("admitted") and a.get("decoded")
             and s >= run.window_t0]
    return 1e3 * stats.median(ticks) if ticks else None
