"""Share of the traced window in which no operation ran on the chips
(``bench/trace_reduce.py``)."""
from bench.metrics._idle import idle_pct


def read(run):
    return idle_pct(run)
