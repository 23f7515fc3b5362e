"""Host milliseconds of a decode step less the runtime's wait for the
device: the median, over the program's ``serving/decode_step`` spans in
the window that ran the step, of their duration less that of the
``runtime/wait`` inside them (block tables and inputs, ``plan.execute``'s
dispatch, the sampling sync's host side), from the traced run's profile
(``bench/metrics/_program.py``)."""
from bench import stats
from bench.metrics import _program


def read(run):
    ticks = _program.self_ms(_program.events(run), "serving/decode_step",
                             "runtime/wait", need_inner=True)
    return stats.median(ticks) if ticks else None
