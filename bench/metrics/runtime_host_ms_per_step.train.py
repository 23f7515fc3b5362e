"""Host milliseconds of the segment runtime per training step: the mean,
over the program's ``plan/execute`` spans in the window, of their
duration less that of the ``runtime/wait`` inside them (``verify``, the
inputs' ``device_put``, segment dispatch and the stats), from the
traced run's profile (``bench/metrics/_program.py``)."""
from bench.metrics import _program


def read(run):
    steps = _program.self_ms(_program.events(run), "plan/execute",
                             "runtime/wait")
    return sum(steps) / len(steps) if steps else None
