"""Host milliseconds of ``kvcache.write_prompt`` per admitted request:
the program's ``serving/write_prompt`` spans in the window (one per
admitted request), their time over their count, from the traced run's
profile (``bench/metrics/_program.py``)."""
from bench.metrics import _program


def read(run):
    writes = _program.spans(_program.events(run), "serving/write_prompt")
    if not writes:
        return None
    return 1e-6 * sum(e - s for s, e in writes) / len(writes)
