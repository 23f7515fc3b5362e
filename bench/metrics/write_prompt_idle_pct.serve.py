"""Share of the traced window in which the chips idle while the host is
in ``kvcache.write_prompt``: the idle gaps whose middle falls in a
``serving/write_prompt`` span as the innermost span open, over the
window, averaged over chips (``bench/metrics/_program.py``)."""
from bench.metrics import _program


def read(run):
    if not _program.spans(_program.events(run), "serving/write_prompt"):
        return None
    t = _program.idle_by_span(run)
    gaps = dict(t["idle_gaps"])
    return 100.0 * gaps.get(_program.PREFIX + "serving/write_prompt",
                            0.0) / t["window_s"]
