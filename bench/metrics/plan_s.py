"""Host seconds to a verified plan: ``repro.trace`` of the step,
``repro.partition`` and ``plan.verify()`` (the harness's ``plan`` span)."""


def read(run):
    spans = run.spans.durations("plan")
    return sum(spans) if spans else None
