"""The program's own spans in a traced run's profile.

The program (``repro.obs``) writes each of its spans into the profiler's
trace, while a profiler session is active, as a
``jax.profiler.TraceAnnotation`` named ``repro/<span name>``, on the
device trace's clock; a span with arguments may carry them after a
``#`` in its name. This module reads them once per run, with the
harness's own ``bench/`` spans and the chips' ``XLA Ops``, into the
shape ``bench/trace_reduce.py`` reduces:

    {"device": {id: [(op, start_ns, dur_ns)]},
     "host": [(name, start_ns, dur_ns)]}

Names are kept as the trace has them; :func:`span_name` cuts the
metadata off.

The readers of the per-layer metrics take from it:

* :func:`spans`: the program's spans of one name that lie wholly inside
  the measured window (``bench/window``);
* :func:`self_ms`: per such span, its host time less that of the spans
  of another name inside it (the runtime's wait for the device);
* :func:`idle_by_span`: the window's device idle put down to the
  innermost span open at each gap's middle, harness and program spans
  alike.

A trace of a program without these spans holds none of them, and the
readers then return ``None``.
"""
from __future__ import annotations

import bisect
import glob
import os

from bench import trace_reduce

PREFIX = "repro/"


def load_events(trace_dir: str) -> dict | None:
    """The events of the newest ``.xplane.pb`` under ``trace_dir``, or
    ``None`` where there is none."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    from jax.profiler import ProfileData
    data = ProfileData.from_file(files[-1])
    device: dict = {}
    host: list = []
    for plane in data.planes:
        m = trace_reduce._TPU_PLANE.match(plane.name)
        if m:
            ops = device.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((trace_reduce.op_label(e.name),
                                float(e.start_ns), float(e.duration_ns))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(("bench/", PREFIX)))
    return {"device": device, "host": host}


def span_name(name: str) -> str:
    """A host event's name without the metadata a ``#`` may append."""
    return name.split("#", 1)[0]


def events(run) -> dict | None:
    """The run's events, read once and kept in ``run.info``."""
    if "program_events" not in run.info:
        run.info["program_events"] = load_events(run.trace_dir)
    return run.info["program_events"]


def _window(ev: dict) -> tuple[float, float] | None:
    w = [(s, s + d) for n, s, d in ev["host"]
         if span_name(n) == trace_reduce.WINDOW]
    return w[-1] if w else None


def spans(ev: dict | None, name: str) -> list[tuple[float, float]]:
    """``(start_ns, end_ns)`` of each program span ``name`` (without the
    prefix) that lies wholly inside the window, by start."""
    w = _window(ev) if ev else None
    if w is None:
        return []
    full = PREFIX + name
    return sorted((s, s + d) for n, s, d in ev["host"]
                  if span_name(n) == full and s >= w[0] and s + d <= w[1])


def self_ms(ev: dict | None, outer: str, inner: str,
            need_inner: bool = False) -> list[float]:
    """Per ``outer`` span in the window, milliseconds of its duration
    less that of the ``inner`` spans inside it; with ``need_inner``,
    only the ``outer`` spans that hold an ``inner`` one."""
    kids = spans(ev, inner)
    starts = [s for s, _ in kids]
    out = []
    for s, e in spans(ev, outer):
        i = bisect.bisect_left(starts, s)
        inside = 0.0
        held = False
        while i < len(kids) and kids[i][0] <= e:
            if kids[i][1] <= e:
                inside += kids[i][1] - kids[i][0]
                held = True
            i += 1
        if held or not need_inner:
            out.append((e - s - inside) * 1e-6)
    return out


def idle_by_span(run) -> dict | None:
    """``bench/trace_reduce.reduce`` over the harness's and the
    program's spans together, on the run's chips; kept in
    ``run.info``."""
    if "program_idle" not in run.info:
        ev = events(run)
        out = None
        if ev is not None and _window(ev) is not None:
            # reduce() cuts its "bench/" prefix off every span name: a
            # program span is handed to it as "bench/repro/<name>"
            host = []
            for n, s, d in ev["host"]:
                n = span_name(n)
                host.append((n if n.startswith("bench/") else "bench/" + n,
                             s, d))
            out = trace_reduce.reduce(
                {"device": ev["device"], "host": host},
                devices=[d.id for d in run.devices])
        run.info["program_idle"] = out
    return run.info["program_idle"]
