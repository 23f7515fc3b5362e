"""From a profiler trace to busy and idle time per chip, the device
operations that took most time, and the idle gaps by what the host was
doing.

The trace is JAX's ``.xplane.pb``. Device operations are the events of
the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, named by
:func:`op_label`; the host's spans
are the harness's ``jax.profiler.TraceAnnotation`` events, named
``bench/<span>``, on the same clock. ``bench/window`` bounds the
measured window: only what falls inside it counts.

* busy: the union of the intervals in which an operation ran;
* idle: the window less busy; each idle gap is put down to the
  innermost harness span that was open at the gap's middle
  (``outside spans`` where none was);
* every figure is averaged over the chips traced.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW = "bench/window"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HLO = re.compile(r"^%?(\S+) = (.*?) ([\w-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(name: str) -> str:
    """``fusion.205 f32[4096,4096] fusion`` from the HLO text that names
    a TPU op event (``%fusion.205 = f32[4096,4096]{1,0:T(8,128)} fusion(
    ...)``): its name, result type without layouts, and opcode."""
    m = _HLO.match(name)
    if not m:
        return name
    return f"{m.group(1)} {_LAYOUT.sub('', m.group(2))} {m.group(3)}"


def load_events(trace_dir: str) -> dict:
    """``{"device": {id: [(name, start_ns, dur_ns)]}, "host": [...]}``
    from the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    device: dict = {}
    host: list = []
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            ops = device.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((op_label(e.name), float(e.start_ns),
                                float(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench/"))
    return {"device": device, "host": host}


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _innermost(spans: list, starts: list, t: float) -> str:
    """The harness span (not the window) open at ``t`` that started
    last: the innermost one, since the harness's spans nest. ``spans``
    is sorted by start, ``starts`` holds their starts."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        name, s, d = spans[i]
        if s + d >= t:
            return name
        i -= 1
    return "outside spans"


def reduce(events: dict, devices: list | None = None) -> dict:
    """Busy and idle seconds, top device operations and idle gaps by
    host span, averaged over ``devices`` (default: every TPU plane)."""
    windows = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW} span")
    w0, w1 = windows[-1]
    spans = sorted(((n[len("bench/"):], s, d) for n, s, d in events["host"]
                    if n != WINDOW), key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    devs = sorted(events["device"]) if devices is None else list(devices)
    busy_total = 0.0
    op_time: dict = defaultdict(float)
    gap_time: dict = defaultdict(float)
    for dev in devs:
        clipped = []
        for name, s, d in events["device"].get(dev, []):
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                op_time[name] += (b - a) * 1e-9
        busy = _union(clipped)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gap_time[_innermost(spans, starts, (a + b) / 2)] += (b - a) * 1e-9
    n = max(len(devs), 1)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])
    gaps = sorted(gap_time.items(), key=lambda kv: -kv[1])
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_total / n,
            "devices": devs,
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[k, v / n] for k, v in gaps]}


def reduce_dir(trace_dir: str, devices: list | None = None) -> dict:
    return reduce(load_events(trace_dir), devices)
