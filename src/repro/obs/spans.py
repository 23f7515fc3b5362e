"""Structured spans and counters — the in-process half of ``repro.obs``.

One process-global :class:`Tracer` collects Chrome-trace-event-shaped
records (complete spans, instants, counters) from anywhere in the
stack: partitioner stages, the compiled runtime's dispatch loop, the
serving engine's request lifecycle. The buffer is drained into a
Perfetto-loadable JSON document by ``repro.obs.trace``.

Two sinks
---------
A span goes to each of two sinks that is on:

* the in-memory tracer above (:func:`enable` / ``REPRO_TRACE``);
* the JAX profiler: while a profiler session is active
  (``jax.profiler.start_trace``), every span is also a
  ``jax.profiler.TraceAnnotation`` named ``repro/<span name>``, with the
  span's arguments as its metadata, on the device trace's clock. There
  is no flag: a profiled run gets the program's spans.

This module imports no JAX. The profiler probe
(``TraceAnnotation.is_enabled``) is looked up once something else has
imported JAX; before that no session can be active. Instants and
counters go to the in-memory tracer only.

Overhead policy
---------------
Tracing is **off by default** and the disabled path is engineered to be
invisible in hot loops:

* ``span(name)`` with no kwargs performs one attribute load, one branch
  and the profiler probe (about 45 ns once JAX is imported), then
  returns a shared immutable no-op singleton — **zero allocations**,
  with or without JAX imported (pinned by ``tests/test_obs.py`` with
  ``tracemalloc``).
* Call sites that build event arguments guard on :func:`active` first,
  so argument dicts are never constructed when both sinks are off.
* The acceptance budget is <2% wall overhead on
  ``benchmarks/bench_overhead.py --runtime`` with tracing disabled.

When enabled (``REPRO_TRACE=1`` / ``REPRO_TRACE=/path/out.json`` in the
environment, or :func:`enable` programmatically), each span costs one
``perf_counter`` pair and a tuple append; expect low single-digit
percent overhead on dispatch-bound runtimes and effectively none on
compute-bound ones. ``list.append`` is atomic under the GIL and the
thread id is recorded per event, so spans from worker threads land in
their own lanes without locking the hot path.

Exit-time behaviour: when ``REPRO_TRACE`` names a path (anything other
than ``0``/``1``/``true``/``false``), the collected buffer is exported
there at interpreter exit via :mod:`atexit` — a zero-code-change way to
trace any existing script or test.
"""
from __future__ import annotations

import atexit
import functools
import os
import sys
import threading
import time
from typing import Any

# Chrome trace-event phase codes used throughout repro.obs.
PH_COMPLETE = "X"
PH_INSTANT = "i"
PH_COUNTER = "C"
PH_METADATA = "M"

#: pid of the live in-process lanes (host threads). The trace exporter
#: reserves further pids for measured / predicted device lanes.
HOST_PID = 0

#: name prefix of the spans written into the JAX profiler's trace
PROFILER_PREFIX = "repro/"


class Tracer:
    """Collects trace events. One process-global instance normally; the
    class is instantiable so tests can run isolated tracers."""

    def __init__(self) -> None:
        self.enabled = False
        # (ph, name, cat, pid, tid, ts_us, dur_us, args) tuples;
        # list.append is GIL-atomic, so no lock on the record path.
        self.events: list[tuple] = []
        self._t0 = time.perf_counter()
        self._meta_lock = threading.Lock()
        self._thread_names: dict[int, str] = {}

    # -- clock ----------------------------------------------------------
    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def epoch(self) -> float:
        """perf_counter value of trace time zero (for aligning externally
        captured timestamps, e.g. runtime timelines, into span time)."""
        return self._t0

    # -- record ---------------------------------------------------------
    def complete(self, name: str, ts_us: float, dur_us: float,
                 cat: str = "repro", args: dict | None = None,
                 tid: int | None = None) -> None:
        self.events.append((PH_COMPLETE, name, cat, HOST_PID,
                            threading.get_ident() if tid is None else tid,
                            ts_us, dur_us, args))

    def instant(self, name: str, cat: str = "repro",
                args: dict | None = None) -> None:
        self.events.append((PH_INSTANT, name, cat, HOST_PID,
                            threading.get_ident(), self.now_us(), 0.0,
                            args))

    def counter(self, name: str, values: dict, cat: str = "repro") -> None:
        self.events.append((PH_COUNTER, name, cat, HOST_PID,
                            threading.get_ident(), self.now_us(), 0.0,
                            dict(values)))

    def name_thread(self, name: str, tid: int | None = None) -> None:
        tid = threading.get_ident() if tid is None else tid
        with self._meta_lock:
            self._thread_names[tid] = name

    def thread_names(self) -> dict[int, str]:
        with self._meta_lock:
            return dict(self._thread_names)

    # -- drain ----------------------------------------------------------
    def drain(self) -> list[tuple]:
        """Return and clear the collected events (names map is kept)."""
        out, self.events = self.events, []
        return out

    def clear(self) -> None:
        self.events = []


class _Span:
    """Live span: records a complete ("X") event on exit, and holds the
    profiler's annotation while a profiler session is active. Nesting is
    correct by construction — Perfetto stacks same-thread X events by
    their [ts, ts+dur] containment, and a with-block exits LIFO."""
    __slots__ = ("_tracer", "name", "cat", "args", "_start", "_ann")

    def __init__(self, tracer: Tracer, name: str, cat: str,
                 args: dict | None, ann: Any = None) -> None:
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = ann

    def set_metadata(self, **args: Any) -> None:
        """Add arguments known only once the span's work is done."""
        self.args = {**(self.args or {}), **args}
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._ann.__enter__()
        self._start = self._tracer.now_us()
        return self

    def __exit__(self, *exc) -> None:
        t = self._tracer
        t.complete(self.name, self._start, t.now_us() - self._start,
                   self.cat, self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)


class _NullSpan:
    """Shared no-op span for the disabled path — a singleton so the
    disabled ``span()`` call allocates nothing."""
    __slots__ = ()

    def set_metadata(self, **args: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()
_TRACER = Tracer()
# jax.profiler.TraceAnnotation, once JAX has been imported
_ANNOTATION: Any = None


def _profiling() -> bool:
    """Whether a JAX profiler session is active. Until JAX is imported
    there can be none; after, the probe is ``TraceAnnotation.is_enabled``
    (one flag read in C++)."""
    global _ANNOTATION
    ann = _ANNOTATION
    if ann is None:
        ann = getattr(sys.modules.get("jax._src.profiler"),
                      "TraceAnnotation", None)
        if ann is None:
            return False
        _ANNOTATION = ann
    return ann.is_enabled()


def get_tracer() -> Tracer:
    return _TRACER


def enabled() -> bool:
    """Whether the in-memory tracer records."""
    return _TRACER.enabled


def active() -> bool:
    """Whether either sink takes spans: call sites guard building span
    arguments on it."""
    return _TRACER.enabled or _profiling()


def enable(on: bool = True) -> None:
    _TRACER.enabled = bool(on)


def span(name: str, cat: str = "repro", **args: Any):
    """Context manager timing a named region into each sink that is on.
    Both off: returns the shared no-op singleton (zero allocation when
    called without kwargs). ``set_metadata(**args)`` on the returned
    span adds arguments known only at its end."""
    t = _TRACER
    if t.enabled:
        ann = (_ANNOTATION(PROFILER_PREFIX + name, **args)
               if _profiling() else None)
        return _Span(t, name, cat, args or None, ann)
    if _profiling():
        return _ANNOTATION(PROFILER_PREFIX + name, **args)
    return _NULL_SPAN


def traced(name: str, cat: str = "repro"):
    """Decorator form of :func:`span` — wraps a whole function body.
    Both sinks off costs one extra call frame and two branches."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not (_TRACER.enabled or _profiling()):
                return fn(*a, **kw)
            with span(name, cat):
                return fn(*a, **kw)
        return wrapper
    return deco


def instant(name: str, cat: str = "repro", **args: Any) -> None:
    """Point-in-time marker (e.g. a transfer prefetch, an eviction)."""
    t = _TRACER
    if t.enabled:
        t.instant(name, cat, args or None)


def counter(name: str, cat: str = "repro", **values: float) -> None:
    """Counter sample (e.g. KV block-pool occupancy); Perfetto renders
    these as stacked area tracks."""
    t = _TRACER
    if t.enabled:
        t.counter(name, values, cat)


def _env_value() -> str:
    return os.environ.get("REPRO_TRACE", "").strip()


def _atexit_export() -> None:
    val = _env_value()
    if not _TRACER.events or val.lower() in ("", "0", "1", "true", "false"):
        return
    from .trace import export_spans
    try:
        export_spans(path=val)
    except OSError:
        pass  # tracing must never take the process down at exit


_env = _env_value()
if _env and _env.lower() not in ("0", "false"):
    _TRACER.enabled = True
    atexit.register(_atexit_export)


__all__ = ["Tracer", "get_tracer", "enabled", "active", "enable", "span",
           "traced", "instant", "counter", "HOST_PID", "PROFILER_PREFIX",
           "PH_COMPLETE", "PH_INSTANT", "PH_COUNTER", "PH_METADATA"]
