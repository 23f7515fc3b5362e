"""The benchmark's harness: finds a cell's files by name, runs its kind,
and prints the result line.

Everything that belongs to one cell is found by name, so a later change
adds a configuration, a traffic mix, a kind of cell or a per-layer metric
as new files and entries and edits none:

* ``BENCHMARK.json`` (the checkout's root): the cells and the metrics;
* ``bench/workloads/<cell>.json``: the cell's configuration, traffic mix
  and the limits of its correctness comparison;
* ``bench/configs/<config>.json``: the model as it is run;
* ``bench/traffic/<traffic>.json``: the traffic's parameters, whose
  ``kind`` names the module ``bench/kinds/<kind>.py`` that runs it;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

A kind module defines ``setup(run) -> state``, ``window(run, state)``
(which calls ``run.start_window()`` and ``run.end_window()`` around the
measured work) and ``check(run, state)`` (the comparison with the plain
reference, made once the window has closed and the device's peak memory
has been read). A metric module defines ``read(run) -> float | None``;
``None`` leaves the metric out of the line.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any

BENCH = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------- files
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by its path (names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """Everything a run of one cell reads from files."""
    name: str
    root: str                 # the directory holding the cell's files
    workload: dict
    config: dict
    traffic: dict
    kind: Any                 # the kind's module
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = BENCH,
              benchmark: dict | None = None) -> Cell:
    """Load cell ``name`` from the files under ``root``."""
    if benchmark is None:
        benchmark = load_json(os.path.join(os.path.dirname(root),
                                           "BENCHMARK.json"))
    wl = load_json(os.path.join(root, "workloads", f"{name}.json"))
    cfg = load_json(os.path.join(root, "configs", f"{wl['config']}.json"))
    tr = load_json(os.path.join(root, "traffic", f"{wl['traffic']}.json"))
    kind = load_module(os.path.join(root, "kinds", f"{tr['kind']}.py"),
                       "kind_" + tr["kind"])
    return Cell(name=name, root=root, workload=wl, config=cfg, traffic=tr, kind=kind,
                end_to_end=[m for m in benchmark["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in benchmark["per_layer"]
                           if _applies(m, name)])


def metric_reader(name: str, root: str = BENCH):
    return load_module(os.path.join(root, "metrics", f"{name}.py"),
                       "metric_" + name)


# ---------------------------------------------------------------- spans
class Spans:
    """The harness's spans around its calls into each layer.

    Each span is kept as ``(name, start_s, end_s, args)`` on the host's
    ``perf_counter`` clock; with ``annotate`` it is also written into the
    profiler's trace by ``jax.profiler.TraceAnnotation``, on the device
    trace's clock.
    """

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.rows: list[tuple[str, float, float, dict]] = []

    @contextlib.contextmanager
    def span(self, name: str, **args):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench/{name}")
        t = time.perf_counter()
        with ann:
            try:
                yield args
            finally:
                self.rows.append((name, t, time.perf_counter(), args))

    def durations(self, name: str) -> list[float]:
        return [e - s for n, s, e, _ in self.rows if n == name]

    def wrap(self, fn, name: str):
        """``fn`` with every call inside a span ``name``; the span's
        ``result`` arg holds what the call returned."""
        def wrapped(*a, **kw):
            with self.span(name) as args:
                out = fn(*a, **kw)
                args["result"] = out
                return out
        return wrapped


class CompileWatch:
    """Counts the executables JAX compiles or loads from its persistent
    cache: both stall the host for as long as they take."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.seconds = 0.0

        def listen(event, duration, **_):
            if event in self.EVENTS:
                self.count += 1
                self.seconds += duration
        monitoring.register_event_duration_secs_listener(listen)


# ------------------------------------------------------------------ run
@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclass
class Run:
    """The state of one run, shared by the harness, the kind and the
    metric readers."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                      # perf_counter at process start
    devices: list = field(default_factory=list)
    spans: Spans = field(default_factory=Spans)
    compiles: Any = None
    out_dir: str = ""
    setup_s: float | None = None
    window_t0: float | None = None
    window_t1: float | None = None
    window_compiles: int = 0
    end_to_end: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)
    checks: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int | None = None
    trace_summary: dict | None = None
    info: dict = field(default_factory=dict)
    _compiles_at_start: int = 0
    _window_ann: Any = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @property
    def limits(self) -> dict:
        return self.cell.workload["limits"]

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    def log(self, msg: str) -> None:
        print(f"[{self.cell.name}] {msg}", file=sys.stderr, flush=True)

    def start_window(self) -> float:
        """Ends set-up and opens the measured window (and the profiler's
        trace, in a traced run). Returns the window's start."""
        if self.trace:
            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # device ops and the harness's annotations; the Python
            # tracer, which records every Python call, stays off
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=options)
        self._compiles_at_start = self.compiles.count if self.compiles else 0
        self.window_t0 = time.perf_counter()
        self.setup_s = self.window_t0 - self.t_start
        if self.trace:
            import jax
            self._window_ann = jax.profiler.TraceAnnotation("bench/window")
            self._window_ann.__enter__()
        return self.window_t0

    def end_window(self, t1: float | None = None) -> None:
        """Closes the window at ``t1`` (default: now)."""
        self.window_t1 = time.perf_counter() if t1 is None else t1
        self.count_compiles()
        if self._window_ann is not None:
            self._window_ann.__exit__(None, None, None)

    def count_compiles(self) -> None:
        """Executables compiled or loaded since the window opened (a kind
        whose requests finish after the window's close counts again once
        they have)."""
        if self.compiles is not None:
            self.window_compiles = self.compiles.count - \
                self._compiles_at_start

    def stop_trace(self) -> None:
        if self.trace:
            import jax
            jax.profiler.stop_trace()

    @property
    def trace_dir(self) -> str:
        return os.path.join(self.out_dir, "trace")

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append(Check(name, float(value), float(limit)))


# --------------------------------------------------------------- device
def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devs)}")
    return devs[:n]


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """JAX's persistent cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), for
    every executable however quickly it compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# --------------------------------------------------------------- result
def result_line(run: Run) -> dict:
    """The last line of standard output."""
    metrics = {}
    if run.trace:
        for m in run.cell.per_layer:
            value = metric_reader(m["name"], run.cell.root).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in run.cell.end_to_end:
            value = run.end_to_end.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    d0 = run.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": all(c.ok for c in run.checks) and bool(run.checks),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if run.trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        out["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"][:10],
            "idle_gaps": run.trace_summary["idle_gaps"][:10]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    return out


def print_checks(run: Run) -> None:
    """Each number compared, beside its limit, as the last lines of
    standard error."""
    for c in run.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, devices: list, out_dir: str) -> Run:
    """Set-up, window and check of one run (no device look, no print)."""
    from bench import trace_reduce
    run = Run(cell=cell, seed=seed, seconds=float(seconds), trace=trace,
              t_start=t_start, devices=devices, spans=Spans(trace),
              out_dir=out_dir)
    run.compiles = CompileWatch()
    state = cell.kind.setup(run)
    cell.kind.window(run, state)
    run.end_to_end["setup_s"] = run.setup_s
    run.stop_trace()
    run.memory_peak_bytes = memory_peak(devices)
    run.check("window_compiles", run.window_compiles, 0)
    cell.kind.check(run, state)
    if trace:
        run.trace_summary = trace_reduce.reduce_dir(
            run.trace_dir, devices=[d.id for d in devices])
    return run

