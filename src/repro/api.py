"""Plan-centric facade — the repo's top-level user surface.

The paper's Figure-1 contract is a *single placement artifact* produced
ahead of time and consumed by an execution engine. This module is that
contract made concrete:

    import repro

    traced = repro.trace(step_fn, params, batch, record=True)
    plan = repro.partition(traced, devices=8, memory=16e9)
    plan.save("step.plan.json")          # JSON header + npz assignment
    ...
    plan = repro.PartitionPlan.load("step.plan.json", traced=traced)
    out = plan.execute(params, batch)    # compiled segment runtime
    # fewer devices than PEs? alias explicitly:
    #   plan.execute(params, batch, device_map=[0] * plan.k)

``trace`` always returns a :class:`TracedModel` (no tuple-vs-graph
return split); ``partition`` always returns a :class:`PartitionPlan`
whose :class:`PlanReport` captures per-stage timings and counters. Plans
are versioned (``PLAN_SCHEMA_VERSION``) and carry the cost graph's
content fingerprint, so a stale plan can never be silently applied to a
model it was not computed for.

The underlying engine (``core.tracing.trace_cost_graph``,
``core.partitioner.pardnn_partition``) is unchanged and remains public —
this facade packages it, it does not fork it.
"""
from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Iterable, Mapping, Sequence

import numpy as np

from .core import errors as _E
from .core.costmodel import DeviceModel, TPU_V5E
from .core.errors import PlanValidationError
from .core.executor import TracedProgram, execute as _execute
from .core.graph import CostGraph, Placement
from .core.partitioner import PardnnOptions, pardnn_partition
from .core.tracing import trace_cost_graph
from .obs.spans import traced as _traced

PLAN_FORMAT = "repro-partition-plan"
PLAN_SCHEMA_VERSION = 1
KNOWN_SCHEMA_VERSIONS = (1,)

RUNTIMES = ("compiled", "interpret")


def _jsonable(x):
    """Recursively convert numpy scalars/arrays and tuples so the value
    round-trips through JSON *unchanged* (tuples become lists up front,
    matching what json.load hands back)."""
    if isinstance(x, Mapping):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, np.ndarray):
        return [_jsonable(v) for v in x.tolist()]
    if isinstance(x, (np.bool_, bool)):
        return bool(x)
    if isinstance(x, (np.integer, int)):
        return int(x)
    if isinstance(x, (np.floating, float)):
        return float(x)
    return x


# ---------------------------------------------------------------------------
# devices
# ---------------------------------------------------------------------------
@dataclass
class DeviceSpec:
    """Target devices for a partition.

    Attributes:
        count: Number of (homogeneous) devices K.
        memory: Per-device capacity in bytes — scalar, length-K sequence,
            or None (no Step-2 memory enforcement).
        jax_devices: Concrete jax devices for :meth:`PartitionPlan.execute`
            (defaults to ``jax.devices()`` at execution time).
    """
    count: int
    memory: float | Sequence[float] | None = None
    jax_devices: list | None = None

    @classmethod
    def resolve(cls, devices, memory=None) -> "DeviceSpec":
        if isinstance(devices, DeviceSpec):
            if memory is not None and devices.memory is None:
                return cls(devices.count, memory, devices.jax_devices)
            return devices
        if isinstance(devices, (int, np.integer)):
            return cls(int(devices), memory)
        # a concrete list of jax devices
        devs = list(devices)
        return cls(len(devs), memory, devs)

    def mem_caps(self) -> np.ndarray | float | None:
        if self.memory is None:
            return None
        if np.isscalar(self.memory):
            return float(self.memory)
        return np.asarray(self.memory, dtype=np.float64)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------
@dataclass
class TracedModel:
    """A traced computation: cost graph + optional executable program.

    Returned by :func:`trace` regardless of ``record`` — the program is
    simply None when not recorded, killing the tuple-vs-graph return
    split of ``trace_cost_graph``.
    """
    graph: CostGraph
    program: TracedProgram | None
    fingerprint: str
    # the device model the costs were derived with; the compiled runtime
    # prices its transfer ops with the same model (transfer_seconds)
    device_model: DeviceModel | None = None

    @property
    def n(self) -> int:
        return self.graph.n

    def annotate(self, profile) -> "TracedModel":
        """Re-annotate this trace's cost graph from a
        :class:`~repro.profiling.CalibrationProfile` (in place).

        Node compute costs are replaced by the profile's *measured*
        per-signature seconds where the signature was profiled, and by
        the calibrated device model's roofline otherwise; edge comm
        costs are re-priced through the fitted alpha–beta link model
        (payload bytes are recovered exactly by inverting the original
        model's ``comm_seconds``). Compute costs are then rescaled by
        the profile's measured *fusion factor* — eager per-op timing
        cannot see XLA fusion, so summed op costs overpredict compiled
        segments by a graph-wide ratio the calibration measures
        independently of any partition. The graph fingerprint changes —
        existing plans for the un-annotated costs no longer validate
        and must be re-partitioned, which is the point.
        """
        from .profiling.opbench import graph_signatures
        g = self.graph
        old = self.device_model
        if old is None:
            raise ValueError("annotate() needs the device model the "
                             "trace was priced with (TracedModel."
                             "device_model) to invert edge costs")
        if g.op_flops is None or g.op_bytes is None:
            raise ValueError("cost graph has no op_flops/op_bytes "
                             "annotations — re-trace with repro.trace")
        model = profile.device_model(base=old)
        flops = np.asarray(g.op_flops, dtype=np.float64)
        bts = np.asarray(g.op_bytes, dtype=np.float64)
        comp = np.maximum(
            flops / (model.peak_flops * model.flop_efficiency),
            bts / model.hbm_bw)
        measured = profile.op_seconds_by_signature()
        if measured:
            for i, sig in enumerate(graph_signatures(g)):
                t = measured.get(sig)
                if t is not None:
                    comp[i] = t
        # both the measured per-op seconds and the roofline fallback
        # describe eager, unfused execution — rescale to what fused
        # compiled segments actually achieve on this graph
        comp *= float(getattr(profile, "fusion_factor", 1.0))
        g.comp = comp
        for adj in (g.out_edges, g.in_edges):
            for u, edges in enumerate(adj):
                adj[u] = [
                    (v, model.comm_seconds(
                        max(c - old.link_latency, 0.0) * old.link_bw))
                    for v, c in edges]
        g._invalidate()
        self.device_model = model
        self.fingerprint = g.fingerprint()
        return self


def _resolve_calibration(calibration):
    """calibration= argument → CalibrationProfile | None. Accepts a
    profile object, a path, or (when None) the ``REPRO_CALIBRATION``
    environment variable pointing at a saved artifact. A profile whose
    device fingerprint does not match this environment is applied but
    *warned about* — measured costs do not transfer across hardware;
    pass ``CalibrationProfile.load(path, expect_device=True)`` to make
    the mismatch a hard error instead."""
    if calibration is None:
        calibration = os.environ.get("REPRO_CALIBRATION") or None
    if calibration is None:
        return None
    from .profiling.artifact import (CalibrationProfile,
                                     current_device_fingerprint)
    if isinstance(calibration, str):
        calibration = CalibrationProfile.load(calibration)
    here = current_device_fingerprint()
    if calibration.device_fingerprint != here:
        import warnings
        warnings.warn(
            f"calibration profile was measured on "
            f"{calibration.device_fingerprint!r} but this environment "
            f"is {here!r} — measured costs may not transfer; "
            f"re-run repro.calibrate on this hardware", stacklevel=3)
    return calibration


def trace(fn: Callable, *example_args, record: bool = False,
          dev: DeviceModel = TPU_V5E, max_scan_unroll: int = 64,
          params_residual: bool = True, calibration=None,
          **example_kwargs) -> TracedModel:
    """Trace ``fn(*example_args)`` into a :class:`TracedModel`.

    With ``record=True`` the node-level program is captured as well, so
    the resulting plan can :meth:`~PartitionPlan.execute` on real
    devices. The graph fingerprint is computed here once and reused for
    every plan produced from this trace.

    ``calibration`` (a :class:`~repro.profiling.CalibrationProfile`, a
    path to a saved one, or — when unset — the ``REPRO_CALIBRATION``
    env var) overlays measured device parameters on ``dev`` before
    pricing, so the graph is annotated with calibrated costs from the
    start; :meth:`TracedModel.annotate` additionally patches in the
    per-op measured seconds afterwards.
    """
    profile = _resolve_calibration(calibration)
    if profile is not None:
        dev = profile.device_model(base=dev)
    res = trace_cost_graph(fn, *example_args, dev=dev,
                           max_scan_unroll=max_scan_unroll,
                           params_residual=params_residual,
                           record=record, **example_kwargs)
    g, prog = res if record else (res, None)
    return TracedModel(graph=g, program=prog, fingerprint=g.fingerprint(),
                       device_model=dev)


def fold_device_map(k: int, devices=None) -> list[int] | None:
    """pe -> device-index aliasing for running a ``k``-PE plan on fewer
    devices (round-robin), or None when enough devices exist. The
    explicit companion of the executor's refusal to wrap PEs silently:
    ``plan.execute(..., device_map=fold_device_map(plan.k))``."""
    if devices is None:
        import jax
        devices = jax.devices()
    n = len(devices)
    return None if n >= k else [i % n for i in range(k)]


def calibrate(traced, *example_args, **kwargs):
    """Measure real op/link costs and fit the device model — the facade
    name for :func:`repro.profiling.run_calibration` (see there for the
    full signature). Returns a
    :class:`~repro.profiling.CalibrationProfile`."""
    from .profiling import run_calibration
    return run_calibration(traced, *example_args, **kwargs)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------
@dataclass
class PlanReport:
    """Structured account of how a plan was produced and what it costs.

    ``stage_seconds`` holds the per-stage wall times (slice / map /
    refine / step2 / total); ``counters`` the mapping, refinement and
    Step-2 movement counters from the partitioner. All values are plain
    JSON types so the report serializes losslessly inside the plan
    header.
    """
    makespan_s: float
    peak_mem_bytes: list
    feasible: bool
    moved_nodes: int
    stage_seconds: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)
    # segment-runtime counters from the plan's last compiled execution:
    # segments, transfers/bytes, compile/execute seconds, measured
    # per-device peak live bytes (next to the predicted peaks above)
    runtime: dict = field(default_factory=dict)
    # predicted-vs-measured scorecard from accuracy_report(): per-stage
    # (segment) MAPE, per-device MAPE, makespan error (repro.profiling)
    accuracy: dict = field(default_factory=dict)
    # static-verification summary from plan.verify() (repro.analysis):
    # severity counts, per-code counts, passes run, error/warn findings
    diagnostics: dict = field(default_factory=dict)
    # serving-engine counters from the plan's last drained serve() run
    # (repro.serving.ServingStats.to_dict): admissions, preemptions,
    # TTFT / inter-token latency percentiles, peak blocks in use
    serving: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"makespan_s": self.makespan_s,
                "peak_mem_bytes": self.peak_mem_bytes,
                "feasible": self.feasible,
                "moved_nodes": self.moved_nodes,
                "stage_seconds": self.stage_seconds,
                "counters": self.counters,
                "runtime": self.runtime,
                "accuracy": self.accuracy,
                "diagnostics": self.diagnostics,
                "serving": self.serving}

    @classmethod
    def from_dict(cls, d: dict) -> "PlanReport":
        return cls(makespan_s=float(d["makespan_s"]),
                   peak_mem_bytes=list(d["peak_mem_bytes"]),
                   feasible=bool(d["feasible"]),
                   moved_nodes=int(d["moved_nodes"]),
                   stage_seconds=dict(d.get("stage_seconds", {})),
                   counters=dict(d.get("counters", {})),
                   runtime=dict(d.get("runtime", {})),
                   accuracy=dict(d.get("accuracy", {})),
                   diagnostics=dict(d.get("diagnostics", {})),
                   serving=dict(d.get("serving", {})))

    @classmethod
    def from_placement(cls, p: Placement) -> "PlanReport":
        timing_keys = ("slice_s", "map_s", "refine_s", "step2_s", "total_s")
        stage_seconds = {k: float(p.stats[k]) for k in timing_keys
                         if k in p.stats}
        counters = _jsonable({k: v for k, v in p.stats.items()
                              if k not in timing_keys})
        peaks = [] if p.peak_mem is None else \
            [float(x) for x in np.asarray(p.peak_mem)]
        return cls(makespan_s=float(p.makespan), peak_mem_bytes=peaks,
                   feasible=bool(p.feasible), moved_nodes=int(p.moved_nodes),
                   stage_seconds=stage_seconds, counters=counters)


# ---------------------------------------------------------------------------
# the plan artifact
# ---------------------------------------------------------------------------
def _npz_path(path: str) -> str:
    stem, ext = os.path.splitext(path)
    return (stem if ext.lower() in (".json", ".plan") else path) + ".npz"


@dataclass
class PartitionPlan:
    """The durable placement artifact (the paper's "single file").

    Produced by :func:`partition`; persisted by :meth:`save` as a JSON
    header (schema version, graph fingerprint, report, metadata) plus an
    npz payload (assignment, per-device peaks, op names); reloaded by
    :meth:`load` with schema and fingerprint validation. Bind a fresh
    trace with :meth:`bind` to :meth:`execute` a loaded plan.
    """
    assignment: np.ndarray                # int64, node -> device
    k: int
    fingerprint: str
    report: PlanReport
    devices: DeviceSpec | None = None
    meta: dict = field(default_factory=dict)
    names: np.ndarray | None = None       # per-node op names (optional)
    schema_version: int = PLAN_SCHEMA_VERSION
    traced: TracedModel | None = None     # not serialized
    # per-node bytes kept beside a graph input on its device (an
    # optimizer's master copy); None: nothing beside the inputs
    resident: np.ndarray | None = None

    # -- convenience views --------------------------------------------------
    @property
    def n(self) -> int:
        return int(self.assignment.shape[0])

    @property
    def makespan(self) -> float:
        return self.report.makespan_s

    @property
    def peak_mem(self) -> np.ndarray:
        return np.asarray(self.report.peak_mem_bytes, dtype=np.float64)

    @property
    def feasible(self) -> bool:
        return self.report.feasible

    def summary(self) -> str:
        r = self.report
        peaks = ", ".join(f"{m / 1e6:.0f}MB" for m in r.peak_mem_bytes)
        return (f"PartitionPlan: {self.n} ops on {self.k} devices, "
                f"makespan {r.makespan_s * 1e3:.3f} ms, "
                f"feasible={r.feasible}, moved={r.moved_nodes}, "
                f"peaks [{peaks}]")

    # -- static verification ------------------------------------------------
    def verify(self, *, strict: bool = False):
        """Statically verify this plan (``repro.analysis``): placement
        holes, schedule liveness (use-after-free / double-free / bad
        donation), transfer completeness, deadlock/acyclicity, and —
        with a bound trace — the per-device peak-memory certificate.
        Nothing executes.

        Returns the :class:`~repro.analysis.DiagnosticReport` (cached
        until the assignment or bound trace changes) and records its
        summary in ``report.diagnostics``. With ``strict=True``,
        error-severity findings raise :class:`PlanValidationError`
        (code RP107) — the mode :meth:`save` and :meth:`execute` use.
        """
        from .analysis import analyze_plan
        key = (id(self.traced),
               None if self.traced is None else id(self.traced.program),
               hashlib.sha256(np.ascontiguousarray(
                   self.assignment, dtype=np.int64).tobytes()).hexdigest(),
               self.k)
        cached = getattr(self, "_verify_cache", None)
        if cached is not None and cached[0] == key:
            report = cached[1]
        else:
            report = analyze_plan(self)
            self._verify_cache = (key, report)
            self.report.diagnostics = report.summary_dict()
        if strict and report.has_errors():
            raise PlanValidationError(
                "static plan verification failed:\n"
                + report.render(max_findings=10),
                code=_E.RP107_VERIFICATION_FAILED)
        return report

    # -- persistence --------------------------------------------------------
    def save(self, path: str) -> str:
        """Write the plan: ``path`` (JSON header) + sibling ``.npz``.

        The header records the schema version, graph fingerprint, a
        sha256 of the assignment payload, the full report, and user
        metadata; the npz holds the arrays bit-for-bit. Returns ``path``.

        The plan is statically verified first (:meth:`verify`) — a plan
        carrying error-severity diagnostics is refused rather than
        persisted; the diagnostic summary is serialized in the header's
        report.
        """
        self.verify(strict=True)
        apath = _npz_path(path)
        assignment = np.ascontiguousarray(self.assignment, dtype=np.int64)
        arrays = {"assignment": assignment,
                  "peak_mem": np.asarray(self.report.peak_mem_bytes,
                                         dtype=np.float64)}
        if self.names is not None:
            arrays["names"] = np.asarray(self.names)
        if self.resident is not None:
            arrays["resident"] = np.asarray(self.resident, dtype=np.float64)
        with open(apath, "wb") as f:
            np.savez(f, **arrays)
        header = {
            "format": PLAN_FORMAT,
            "schema_version": self.schema_version,
            "graph_fingerprint": self.fingerprint,
            "num_nodes": self.n,
            "devices": self.k,
            "memory": _jsonable(self.devices.memory) if self.devices
                      else None,
            "assignment_file": os.path.basename(apath),
            "assignment_sha256": hashlib.sha256(
                assignment.tobytes()).hexdigest(),
            "report": self.report.to_dict(),
            "meta": _jsonable(self.meta),
        }
        with open(path, "w") as f:
            json.dump(header, f, indent=1)
        return path

    @classmethod
    def load(cls, path: str, traced: TracedModel | None = None,
             graph: CostGraph | None = None) -> "PartitionPlan":
        """Load and validate a plan artifact.

        Raises :class:`PlanValidationError` on an unknown schema version,
        a corrupted assignment payload, or — when ``traced``/``graph`` is
        supplied — a graph-fingerprint mismatch (the plan was computed
        for a different model). A plan loaded without a graph can still
        be inspected and saved, but must be :meth:`bind`-ed before
        :meth:`execute`.
        """
        with open(path) as f:
            header = json.load(f)
        if header.get("format") != PLAN_FORMAT:
            raise PlanValidationError(
                f"{path}: not a {PLAN_FORMAT} file "
                f"(format={header.get('format')!r})")
        ver = header.get("schema_version")
        if ver not in KNOWN_SCHEMA_VERSIONS:
            raise PlanValidationError(
                f"{path}: unknown plan schema version {ver!r}; this build "
                f"supports {list(KNOWN_SCHEMA_VERSIONS)} — regenerate the "
                f"plan with repro.partition or upgrade the library",
                code=_E.RP101_SCHEMA_UNKNOWN)
        apath = os.path.join(os.path.dirname(os.path.abspath(path)),
                             header["assignment_file"])
        with np.load(apath) as z:
            assignment = np.asarray(z["assignment"], dtype=np.int64)
            peak_mem = np.asarray(z["peak_mem"], dtype=np.float64)
            names = np.asarray(z["names"]) if "names" in z.files else None
            resident = (np.asarray(z["resident"], dtype=np.float64)
                        if "resident" in z.files else None)
        digest = hashlib.sha256(
            np.ascontiguousarray(assignment).tobytes()).hexdigest()
        if digest != header["assignment_sha256"]:
            raise PlanValidationError(
                f"{path}: assignment payload corrupted "
                f"(sha256 {digest[:12]}… != header "
                f"{header['assignment_sha256'][:12]}…)",
                code=_E.RP103_PAYLOAD_CORRUPT)
        if assignment.shape[0] != header["num_nodes"]:
            raise PlanValidationError(
                f"{path}: assignment has {assignment.shape[0]} nodes, "
                f"header says {header['num_nodes']}",
                code=_E.RP103_PAYLOAD_CORRUPT)
        report = PlanReport.from_dict(header["report"])
        # npz carries the peaks bit-for-bit; trust it over the JSON floats
        report.peak_mem_bytes = [float(x) for x in peak_mem]
        mem = header.get("memory")
        plan = cls(assignment=assignment, k=int(header["devices"]),
                   fingerprint=header["graph_fingerprint"], report=report,
                   devices=DeviceSpec(int(header["devices"]), mem),
                   meta=dict(header.get("meta") or {}), names=names,
                   schema_version=int(ver), resident=resident)
        if traced is not None or graph is not None:
            plan.bind(traced if traced is not None
                      else TracedModel(graph, None, graph.fingerprint()))
        return plan

    # -- binding & execution ------------------------------------------------
    def bind(self, traced: TracedModel) -> "PartitionPlan":
        """Attach a fresh trace to this plan, validating that it is the
        same computation the plan was produced for."""
        if traced.fingerprint != self.fingerprint:
            raise PlanValidationError(
                f"graph fingerprint mismatch: plan was computed for "
                f"{self.fingerprint[:16]}…, got {traced.fingerprint[:16]}… "
                f"— the model, shapes, or cost model changed; re-run "
                f"repro.partition", code=_E.RP102_FINGERPRINT_MISMATCH)
        if traced.graph.n != self.n:
            raise PlanValidationError(
                f"graph has {traced.graph.n} nodes, plan has {self.n}",
                code=_E.RP102_FINGERPRINT_MISMATCH)
        self.traced = traced
        return self

    def _jax_devices(self, devices=None, device_map=None) -> list:
        if devices is None and self.devices is not None:
            devices = self.devices.jax_devices
        if devices is None:
            import jax
            devices = jax.devices()
        devices = list(devices)
        if device_map is not None:
            device_map = [int(i) for i in device_map]
            if len(device_map) < self.k:
                raise PlanValidationError(
                    f"device_map has {len(device_map)} entries, plan "
                    f"uses {self.k} PEs", code=_E.RP104_DEVICE_MISMATCH)
            bad = [i for i in device_map
                   if i < 0 or i >= len(devices)]
            if bad:
                raise PlanValidationError(
                    f"device_map entries {bad} out of range: "
                    f"{len(devices)} jax devices available (indices "
                    f"0..{len(devices) - 1})",
                    code=_E.RP104_DEVICE_MISMATCH)
            devices = [devices[i] for i in device_map]
        if len(devices) < self.k:
            raise PlanValidationError(
                f"plan uses {self.k} PEs but only {len(devices)} jax "
                f"devices are available — pass device_map= (pe -> device "
                f"index, e.g. device_map=[0]*{self.k} to fold onto one "
                f"device) to alias PEs explicitly",
                code=_E.RP104_DEVICE_MISMATCH)
        return devices

    @_traced("plan/execute", "plan")
    def execute(self, *args, devices=None, device_map=None,
                runtime: str | None = None, donate: bool = True,
                mode: str | None = None, trace: str | None = None,
                **kwargs):
        """Run the recorded program under this placement (the paper's
        "placement file → execution engine" path).

        Args:
            devices: overrides the jax devices (defaults to
                ``jax.devices()``). A plan with more PEs than devices
                raises; alias PEs explicitly via ``device_map``.
            device_map: pe -> device-index list realizing the placement
                on fewer devices (e.g. the CPU-host test setup).
            runtime: ``"compiled"`` (default; segment runtime — per-device
                jitted subgraphs, liveness-driven buffer freeing) or
                ``"interpret"`` (op-by-op reference). Overridable via the
                ``REPRO_RUNTIME`` env var, mirroring Step-2's
                ``REPRO_STEP2_ENGINE`` switch. Both paths are pinned
                bit-equal by the test suite.
            donate: let the compiled runtime donate dead segment inputs
                to XLA.
            mode: compiled dispatch mode — ``"async"`` (overlapped:
                eager dispatch, prefetched transfers; the default) or
                ``"sync"`` (serialized: blocked per segment, lazy
                transfers). ``None`` resolves the
                ``REPRO_RUNTIME_SYNC=1`` escape hatch. Both modes run
                the same compiled segments and are bit-identical;
                ``report.runtime["mode"]`` records which one produced
                the timings.
            trace: write a Chrome trace-event / Perfetto JSON file to
                this path (open in ui.perfetto.dev). The call runs one
                async :meth:`~repro.core.runtime.CompiledRuntime.
                measure_timeline` pass and merges the **measured**
                per-device segment lanes with the overlap emulator's
                **predicted** lanes for the same segments
                (``repro.obs.trace``) — prediction error per segment is
                the offset between the two lane groups. Compiled
                runtime only.

        A compiled execution caches its jitted segments on the plan
        (recompiles only when the devices change) and records its
        :class:`~repro.core.runtime.RuntimeStats` in
        ``report.runtime``. Requires a bound trace recorded with
        ``record=True``.
        """
        if self.traced is None or self.traced.program is None:
            raise PlanValidationError(
                "plan has no executable program: trace with record=True "
                "and partition (or PartitionPlan.bind) before execute()",
                code=_E.RP106_PLAN_NOT_EXECUTABLE)
        self.verify(strict=True)
        if runtime is None:
            runtime = os.environ.get("REPRO_RUNTIME", "compiled")
        if runtime not in RUNTIMES:
            raise ValueError(f"unknown runtime {runtime!r}; "
                             f"have {list(RUNTIMES)}")
        devs = self._jax_devices(devices, device_map)
        if runtime == "interpret":
            if trace is not None:
                raise ValueError("trace= needs the compiled runtime's "
                                 "measured timeline; drop "
                                 "runtime='interpret'")
            return _execute(self.traced.program, self.assignment,
                            devs, *args, **kwargs)
        from .core.runtime import CompiledRuntime, resolve_runtime_mode
        key = (tuple(devs[:self.k]), donate)
        rt = getattr(self, "_compiled_runtime", None)
        if rt is None or rt[0] != key:
            rt = (key, CompiledRuntime(self.traced.program,
                                       self.assignment, devs[:self.k],
                                       donate=donate,
                                       device_model=self.traced
                                       .device_model))
            self._compiled_runtime = rt
        # mode is resolved per call (not cached in the key): the same
        # compiled segments serve both dispatch modes
        rt[1].mode = resolve_runtime_mode(mode)
        if trace is not None:
            from .obs.trace import build_plan_trace
            out, timeline = rt[1].measure_timeline(*args, **kwargs)
            self.report.runtime = rt[1].stats.to_dict()
            build_plan_trace(self, rt[1], timeline).save(trace)
            return out
        out = rt[1](*args, **kwargs)
        self.report.runtime = rt[1].stats.to_dict()
        return out

    def accuracy_report(self, *args, devices=None, device_map=None,
                        reps: int = 3, donate: bool = True,
                        **kwargs) -> dict:
        """Score the Step-2 emulator's predictions against the compiled
        runtime's measurements — the closed predict→execute loop.

        Runs the plan through the segment runtime in per-segment
        profiling mode (``reps`` blocked passes, medians taken), runs
        the emulator on the same placement, and compares stage by stage
        (a *stage* = one compiled segment): predicted seconds (sum of
        annotated node costs) vs measured wall seconds, as absolute
        percentage error. The scorecard lands in
        ``report.accuracy`` (serialized with the plan) and is returned.

        A huge MAPE is not a bug — it is the measurement that tells you
        the cost model is wrong for this hardware. Calibrate
        (``repro.calibrate`` → :meth:`TracedModel.annotate`),
        re-partition, and re-score to close the loop.

        Sync and async samples are never mixed: per-stage timings come
        from the serialized profiling mode (attributable, blocked),
        while the overlap scoring runs one *async* timeline pass
        (:meth:`CompiledRuntime.measure_timeline`) and compares its
        measured makespan against the overlap emulator's segment-level
        prediction. ``timing_modes`` labels which mode produced each
        number.
        """
        from .core.emulator import (emulate, emulate_overlap,
                                    segment_cost_graph,
                                    serialized_makespan)
        from .profiling.opbench import profile_segments

        if self.traced is None or self.traced.program is None:
            raise PlanValidationError(
                "accuracy_report needs a bound trace recorded with "
                "record=True (the plan must be executable)",
                code=_E.RP106_PLAN_NOT_EXECUTABLE)
        # ensure the compiled runtime exists (and reuse its cache); this
        # call already runs the program end-to-end and pays compilation,
        # so profile_segments can skip its own warmup pass
        self.execute(*args, devices=devices, device_map=device_map,
                     runtime="compiled", donate=donate, **kwargs)
        rt = self._compiled_runtime[1]
        prof = profile_segments(rt, *args, reps=reps, warmup=False,
                                **kwargs)
        g = self.traced.graph
        comp = np.asarray(g.comp, dtype=np.float64)
        segments = rt.schedule.segments
        pred = np.asarray([float(np.sum(comp[list(s.nodes)]))
                           for s in segments])
        meas = np.asarray(prof["seconds"], dtype=np.float64)
        disp = np.asarray(prof["dispersion"], dtype=np.float64)
        ape = np.abs(pred - meas) / np.maximum(meas, 1e-12)
        # score only stages/devices with measurable duration: sub-2us
        # wall times are clock noise on every platform we run on. None
        # (not NaN — the scorecard must stay valid JSON) when nothing
        # clears the floor.
        scored = meas > 2e-6
        mape = float(np.mean(ape[scored]) * 100) if scored.any() else None
        k = max(self.k, 1)
        pred_dev = np.zeros(k)
        meas_dev = np.zeros(k)
        for s, p, m in zip(segments, pred, meas):
            pred_dev[s.device] += p
            meas_dev[s.device] += m
        dev_scored = meas_dev > 2e-6
        dev_ape = np.abs(pred_dev - meas_dev) / np.maximum(meas_dev, 1e-12)
        sched = emulate(g, self.assignment, self.k)
        wall = float(np.median(prof["wall_seconds"]))
        # one async timeline pass: measured per-segment dispatch/ready/
        # done envelope + async wall — scored against the overlap
        # emulator's segment-level makespan prediction
        prev_mode = rt.mode
        try:
            rt.mode = "async"
            _, timeline = rt.measure_timeline(*args, **kwargs)
        finally:
            rt.mode = prev_mode
        dm = self.traced.device_model
        overlap_pred = serial_pred = None
        if dm is not None:
            sg, seg_assign = segment_cost_graph(
                self.traced.program, rt.schedule, g, dm)
            ov = emulate_overlap(sg, seg_assign, self.k,
                                 comm_streams=dm.comm_streams)
            overlap_pred = float(ov.makespan)
            serial_pred = float(serialized_makespan(sg, seg_assign))
        async_wall = float(timeline["makespan_s"])
        result = {
            "num_stages": len(segments),
            "stages_scored": int(np.count_nonzero(scored)),
            "reps": int(reps),
            "per_stage": [
                {"stage": int(s.sid), "device": int(s.device),
                 "nodes": len(s.nodes), "predicted_s": float(p),
                 "measured_s": float(m), "dispersion": float(d),
                 "ape_pct": float(a * 100)}
                for s, p, m, d, a in zip(segments, pred, meas, disp, ape)],
            "stage_mape_pct": mape,
            "per_device_ape_pct": [float(a * 100) if s else None
                                   for a, s in zip(dev_ape, dev_scored)],
            "devices_scored": int(np.count_nonzero(dev_scored)),
            "device_mape_pct": (float(np.mean(dev_ape[dev_scored]) * 100)
                                if dev_scored.any() else None),
            "predicted_makespan_s": float(sched.makespan),
            "measured_wall_s": wall,
            "makespan_ratio": (wall / float(sched.makespan)
                               if sched.makespan > 0 else None),
            # overlap scoring — async samples only, never mixed with
            # the sync per-stage numbers above (see timing_modes)
            "timing_modes": {"per_stage": "sync",
                             "measured_wall_s": "sync",
                             "timeline": str(timeline["mode"]),
                             "measured_async_wall_s": "async"},
            "predicted_overlap_makespan_s": overlap_pred,
            "predicted_serialized_makespan_s": serial_pred,
            "measured_async_wall_s": async_wall,
            "overlap_makespan_ratio": (
                async_wall / overlap_pred
                if overlap_pred else None),
            "serialized_makespan_ratio": (
                wall / serial_pred if serial_pred else None),
            "timeline": timeline,
            "cost_model": (self.traced.device_model.name
                           if self.traced.device_model else None),
        }
        self.report.accuracy = result
        return result

    def benchmark_runtimes(self, *args, devices=None, device_map=None,
                           reps: int = 3, **kwargs) -> dict:
        """Time both execution engines on this plan with the same inputs.

        One blocked interpreter run, one compiled run paying segment
        compilation, then the steady-state compiled path measured by
        the robust estimator (:mod:`repro.profiling.measure` —
        median-of-k with outlier rejection and noisy-window retries,
        ``reps`` samples per attempt). Returns the comparison dict used
        by ``launch/dryrun.py --pardnn-execute`` and
        ``benchmarks/bench_overhead.py --runtime``: timings (with
        sample dispersion), speedup, segment/transfer counters, output
        drift, and measured-vs-predicted per-device peak bytes.
        """
        import time

        import jax

        from .profiling.measure import MeasureSpec, measure_call

        def _timed(runtime):
            t0 = time.perf_counter()
            out = self.execute(*args, devices=devices,
                               device_map=device_map, runtime=runtime,
                               **kwargs)
            jax.block_until_ready(out)
            return out, time.perf_counter() - t0

        out_i, interp_s = _timed("interpret")
        out_c, first_s = _timed("compiled")
        m = measure_call(
            lambda: self.execute(*args, devices=devices,
                                 device_map=device_map,
                                 runtime="compiled", mode="async",
                                 **kwargs),
            spec=MeasureSpec(warmup=0, reps=max(int(reps), 2)),
            sync=jax.block_until_ready)
        out_c = m.result
        best = m.seconds
        rt = dict(self.report.runtime)
        # the serialized escape hatch, same compiled segments: the
        # async-vs-sync delta is the measured overlap speedup
        m_sync = measure_call(
            lambda: self.execute(*args, devices=devices,
                                 device_map=device_map,
                                 runtime="compiled", mode="sync",
                                 **kwargs),
            spec=MeasureSpec(warmup=0, reps=max(int(reps), 2)),
            sync=jax.block_until_ready)
        sync_s = m_sync.seconds
        sync_drift = 0.0
        for a, b in zip(jax.tree_util.tree_leaves(m_sync.result),
                        jax.tree_util.tree_leaves(out_c)):
            a = np.asarray(a, dtype=np.float64)
            b = np.asarray(b, dtype=np.float64)
            if a.size:
                sync_drift = max(sync_drift,
                                 float(np.max(np.abs(a - b))))
        drift = 0.0
        for a, b in zip(jax.tree_util.tree_leaves(out_c),
                        jax.tree_util.tree_leaves(out_i)):
            a = np.asarray(a, dtype=np.float64)
            b = np.asarray(b, dtype=np.float64)
            if a.size:
                drift = max(drift, float(np.max(np.abs(a - b))))
        predicted = [float(x) for x in self.peak_mem]
        measured = list(rt.get("peak_live_bytes", []))
        # the full estimator evidence (median/MAD/dispersion/attempts/
        # noisy per dispatch mode) rides in report.runtime so it
        # serializes with the plan — a one-number speedup without its
        # dispersion is not diagnosable from artifacts alone
        timing_modes = {"async": m.to_dict(), "sync": m_sync.to_dict()}
        self.report.runtime = {**self.report.runtime,
                               "timing_modes": timing_modes}
        return {
            "timing_modes": timing_modes,
            "interpreter_s": interp_s,
            "compiled_first_call_s": first_s,
            "compiled_s": best,
            "compiled_dispersion": m.dispersion,
            "compiled_samples": int(m.samples.size),
            "timing_attempts": int(m.attempts),
            "timing_noisy": bool(m.noisy),
            "speedup": interp_s / best if best > 0 else float("inf"),
            "compiled_mode": rt.get("mode", "async"),
            "compiled_sync_s": sync_s,
            "compiled_sync_dispersion": m_sync.dispersion,
            "overlap_speedup": sync_s / best if best > 0 else float("inf"),
            "sync_async_drift": sync_drift,
            "prefetched_transfers": rt.get("prefetched_transfers", 0),
            "deferred_transfers": rt.get("deferred_transfers", 0),
            "compile_s": rt.get("compile_seconds", 0.0),
            "num_segments": rt.get("num_segments", 0),
            "segments_per_device": rt.get("segments_per_device", []),
            "transfers": rt.get("transfers", 0),
            "transfer_bytes": rt.get("transfer_bytes", 0.0),
            "freed_buffers": rt.get("freed_buffers", 0),
            "output_drift": drift,
            "predicted_peak_bytes": predicted,
            "measured_peak_bytes": measured,
            "measured_over_predicted": [
                (m / p if p else None)
                for m, p in zip(measured, predicted)],
        }

    # -- serving ------------------------------------------------------------
    def serve(self, cfg, params, *, devices=None, device_map=None,
              runtime: str | None = None, trace: str | None = None,
              **overrides):
        """Build a :class:`~repro.serving.ServingEngine` deploying this
        plan: the paged KV pools are allocated on the devices the plan
        assigns their consuming attention ops to, and every decode step
        runs through the plan's compiled segment runtime.

        The serving geometry (block_size / num_blocks / max_batch /
        max_len) defaults to what the plan was partitioned for
        (``meta["serving"]``, recorded by
        :func:`repro.serving.partition_for_serving`); keyword
        ``overrides`` replace individual values — but changing geometry
        changes the traced decode step's shapes, so overrides that
        alter it will fail the fingerprint check at bind time, which is
        the intended guardrail.

        ``trace`` names a Chrome trace-event JSON path; the engine then
        records the request lifecycle (queued→prefill→decode→done, with
        evictions) and block-pool occupancy, written at drain time.
        """
        from .serving import ServingEngine
        geo = dict(self.meta.get("serving") or {})
        geo.update(overrides)
        if not geo:
            raise ValueError(
                "plan carries no serving geometry (meta['serving']) — "
                "build it with repro.serving.partition_for_serving, or "
                "pass block_size/num_blocks/max_batch/max_len explicitly")
        return ServingEngine(cfg, params, plan=self, devices=devices,
                             device_map=device_map, runtime=runtime,
                             trace=trace, **geo)

    # -- bridges ------------------------------------------------------------
    def to_pipeline_stages(self, layer_costs, layer_mem, act_bytes: float,
                           num_stages: int | None = None,
                           mem_cap: float | None = None, **kw):
        """Bridge to the pipeline planner: contiguous stage boundaries
        for a layer chain, defaulting the stage count to this plan's K
        and the stage memory cap to this plan's per-device capacity."""
        from .pipeline.pardnn_pp import plan_stages
        if num_stages is None:
            num_stages = self.k
        if mem_cap is None and self.devices is not None \
                and self.devices.memory is not None:
            m = self.devices.memory
            mem_cap = float(m) if np.isscalar(m) else float(np.max(m))
        return plan_stages(layer_costs, layer_mem, act_bytes=act_bytes,
                           num_stages=num_stages, mem_cap=mem_cap, **kw)

    def compare(self, baselines: Iterable[str] = ("rr", "topo"),
                graph: CostGraph | None = None) -> dict:
        """Run baseline partitioners on the same graph; returns
        ``{name: {"makespan_s": ..., "speedup": plan-vs-baseline}}``."""
        from .core.baselines import BASELINES
        g = graph if graph is not None else \
            (self.traced.graph if self.traced is not None else None)
        if g is None:
            raise ValueError("compare() needs a bound trace or graph=")
        out = {}
        for name in baselines:
            if name not in BASELINES:
                raise ValueError(f"unknown baseline {name!r}; "
                                 f"have {sorted(BASELINES)}")
            b = BASELINES[name](g, self.k)
            out[name] = {"makespan_s": float(b.makespan),
                         "speedup": float(b.makespan / self.makespan)
                         if self.makespan else float("nan")}
        return out


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------
def partition(traced_or_graph: TracedModel | CostGraph,
              devices: DeviceSpec | int | Sequence = 1,
              memory: float | Sequence[float] | None = None,
              options: PardnnOptions | None = None,
              progress: Callable[[str, dict], None] | None = None,
              meta: dict | None = None,
              resident: Sequence[float] | None = None) -> PartitionPlan:
    """Partition a traced model (or raw cost graph) into a
    :class:`PartitionPlan`.

    Args:
        traced_or_graph: A :class:`TracedModel` from :func:`trace`, or a
            bare finalized :class:`CostGraph`.
        devices: Device count, a :class:`DeviceSpec`, or a list of jax
            devices.
        memory: Per-device capacity in bytes (scalar or per-device);
            overrides nothing if the DeviceSpec already carries one.
        options: :class:`~repro.core.partitioner.PardnnOptions`.
        progress: Optional ``progress(stage, info)`` callback, threaded
            through the partitioner's stages and Step-2 rounds.
        meta: Free-form JSON-serializable metadata stored in the plan
            header (arch name, config hash, …).
        resident: Bytes kept beside each flat input leaf of the traced
            function, in its order, on whichever device holds the leaf
            (an optimizer's float32 master beside a bf16 weight is twice
            the weight's bytes). Step-2 and the verifier's certificates
            charge them; they are never shipped. Needs a recorded
            program.

    With a recorded program and a capacity, Step-2 is held to the
    verifier's memory certificate: where the peaks the runtime's segment
    schedule keeps live exceed a device's capacity, Step-2's overflow
    stage runs again below it (``pardnn_partition``'s ``certify``), so a
    plan marked feasible is one :meth:`PartitionPlan.verify` accepts.
    """
    if isinstance(traced_or_graph, TracedModel):
        traced = traced_or_graph
    elif isinstance(traced_or_graph, CostGraph):
        g = traced_or_graph
        traced = TracedModel(graph=g, program=None,
                             fingerprint=g.fingerprint())
    else:
        raise TypeError(
            f"partition() takes a TracedModel or CostGraph, got "
            f"{type(traced_or_graph).__name__}")
    spec = DeviceSpec.resolve(devices, memory)
    prog = traced.program
    per_node = None
    if resident is not None:
        if prog is None:
            raise ValueError("resident= needs a program recorded with "
                             "trace(..., record=True)")
        if len(resident) != len(prog.input_nodes):
            raise ValueError(f"resident= has {len(resident)} entries; the "
                             f"program has {len(prog.input_nodes)} input "
                             f"leaves")
        per_node = np.zeros(traced.graph.n)
        per_node[np.asarray(prog.input_nodes, dtype=np.int64)] = \
            np.asarray(resident, dtype=np.float64)
    certify = None
    if prog is not None and spec.mem_caps() is not None:
        from .analysis import certified_peaks

        def certify(assignment):
            return certified_peaks(prog, assignment, spec.count,
                                   traced.graph, per_node)
    placement = pardnn_partition(traced.graph, spec.count,
                                 mem_caps=spec.mem_caps(), options=options,
                                 progress=progress, resident=per_node,
                                 certify=certify)
    return PartitionPlan(
        assignment=np.asarray(placement.assignment, dtype=np.int64),
        k=spec.count, fingerprint=traced.fingerprint,
        report=PlanReport.from_placement(placement), devices=spec,
        meta=dict(meta or {}),
        names=np.asarray(traced.graph.names) if traced.graph.names else None,
        traced=traced, resident=per_node)


__all__ = [
    "trace", "partition", "calibrate", "fold_device_map", "TracedModel",
    "DeviceSpec", "PartitionPlan", "PlanReport", "PlanValidationError",
    "PardnnOptions", "PLAN_SCHEMA_VERSION", "RUNTIMES",
]
