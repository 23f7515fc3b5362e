"""Compiled segment runtime — executes a placed program at device speed.

Where ``core.executor.execute`` replays the traced program one primitive
at a time (the bit-exact reference), this runtime lowers the placement
into the shape related systems (Tofu, Tarnawski et al.) execute: per-
device compiled subprograms with explicit transfers.

* Each :class:`~repro.core.segments.Segment` becomes one ``jax.jit``
  callable compiled exactly once (AOT via ``lower().compile()`` on the
  first call, so compile time is accounted separately from run time);
  the first call compiles all segments at once, in a pool of threads.
* Cross-segment values live in a slot environment with **reference
  counts** derived from the trace-time liveness table: when the last
  consuming segment of a value has run, its buffer is dropped — live
  memory tracks the plan's predicted per-device profile instead of the
  whole graph (the interpreter's all-live behaviour).
* Cross-device reads become explicit ``jax.device_put`` transfer ops,
  counted (count/bytes/modelled seconds) in :class:`RuntimeStats`; with
  a telemetry sink on, each is a ``runtime/transfer`` span carrying its
  ``bytes``, ``src`` and ``dst`` pe and whether it was ``prefetched``.
* A view input (``Segment.views``: a cross-device slot read only by
  ``__scan_slice__`` nodes) is cut on the source's device by a compiled
  slicer and only the slice is shipped, lazily at its reader in both
  modes; its span carries ``index``. ``RuntimeStats.view_transfers`` /
  ``view_transfer_bytes`` count them, and ``transfers`` /
  ``transfer_bytes`` include them.
* Segment inputs that die at their segment (``Segment.dead_inputs``)
  are donated to XLA so the output can reuse the input buffer.

Dispatch modes (``mode``, default resolved from ``REPRO_RUNTIME_SYNC``):

* ``"async"`` — the overlapped path. The Python loop *dispatches*
  segments in schedule order without ever blocking; XLA's per-device
  streams execute them concurrently. Cross-device copies are
  **prefetched**: the ``device_put`` for every ``(slot, target pe)``
  a later segment will need is issued the moment the producing segment
  has been dispatched (``SegmentSchedule.prefetch``), so the transfer
  overlaps with compute instead of stalling the consumer. Live
  prefetched bytes are capped by a bounded in-flight **transfer
  window** (``transfer_window_bytes``): a prefetch that would push the
  live transferred-copy total over the window is *deferred* to lazy
  consumer-time issue — never blocked on.
* ``"sync"`` — the serialized escape hatch (``REPRO_RUNTIME_SYNC=1``):
  no prefetch, every transfer issued lazily at its consumer, and a
  ``block_until_ready`` after every segment. This is what per-segment
  profiling (``profile_segments``) needs for attributable timings, and
  the baseline the overlap speedup is measured against.

``RuntimeStats.mode`` records which mode produced each call's timings,
so accuracy reports never mix sync and async samples. The measured
per-segment timeline (dispatch/ready/done timestamps, transfer-wait
seconds) is captured by :meth:`CompiledRuntime.measure_timeline`.

Out-of-order completion never breaks liveness: dropping the Python
reference after the last *dispatched* consumer is safe because XLA
holds its own reference to every buffer a pending execution reads, and
donation order follows dispatch order on each device stream.

The runtime is pinned bit-equal to the interpreter and the
un-partitioned program by ``tests/test_runtime.py`` (both modes).
"""
from __future__ import annotations

import os
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from ..obs import spans as _obs
from .costmodel import DeviceModel
from .errors import PlanValidationError
from .executor import TracedProgram, validate_device_count
from .segments import (SCAN_SLICE, Segment, SegmentSchedule, Slot,
                       cut_segments)

#: Default cap on live prefetched-transfer bytes (the in-flight window).
#: A prefetch that would push the live transferred-copy total past this
#: is deferred to lazy consumer-time issue. Override per runtime via the
#: ``transfer_window_bytes`` argument or ``REPRO_TRANSFER_WINDOW_MB``.
DEFAULT_TRANSFER_WINDOW_BYTES: float = 64 * 1024 * 1024


def resolve_runtime_mode(mode: str | None = None) -> str:
    """Dispatch-mode resolution shared by the runtime and the facade:
    explicit argument first, then the ``REPRO_RUNTIME_SYNC=1`` escape
    hatch, else the overlapped default."""
    if mode is None:
        mode = "sync" if os.environ.get("REPRO_RUNTIME_SYNC") == "1" \
            else "async"
    if mode not in ("async", "sync"):
        raise ValueError(f"runtime mode must be 'async' or 'sync', "
                         f"got {mode!r}")
    return mode


def _resolve_window(window: float | None) -> float:
    if window is not None:
        return float(window)
    env = os.environ.get("REPRO_TRANSFER_WINDOW_MB")
    if env is not None:
        return float(env) * 1024 * 1024
    return DEFAULT_TRANSFER_WINDOW_BYTES


@dataclass
class RuntimeStats:
    """Counters from building/running a :class:`CompiledRuntime`."""
    num_segments: int = 0
    segments_per_device: list = field(default_factory=list)
    num_transfer_edges: int = 0        # static cross-device slot reads
    compile_seconds: float = 0.0       # cumulative across calls
    calls: int = 0
    # per-call counters (the last call's values):
    mode: str = ""                     # dispatch mode that produced them
    transfers: int = 0                 # executed device_put copies
    prefetched_transfers: int = 0      # issued at producer dispatch
    deferred_transfers: int = 0        # prefetches pushed past the window
    transfer_bytes: float = 0.0
    view_transfers: int = 0            # slices shipped in place of stacks
    view_transfer_bytes: float = 0.0
    transfer_seconds_modeled: float = 0.0
    transfer_window_bytes: float = 0.0
    peak_inflight_transfer_bytes: float = 0.0   # live transferred copies
    execute_seconds: float = 0.0       # compile excluded
    freed_buffers: int = 0
    peak_live_bytes: list = field(default_factory=list)   # per device
    resident_bytes: list = field(default_factory=list)    # inputs+consts
    # per-segment wall seconds of the last call — populated only when the
    # runtime's profile_segments mode is on (forces sync dispatch: blocks
    # after every segment, trading pipelining for attributable timings)
    segment_seconds: list = field(default_factory=list)
    # measured timeline of the last call, seconds from call start:
    # dispatch is recorded on every call; ready/done/transfer_wait only
    # by measure_timeline() (they require retaining segment outputs)
    dispatch_seconds: list = field(default_factory=list)
    ready_seconds: list = field(default_factory=list)
    done_seconds: list = field(default_factory=list)
    transfer_wait_seconds: list = field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "num_segments": int(self.num_segments),
            "segments_per_device": [int(x) for x in
                                    self.segments_per_device],
            "num_transfer_edges": int(self.num_transfer_edges),
            "mode": str(self.mode),
            "transfers": int(self.transfers),
            "prefetched_transfers": int(self.prefetched_transfers),
            "deferred_transfers": int(self.deferred_transfers),
            "transfer_bytes": float(self.transfer_bytes),
            "view_transfers": int(self.view_transfers),
            "view_transfer_bytes": float(self.view_transfer_bytes),
            "transfer_seconds_modeled": float(self.transfer_seconds_modeled),
            "transfer_window_bytes": float(self.transfer_window_bytes),
            "peak_inflight_transfer_bytes":
                float(self.peak_inflight_transfer_bytes),
            "compile_seconds": float(self.compile_seconds),
            "execute_seconds": float(self.execute_seconds),
            "calls": int(self.calls),
            "freed_buffers": int(self.freed_buffers),
            "peak_live_bytes": [float(x) for x in self.peak_live_bytes],
            "resident_bytes": [float(x) for x in self.resident_bytes],
            "segment_seconds": [float(x) for x in self.segment_seconds],
            "dispatch_seconds": [float(x) for x in self.dispatch_seconds],
            "ready_seconds": [float(x) for x in self.ready_seconds],
            "done_seconds": [float(x) for x in self.done_seconds],
            "transfer_wait_seconds": [float(x) for x in
                                      self.transfer_wait_seconds],
        }

    def timeline(self) -> dict:
        """The last measured per-segment timeline as one dict (empty
        lists unless the call came from ``measure_timeline``)."""
        return {
            "mode": str(self.mode),
            "dispatch_s": [float(x) for x in self.dispatch_seconds],
            "ready_s": [float(x) for x in self.ready_seconds],
            "done_s": [float(x) for x in self.done_seconds],
            "transfer_wait_s": [float(x) for x in
                                self.transfer_wait_seconds],
            "makespan_s": float(self.execute_seconds),
        }


def _nbytes(v: Any) -> int:
    nb = getattr(v, "nbytes", None)
    return int(nb) if nb is not None else 0


def _make_segment_fn(prog: TracedProgram, seg: Segment):
    """Build the python callable replaying ``seg``'s nodes; ``jax.jit``
    of this function is the segment's compiled subprogram. Its arguments
    are ``seg.inputs`` then one slice per ``seg.views`` entry, which
    stands in for the slice nodes that read it."""
    input_slots = seg.inputs
    n_in = len(input_slots)

    def fn(*invals):
        env: dict[Slot, Any] = dict(zip(input_slots, invals))
        views = {(v.slot, v.index): x
                 for v, x in zip(seg.views, invals[n_in:])}
        local: dict[int, Any] = {}

        def read(src: int, idx: int):
            if src in local:
                v = local[src]
                return v[idx] if isinstance(v, tuple) else v
            return env[(src, idx)]

        for nid in seg.nodes:
            prim, params, inputs = prog.program[nid]
            if prim == SCAN_SLICE and views:
                view = views.get(((inputs[0][1], inputs[0][2]),
                                  params["index"]))
                if view is not None:
                    local[nid] = view
                    continue
            vals = [inp[1] if inp[0] == "lit" else read(inp[1], inp[2])
                    for inp in inputs]
            if prim == SCAN_SLICE:
                out = vals[0][params["index"]]
            elif prim == "__scan_stack__":
                out = jnp.stack(vals)
            else:
                out = prim.bind(*vals, **params)
                if prim.multiple_results:
                    out = tuple(out)
            local[nid] = out
        return tuple(read(src, idx) for src, idx in seg.outputs)

    return fn


class CompiledRuntime:
    """Execute a placed :class:`TracedProgram` as jitted segments.

    Args:
        prog: recorded program (``trace(..., record=True)``).
        assignment: node -> pe (None: single-device reference mode).
        devices: concrete jax devices, one per pe — must cover every pe
            the assignment uses (no silent aliasing; expand the list
            explicitly to share devices).
        donate: donate dead segment inputs to XLA (default True).
        device_model: optional :class:`DeviceModel` used to price
            transfers (``transfer_seconds``) into the stats.
        mode: ``"async"`` (overlapped, default) or ``"sync"``
            (serialized); ``None`` resolves ``REPRO_RUNTIME_SYNC``.
            Mutable attribute — flip it between calls.
        transfer_window_bytes: cap on live prefetched-transfer bytes
            (``None``: ``REPRO_TRANSFER_WINDOW_MB`` env or the 64 MiB
            default; ``0`` disables prefetching entirely).

    The instance is reusable: segments compile on the first call and are
    cached; subsequent calls only pay execution. Both modes run the same
    compiled executables on the same values in the same order, so their
    outputs are bit-identical — only dispatch/transfer timing differs.
    """

    def __init__(self, prog: TracedProgram, assignment: np.ndarray | None,
                 devices: list | None, *, donate: bool = True,
                 device_model: DeviceModel | None = None,
                 mode: str | None = None,
                 transfer_window_bytes: float | None = None):
        if devices is None:
            devices = [jax.devices()[0]]
        devices = list(devices)
        validate_device_count(assignment, devices)
        self.prog = prog
        self.assignment = assignment
        self.devices = devices
        self.donate = donate
        self.device_model = device_model
        self.mode = resolve_runtime_mode(mode)
        self.transfer_window_bytes = _resolve_window(transfer_window_bytes)
        # per-segment profiling mode: forces sync dispatch (block after
        # every segment) and records RuntimeStats.segment_seconds
        # (repro.profiling.opbench flips this; off by default — blocking
        # defeats async dispatch)
        self.profile_segments = False
        self._timeline = False          # measure_timeline() sets this
        self.schedule: SegmentSchedule = cut_segments(
            prog, assignment, k=len(devices))
        self.stats = RuntimeStats(
            num_segments=self.schedule.num_segments,
            segments_per_device=self.schedule.segments_per_device(),
            num_transfer_edges=self.schedule.num_transfer_edges)
        self._jits: list[Any] = []
        self._donate_sets: list[frozenset[int]] = []
        _, output_nodes = prog.liveness()
        prog_nodes = set(prog.program)
        for seg in self.schedule.segments:
            fn = _make_segment_fn(prog, seg)
            dn = self._effective_donations(seg, prog_nodes,
                                           output_nodes) if donate else ()
            # a view copy is a fresh slice: its last reader on the
            # device may always donate it, aliased devices included
            vdn = tuple(len(seg.inputs) + p for p in seg.dead_views) \
                if donate else ()
            self._donate_sets.append(frozenset(dn + vdn))
            self._jits.append(jax.jit(fn, donate_argnums=dn + vdn))
        self._compiled: dict[int, Any] = {}
        # (view slot, index) -> compiled slicer on the source's device
        self._slicers: dict[tuple[Slot, int], Any] = {}
        # slots whose env value is donated by some consumer (same-device
        # or aliased reads) and transfer-copy keys donated by their last
        # reader: the timeline sweep must not retain those buffers —
        # XLA deletes them when the donating segment executes
        self._donated_env_slots: set[Slot] = set()
        self._donated_copy_keys: set[tuple] = set()
        for seg, dset in zip(self.schedule.segments, self._donate_sets):
            seg_dev = self.devices[seg.device]
            tpos = set(seg.transfer_inputs)
            for pos in dset:
                if pos >= len(seg.inputs):
                    v = seg.views[pos - len(seg.inputs)]
                    self._donated_copy_keys.add((v.slot, seg.device,
                                                 v.index))
                    continue
                slot = seg.inputs[pos]
                if pos in tpos and self._dev_of(slot[0]) is not seg_dev:
                    self._donated_copy_keys.add((slot, seg.device))
                else:
                    self._donated_env_slots.add(slot)
        # consts are placed once and pinned for the runtime's lifetime
        self._const_vals: dict[int, Any] = {}
        for nid, cval in prog.const_nodes:
            self._const_vals[nid] = jax.device_put(
                cval, self._dev_of(nid))
        # static index: exported slots per producer (for O(deg) freeing)
        # and boundary slots fed by graph inputs/consts
        self._slots_by_producer: dict[int, list[Slot]] = {}
        self._root_slots: list[Slot] = []
        roots = set(self._const_vals) | set(prog.input_nodes)
        seen_root: set[Slot] = set()
        for seg in self.schedule.segments:
            for slot in seg.outputs:
                self._slots_by_producer.setdefault(slot[0], []).append(slot)
            for slot in seg.read_slots:
                if slot[0] in roots and slot not in seen_root:
                    seen_root.add(slot)
                    self._root_slots.append(slot)
        for slot in prog.out_slots:
            if slot is not None and slot[0] in roots \
                    and slot not in seen_root:
                seen_root.add(slot)
                self._root_slots.append(slot)

    # ------------------------------------------------------------------
    def _effective_donations(self, seg: Segment, prog_nodes: set,
                             output_nodes: frozenset) -> tuple[int, ...]:
        """``Segment.dead_inputs`` assumes a cross-pe read materializes a
        fresh copy. When the concrete device list aliases pes onto the
        same physical device (``device_map=[0]*k``), ``jax.device_put``
        is a no-copy alias — donating it would delete the buffer the
        slot environment (or the pinned const cache) still references.
        Mask those positions back to the same-device intermediate rule:
        donate only values whose last reader is this segment."""
        seg_dev = self.devices[seg.device]
        transfer_pos = set(seg.transfer_inputs)
        out = []
        for pos in seg.dead_inputs:
            src = seg.inputs[pos][0]
            if pos in transfer_pos and self._dev_of(src) is seg_dev:
                if not (src in prog_nodes and src not in output_nodes
                        and self.schedule.last_consumer_seg.get(src)
                        == seg.sid):
                    continue
            out.append(pos)
        return tuple(out)

    def _dev_of(self, nid: int):
        pe = 0 if self.assignment is None else int(self.assignment[nid])
        return self.devices[pe]

    def _pe_of(self, nid: int) -> int:
        return 0 if self.assignment is None else int(self.assignment[nid])

    # ------------------------------------------------------------------
    def measure_timeline(self, *args, **kwargs):
        """One async call that captures the measured per-segment
        timeline: dispatch timestamps (exact), then — after everything
        has been dispatched — a ``block_until_ready`` sweep over each
        segment's transferred inputs and outputs in dispatch order.
        The sweep runs while execution is still in flight, so the
        recorded ready/done times are the *observed-completion
        envelope*: monotone in dispatch order, exact for segments that
        finish in order, clamped to the previous observation otherwise.
        Transfer-wait seconds is the sweep time spent blocked on a
        segment's incoming copies specifically.

        Retains every segment's outputs until the sweep, so liveness
        freeing is logical-only for this call — peak-memory stats from
        a timeline call measure retention, not the freeing schedule.

        Returns ``(result, timeline_dict)``; the timeline is also left
        in ``stats`` (``dispatch/ready/done/transfer_wait_seconds``).
        """
        self._timeline = True
        try:
            result = self(*args, **kwargs)
        finally:
            self._timeline = False
        return result, self.stats.timeline()

    # ------------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        prog = self.prog
        flat_args = jax.tree_util.tree_leaves((args, kwargs))
        if len(flat_args) != len(prog.input_nodes):
            raise ValueError(f"expected {len(prog.input_nodes)} leaves, "
                             f"got {len(flat_args)}")
        # profile_segments needs a block after every segment anyway, so
        # it forces the serialized mode for attributable timings
        sync = self.mode == "sync" or self.profile_segments
        window = 0.0 if sync else float(self.transfer_window_bytes)
        # telemetry: the sinks are read once per call, and span
        # arguments are built only when one of them is on
        traced = _obs.active()
        call_span = (_obs.span("runtime/call", "runtime",
                               mode="sync" if sync else "async",
                               segments=self.stats.num_segments)
                     if traced else _obs.span("runtime/call"))
        with call_span:
            result = self._run(flat_args, sync, window, traced)
            if traced:
                st = self.stats
                call_span.set_metadata(
                    transfers=st.transfers,
                    prefetched=st.prefetched_transfers,
                    deferred=st.deferred_transfers)
        return result

    def _precompile(self, node_vals: dict) -> float:
        """Compile every segment not compiled yet, before the first
        dispatch: each segment's input shapes follow from the call's
        placed inputs and constants (``node_vals``) through
        ``jax.eval_shape`` of the segments before it, each is lowered for
        its device, and the compiles run in a pool of threads (XLA
        compiles without holding the GIL). Each view's slicer is
        compiled beside them, once per (shape, dtype, index, source
        device). Returns the seconds."""
        t0 = time.perf_counter()
        avals: dict[Slot, Any] = {
            (nid, 0): jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           weak_type=v.aval.weak_type)
            for nid, v in node_vals.items()}
        lowered = []
        # slicer signature -> view keys it serves
        slicer_users: dict[tuple, list[tuple[Slot, int]]] = {}

        def compile_one(item):
            key, lw = item
            if isinstance(key, int):
                with _obs.span("runtime/compile", "runtime", segment=key):
                    return lw.compile()
            with _obs.span("runtime/compile", "runtime", index=key[3]):
                return lw.compile()

        with warnings.catch_warnings():
            # CPU backends may decline donation; that is a performance
            # hint, not an error
            warnings.filterwarnings("ignore", message=".*donated.*",
                                    category=UserWarning)
            for seg, jitted in zip(self.schedule.segments, self._jits):
                sharding = SingleDeviceSharding(self.devices[seg.device])
                ins = [jax.ShapeDtypeStruct(avals[s].shape, avals[s].dtype,
                                            weak_type=avals[s].weak_type,
                                            sharding=sharding)
                       for s in seg.inputs]
                for v in seg.views:
                    a = avals[v.slot]
                    ins.append(jax.ShapeDtypeStruct(
                        a.shape[1:], a.dtype, weak_type=a.weak_type,
                        sharding=sharding))
                    if (v.slot, v.index) not in self._slicers:
                        sig = (a.shape, a.dtype, a.weak_type, v.index,
                               self._pe_of(v.slot[0]))
                        slicer_users.setdefault(sig, []).append(
                            (v.slot, v.index))
                outs = jax.eval_shape(jitted, *ins)
                for slot, o in zip(seg.outputs, outs):
                    avals[slot] = o
                if seg.sid not in self._compiled:
                    lowered.append((seg.sid, jitted.lower(*ins)))
            for sig in slicer_users:
                shape, dtype, weak, index, pe = sig
                src = jax.ShapeDtypeStruct(
                    shape, dtype, weak_type=weak,
                    sharding=SingleDeviceSharding(self.devices[pe]))
                lowered.append((sig, jax.jit(
                    lambda x, i=index: x[i]).lower(src)))
            # a few threads: each compile also takes the persistent
            # cache's file lock, which more threads only queue on
            workers = max(1, min(len(lowered), os.cpu_count() or 1, 8))
            with ThreadPoolExecutor(workers) as pool:
                for (key, _), exe in zip(lowered,
                                         pool.map(compile_one, lowered)):
                    if isinstance(key, int):
                        self._compiled[key] = exe
                    else:
                        for view_key in slicer_users[key]:
                            self._slicers[view_key] = exe
        return time.perf_counter() - t0

    def _run(self, flat_args: list, sync: bool, window: float,
             traced: bool):
        """One call's dispatch loop and final wait (``__call__``)."""
        prog, sched = self.prog, self.schedule
        obs = _obs.get_tracer()
        obs_on = obs.enabled        # instants: the in-memory sink only
        t_start = time.perf_counter()
        k = len(self.devices)
        live = np.zeros(k, dtype=np.float64)
        peak = np.zeros(k, dtype=np.float64)
        freed = 0
        refcount = dict(sched.node_refcount)
        st = self.stats
        st.mode = "sync" if sync else "async"
        st.transfers = 0
        st.prefetched_transfers = 0
        st.deferred_transfers = 0
        st.transfer_bytes = 0.0
        st.view_transfers = 0
        st.view_transfer_bytes = 0.0
        st.transfer_seconds_modeled = 0.0
        st.transfer_window_bytes = window
        st.peak_inflight_transfer_bytes = 0.0
        inflight = 0.0                  # live transferred-copy bytes

        def alloc(pe: int, nb: float) -> None:
            live[pe] += nb
            if live[pe] > peak[pe]:
                peak[pe] = live[pe]

        # inputs/consts are resident for the whole call (the paper's
        # res_ns): committed copies on their assigned devices
        env: dict[Slot, Any] = {}
        node_vals: dict[int, Any] = {}
        for nid, cv in self._const_vals.items():
            node_vals[nid] = cv
            alloc(self._pe_of(nid), _nbytes(cv))
        with _obs.span("runtime/inputs", "runtime"):
            for nid, a in zip(prog.input_nodes, flat_args):
                v = jax.device_put(a, self._dev_of(nid))
                node_vals[nid] = v
                alloc(self._pe_of(nid), _nbytes(v))
        resident = live.copy()
        for slot in self._root_slots:
            env[slot] = node_vals[slot[0]]

        # transferred copies, one per (slot, target pe) and one per
        # (slot, target pe, index) for views, live until their last
        # reader on that device donates them or the source is freed
        xfer_cache: dict[tuple, Any] = {}
        cache_by_src: dict[int, list[tuple]] = {}

        def put(v: Any, nb: float, src_pe: int, dst_pe: int,
                prefetched: bool, index: int | None = None) -> Any:
            """One cross-device copy, in a ``runtime/transfer`` span
            (with the ``index`` of a view)."""
            if not traced:
                return jax.device_put(v, self.devices[dst_pe])
            args = {} if index is None else {"index": index}
            with _obs.span("runtime/transfer", "runtime", bytes=nb,
                           src=src_pe, dst=dst_pe, prefetched=prefetched,
                           **args):
                return jax.device_put(v, self.devices[dst_pe])

        def keep_copy(key: tuple, v: Any, nb: float) -> None:
            """Cache a lazily issued copy for later readers on its
            device."""
            nonlocal inflight
            inflight += nb
            if inflight > st.peak_inflight_transfer_bytes:
                st.peak_inflight_transfer_bytes = inflight
            xfer_cache[key] = v
            cache_by_src.setdefault(key[0][0], []).append(key)

        def count_transfer(nb: float) -> None:
            st.transfers += 1
            st.transfer_bytes += nb
            if self.device_model is not None:
                st.transfer_seconds_modeled += \
                    self.device_model.transfer_seconds(nb)

        def issue_prefetch(psid: int) -> None:
            """Start the cross-device copies of ``psid``'s exports the
            moment the producer is dispatched. Never blocks: a copy
            that would push live transferred bytes past the window is
            deferred to lazy issue at its consumer."""
            nonlocal inflight
            for slot, dst_pe in sched.prefetch.get(psid, ()):
                dev = self.devices[dst_pe]
                if self._dev_of(slot[0]) is dev:
                    continue            # aliased pes: no copy needed
                key = (slot, dst_pe)
                if key in xfer_cache:
                    continue
                src_v = env.get(slot)
                if src_v is None:
                    continue            # freed early — lazy path guards
                nb = float(_nbytes(src_v))
                if inflight + nb > window:
                    st.deferred_transfers += 1
                    if obs_on:
                        obs.instant("runtime/transfer_defer", "runtime",
                                    {"bytes": nb, "device": dst_pe})
                    continue
                v = put(src_v, nb, self._pe_of(slot[0]), dst_pe, True)
                if obs_on:
                    obs.instant("runtime/transfer_prefetch", "runtime",
                                {"bytes": nb, "device": dst_pe,
                                 "producer_seg": psid})
                count_transfer(nb)
                st.prefetched_transfers += 1
                alloc(dst_pe, nb)
                inflight += nb
                if inflight > st.peak_inflight_transfer_bytes:
                    st.peak_inflight_transfer_bytes = inflight
                xfer_cache[key] = v
                cache_by_src.setdefault(slot[0], []).append(key)

        if not sync:
            issue_prefetch(-1)          # graph inputs/consts

        compile_s = 0.0
        if len(self._compiled) < len(self._jits):
            compile_s = self._precompile(node_vals)
        seg_seconds: list[float] = []
        dispatch_s: list[float] = []
        retained: list[tuple[tuple, list]] = []
        for seg in sched.segments:
            dispatch_span = (_obs.span("runtime/dispatch", "runtime",
                                       segment=seg.sid,
                                       device=seg.device)
                             if traced else _obs.span("runtime/dispatch"))
            with dispatch_span:
                dev = self.devices[seg.device]
                transfer_pos = set(seg.transfer_inputs)
                donate_set = self._donate_sets[seg.sid]
                dying_copy_bytes = 0.0  # donated copies die inside exe
                invals = []
                xfer_vals: list[Any] = []   # the segment's incoming copies
                for pos, slot in enumerate(seg.inputs):
                    v = env[slot]
                    if pos in transfer_pos \
                            and self._dev_of(slot[0]) is not dev:
                        # cross-pe reads on *aliased* devices are no-copy
                        # no-ops — only real copies count as transfers
                        key = (slot, seg.device)
                        cached = xfer_cache.get(key)
                        if cached is not None:
                            v = cached
                            if pos in donate_set:      # last reader here
                                xfer_cache.pop(key)
                                nb = float(_nbytes(v))
                                dying_copy_bytes += nb
                                inflight -= nb
                        else:
                            # lazy issue: sync mode, window-deferred, or
                            # the copy was already donated by an earlier
                            # reader
                            nb = float(_nbytes(v))
                            v = put(v, nb, self._pe_of(slot[0]),
                                    seg.device, False)
                            count_transfer(nb)
                            alloc(seg.device, nb)
                            if pos in donate_set:
                                dying_copy_bytes += nb
                            else:
                                keep_copy(key, v, nb)
                        if self._timeline \
                                and key not in self._donated_copy_keys:
                            xfer_vals.append(v)
                    invals.append(v)
                for vpos, view in enumerate(seg.views):
                    # the source's device cuts the slice and ships it
                    # alone, at this reader, in both modes
                    key = (view.slot, seg.device, view.index)
                    v = xfer_cache.get(key)
                    donated = vpos + len(seg.inputs) in donate_set
                    if v is not None:
                        if donated:
                            xfer_cache.pop(key)
                            nb = float(_nbytes(v))
                            dying_copy_bytes += nb
                            inflight -= nb
                    else:
                        src_pe = self._pe_of(view.slot[0])
                        v = self._slicers[(view.slot, view.index)](
                            env[view.slot])
                        nb = float(_nbytes(v))
                        if self.devices[src_pe] is not dev:
                            alloc(src_pe, nb)   # the cut, until shipped
                            v = put(v, nb, src_pe, seg.device, False,
                                    view.index)
                            live[src_pe] -= nb
                            count_transfer(nb)
                            st.view_transfers += 1
                            st.view_transfer_bytes += nb
                        alloc(seg.device, nb)
                        if donated:
                            dying_copy_bytes += nb
                        else:
                            keep_copy(key, v, nb)
                    if self._timeline \
                            and key not in self._donated_copy_keys:
                        xfer_vals.append(v)
                    invals.append(v)
                exe = self._compiled[seg.sid]
                t_seg = (time.perf_counter() if self.profile_segments
                         else 0.0)
                with warnings.catch_warnings():
                    warnings.filterwarnings(
                        "ignore", message=".*donated.*",
                        category=UserWarning)
                    outs = exe(*invals)
                if self.profile_segments:
                    jax.block_until_ready(outs)
                    seg_seconds.append(time.perf_counter() - t_seg)
                elif sync:
                    jax.block_until_ready(outs)
                if not invals:
                    # no committed inputs to infer placement from: pin
                    # the outputs to the segment's device explicitly
                    outs = tuple(jax.device_put(o, dev) for o in outs)
                dispatch_s.append(time.perf_counter() - t_start
                                  - compile_s)
            for slot, v in zip(seg.outputs, outs):
                env[slot] = v
                alloc(seg.device, _nbytes(v))
            if not sync:
                # outputs are registered, producer is in flight: start
                # the copies its consumers on other devices will need
                issue_prefetch(seg.sid)
            if self._timeline:
                keep = tuple(v for slot, v in zip(seg.outputs, outs)
                             if slot not in self._donated_env_slots)
                retained.append((keep, xfer_vals))
            live[seg.device] -= dying_copy_bytes
            # liveness-driven freeing: drop values whose last consuming
            # segment has now run (plus their cached transfer copies)
            for src in {s[0] for s in seg.read_slots}:
                if src not in refcount:
                    continue
                refcount[src] -= 1
                if refcount[src] != 0:
                    continue
                for key in cache_by_src.pop(src, ()):
                    v = xfer_cache.pop(key, None)
                    if v is not None:
                        nb = float(_nbytes(v))
                        live[key[1]] -= nb
                        inflight -= nb
                        freed += 1
                if src not in node_vals:
                    pe = self._pe_of(src)
                    for slot in self._slots_by_producer.get(src, ()):
                        v = env.pop(slot, None)
                        if v is not None:
                            live[pe] -= _nbytes(v)
                            freed += 1

        outs = []
        for slot in prog.out_slots:
            outs.append(None if slot is None else env[slot])
        result = jax.tree_util.tree_unflatten(prog.out_tree, outs)
        ready_s: list[float] = []
        done_s: list[float] = []
        xfer_wait_s: list[float] = []
        with _obs.span("runtime/wait", "runtime"):
            if self._timeline:
                # observed-completion sweep: runs while execution is
                # still in flight (dispatch above never blocked), so each
                # block returns at ~the segment's true completion for
                # segments finishing in dispatch order
                for seg_outs, seg_xfers in retained:
                    t0 = time.perf_counter()
                    if seg_xfers:
                        jax.block_until_ready(seg_xfers)
                    t1 = time.perf_counter()
                    ready_s.append(t1 - t_start - compile_s)
                    xfer_wait_s.append(t1 - t0)
                    jax.block_until_ready(seg_outs)
                    done_s.append(time.perf_counter() - t_start
                                  - compile_s)
            # sync before reading the clock: under async dispatch the
            # wall time up to here is dispatch time, not execution time
            jax.block_until_ready([o for o in outs if o is not None])
        self.stats.compile_seconds += compile_s
        self.stats.execute_seconds = (time.perf_counter() - t_start
                                      - compile_s)
        self.stats.calls += 1
        self.stats.freed_buffers = freed
        self.stats.segment_seconds = seg_seconds
        self.stats.dispatch_seconds = dispatch_s
        self.stats.ready_seconds = ready_s
        self.stats.done_seconds = done_s
        self.stats.transfer_wait_seconds = xfer_wait_s
        self.stats.peak_live_bytes = [float(x) for x in peak]
        self.stats.resident_bytes = [float(x) for x in resident]
        return result


def execute_compiled(prog: TracedProgram, assignment: np.ndarray | None,
                     devices: list | None, *args,
                     device_model: DeviceModel | None = None,
                     mode: str | None = None, **kwargs):
    """One-shot convenience: build a :class:`CompiledRuntime` and call it.
    Returns ``(result, runtime)`` so callers can read the stats or reuse
    the compiled segments."""
    rt = CompiledRuntime(prog, assignment, devices,
                         device_model=device_model, mode=mode)
    return rt(*args, **kwargs), rt
