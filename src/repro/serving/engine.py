"""Placement-aware continuous-batching serving engine.

The engine composes the three serving pieces:

* :mod:`repro.serving.kvcache` — paged KV storage (block pools, free
  list, block tables, placement-aware residency);
* :mod:`repro.serving.scheduler` — admission / growth / preemption over
  the request state machine;
* this module — the model loop: one *batched* prefill per tick for all
  admitted prompts (a single host sync for the batch argmax), then one
  slot-free decode step over the block tables for every DECODE request.

Two execution paths share the same pure step functions:

* **local** (``plan=None``): ``jax.jit`` on the default device;
* **plan-backed** (``plan=``): the decode step runs through
  ``PartitionPlan.execute`` — the compiled segment runtime places every
  op on its plan-assigned device — and the KV pools are *allocated* on
  the devices the plan assigns their consuming attention ops to
  (``kvcache.place_pools``), so steady-state decode moves tokens and
  block tables, never cache blocks. Build the plan with
  :func:`partition_for_serving`, or call ``plan.serve(cfg, params)``
  which reads the serving geometry back out of the plan's metadata.

Correctness anchor: plan-backed, continuously-batched, paged greedy
decode is token-for-token equal to the un-partitioned sequential
reference for every request, under any admission order and any
eviction/resume schedule (greedy decode is deterministic, and
recompute-on-resume replays it exactly).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.configs.base import ModelConfig
from repro.models import decode_step, prefill_batched
from repro.obs.spans import PH_COMPLETE, PH_COUNTER, PH_INSTANT
from repro.obs.stats import latency_summary

from . import kvcache
from .kvcache import BlockAllocator
from .scheduler import RequestState, Scheduler, ServingRequest

# public alias: the request type users construct and submit
Request = ServingRequest


def _ceil_pow2(n: int, floor: int = 1) -> int:
    p = max(int(floor), 1)
    while p < n:
        p *= 2
    return p


@dataclass
class ServingStats:
    """Engine counters + latency samples, mirrored into
    ``PlanReport.serving`` for plan-backed engines."""
    submitted: int = 0
    admitted: int = 0              # prefill admissions (incl. resumes)
    preempted: int = 0             # eviction events
    evicted_requests: int = 0      # distinct requests evicted >= once
    completed: int = 0
    rejected: int = 0              # refused at submit()
    ticks: int = 0
    prefill_calls: int = 0
    prefill_tokens: int = 0
    prompt_writes: int = 0         # write_prompt's device calls
    prompt_write_blocks: int = 0   # prompt blocks write_prompt wrote
    decode_steps: int = 0
    generated_tokens: int = 0
    peak_active: int = 0
    peak_blocks_in_use: int = 0
    leaked_blocks: int = 0
    ttft_s: list = field(default_factory=list)
    inter_token_s: list = field(default_factory=list)

    def record_request(self, req: ServingRequest) -> None:
        t = req.ttft_s()
        if t is not None:
            self.ttft_s.append(float(t))
        self.inter_token_s.extend(float(d) for d in req.inter_token_s())
        if req.evictions:
            self.evicted_requests += 1
        self.preempted += req.evictions

    def to_dict(self) -> dict:
        # the latency blocks come from the shared dispersion module
        # (repro.obs.stats): p50/p99 plus median/MAD/sample-count, so
        # PlanReport.serving carries the full estimator evidence
        return {
            "submitted": self.submitted, "admitted": self.admitted,
            "preempted": self.preempted,
            "evicted_requests": self.evicted_requests,
            "completed": self.completed, "rejected": self.rejected,
            "ticks": self.ticks, "prefill_calls": self.prefill_calls,
            "prefill_tokens": self.prefill_tokens,
            "prompt_writes": self.prompt_writes,
            "prompt_write_blocks": self.prompt_write_blocks,
            "decode_steps": self.decode_steps,
            "generated_tokens": self.generated_tokens,
            "peak_active": self.peak_active,
            "peak_blocks_in_use": self.peak_blocks_in_use,
            "leaked_blocks": self.leaked_blocks,
            **latency_summary(self.ttft_s, prefix="ttft_"),
            **latency_summary(self.inter_token_s, prefix="inter_token_"),
        }


class ServingEngine:
    """Continuous-batching engine over a paged, placement-aware KV cache.

    Args:
        cfg, params: the model (attention-family archs; recurrent/
            encoder-only configs raise ``NotImplementedError`` — see
            :func:`kvcache.supported_reason`).
        block_size: tokens per KV block.
        num_blocks: pool size in blocks (one block is reserved as the
            null block).
        max_batch: decode batch width (rows of the block-table batch).
        max_len: per-request token ceiling (prompt + generated); must be
            a multiple of ``block_size``. Fixes the gathered dense view
            at ``max_len`` so the decode step compiles once.
        token_budget: max prompt tokens admitted per tick (an admission
            batch always takes at least one request regardless).
        plan: a :class:`~repro.api.PartitionPlan` produced by
            :func:`partition_for_serving` with the same geometry; decode
            then executes through the plan's compiled segment runtime
            and pools are placed by the plan.
        devices / device_map: forwarded to ``plan.execute`` (e.g.
            ``device_map`` to fold PEs onto fewer real devices).
        jit: jit the local step functions (ignored for the plan path).
        trace: Chrome trace-event JSON path written at drain time — the
            request lifecycle (queued+prefill / decode lanes per
            request, eviction markers), admission batches, decode
            steps, and block-pool occupancy counters
            (``repro.obs.trace``; open in ui.perfetto.dev). The engine
            records them through ``repro.obs``, whose in-memory sink it
            turns on from construction to drain.
    """

    def __init__(self, cfg: ModelConfig, params, *, block_size: int = 16,
                 num_blocks: int = 64, max_batch: int = 8,
                 max_len: int = 256, token_budget: int | None = None,
                 plan=None, devices=None, device_map=None,
                 runtime: str | None = None, jit: bool = True,
                 trace: str | None = None):
        if max_len % block_size:
            raise ValueError(f"max_len {max_len} must be a multiple of "
                             f"block_size {block_size}")
        reason = kvcache.supported_reason(cfg)
        if reason is not None:
            raise NotImplementedError(
                f"{cfg.name}: paged serving unsupported — {reason}")
        self.cfg = cfg
        self.params = params
        self.block_size = int(block_size)
        self.max_len = int(max_len)
        self.max_blocks_per_req = self.max_len // self.block_size
        self.max_batch = int(max_batch)
        self.allocator = BlockAllocator(num_blocks)
        if self.max_blocks_per_req > self.allocator.capacity:
            raise ValueError(
                f"max_len {max_len} needs up to {self.max_blocks_per_req} "
                f"blocks per request but the pool only has "
                f"{self.allocator.capacity} allocatable blocks — raise "
                f"num_blocks or lower max_len")
        self.scheduler = Scheduler(
            self.allocator, block_size=self.block_size,
            max_batch=self.max_batch,
            token_budget=int(token_budget) if token_budget else
            self.max_batch * self.max_len)
        self.pools = kvcache.init_pools(cfg, num_blocks, self.block_size)
        self.stats = ServingStats()
        self.completed: dict[int, ServingRequest] = {}
        # trace=: the engine's spans, counters and evictions go to the
        # repro.obs tracer, on from here to drain; whether it was on
        # before decides what write_trace keeps of its buffer
        self._trace_path = trace
        self._trace_t0 = _obs.get_tracer().now_us()
        self._obs_was_on = _obs.enabled()
        if trace is not None:
            _obs.enable()
        self.plan = plan
        self._devices = devices
        self._device_map = device_map
        self._runtime = runtime
        self.pool_devices: list | None = None
        self._jit = bool(jit)
        self._prefill_cache: dict[tuple[int, int], object] = {}
        if plan is not None:
            self._bind_plan(plan)
        else:
            self._decode = (jax.jit(self._decode_impl, donate_argnums=(1,))
                            if self._jit else self._decode_impl)
        # write_prompt makes one device call per device holding pools
        self._write_calls = len(kvcache.device_groups(
            jax.tree_util.tree_leaves(self.pools)))

    # ------------------------------------------------------------- model
    def _decode_impl(self, params, pools, block_tables, tokens, lengths):
        """The pure paged decode step (also the traced/partitioned fn):
        gather pages → dense decode at per-row positions → scatter the
        one new token per row back into its block."""
        dense = kvcache.gather_pages(pools, block_tables)
        logits, new_dense = decode_step(self.cfg, params, dense, tokens,
                                        lengths)
        new_pools = kvcache.scatter_token(pools, new_dense, block_tables,
                                          lengths)
        return logits, new_pools

    def _decode_example_args(self):
        """Example inputs fixing the decode step's (static) shapes."""
        bt = jnp.zeros((self.max_batch, self.max_blocks_per_req), jnp.int32)
        toks = jnp.zeros((self.max_batch, 1), jnp.int32)
        lens = jnp.zeros((self.max_batch,), jnp.int32)
        return (self.params, self.pools, bt, toks, lens)

    def _bind_plan(self, plan) -> None:
        import repro
        traced = plan.traced
        if traced is None or traced.program is None:
            # the pools by shape only: the trace keeps its example
            # arguments, and write_prompt donates these buffers
            params, pools, *rest = self._decode_example_args()
            traced = repro.trace(self._decode_impl, params,
                                 jax.eval_shape(lambda: pools), *rest,
                                 record=True)
            plan.bind(traced)
        devs = plan._jax_devices(self._devices, self._device_map)
        n_params = len(jax.tree_util.tree_leaves(self.params))
        self.pools, self.pool_devices = kvcache.place_pools(
            plan, n_params, self.pools, devs)

        def _plan_decode(params, pools, bt, toks, lens):
            return plan.execute(params, pools, bt, toks, lens,
                                devices=self._devices,
                                device_map=self._device_map,
                                runtime=self._runtime)
        self._decode = _plan_decode

    def _prefill_fn(self, B: int, S: int):
        key = (B, S)
        fn = self._prefill_cache.get(key)
        if fn is None:
            def impl(params, tokens, plens):
                return prefill_batched(self.cfg, params, tokens, plens)
            fn = jax.jit(impl) if self._jit else impl
            self._prefill_cache[key] = fn
        return fn

    # ------------------------------------------------------------ intake
    def submit(self, req: ServingRequest) -> None:
        """Queue a request, refusing inputs that could never complete:
        the silent-KV-overflow class of bugs is rejected here with a
        clear error instead of corrupting a live cache later."""
        plen = len(req.prompt)
        if plen < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens "
                             f"{req.max_new_tokens} < 1")
        if plen + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({plen} tokens) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds the "
                f"engine's max_len ({self.max_len}) — the KV cache would "
                f"overflow; shorten the prompt, lower max_new_tokens, or "
                f"raise max_len")
        if req.arrival_s is None:       # else the caller's arrival time
            req.arrival_s = time.perf_counter()
        self.stats.submitted += 1
        _obs.instant("serving/submit", "serving", rid=req.rid,
                     prompt_tokens=plen)
        self.scheduler.submit(req)

    # ------------------------------------------------------------- steps
    def _run_prefill(self, admits) -> None:
        """One padded prefill for every admission: a single device call
        and a single host sync for the whole batch (no per-admit
        ``int(argmax)`` round-trips)."""
        with _obs.span("serving/prefill_forward", "serving"):
            B = _ceil_pow2(len(admits))
            S = _ceil_pow2(max(len(a.prompt) for a in admits), floor=8)
            tokens = np.zeros((B, S), dtype=np.int32)
            plens = np.ones((B,), dtype=np.int32)
            for j, a in enumerate(admits):
                tokens[j, :len(a.prompt)] = a.prompt
                plens[j] = len(a.prompt)
            logits, caches = self._prefill_fn(B, S)(
                self.params, jnp.asarray(tokens), jnp.asarray(plens))
        with _obs.span("serving/sample", "serving"):
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                             dtype=np.int32)              # one host sync
        now = time.perf_counter()
        self.stats.prefill_calls += 1
        self.stats.prefill_tokens += int(sum(len(a.prompt) for a in admits))
        self.stats.admitted += len(admits)
        traced = _obs.active()
        for j, a in enumerate(admits):
            req = a.req
            with (_obs.span("serving/write_prompt", "serving",
                            blocks=len(req.blocks), tokens=len(a.prompt))
                  if traced else _obs.span("serving/write_prompt")):
                self.pools = kvcache.write_prompt(
                    self.pools, req.blocks, caches, j, len(a.prompt),
                    self.block_size)
            self.stats.prompt_writes += self._write_calls
            self.stats.prompt_write_blocks += len(req.blocks)
            req.emit(int(nxt[j]), now)
            self.stats.generated_tokens += 1
            req.state = RequestState.DECODE
            if req.hit_stop():
                self._finish(req)

    def _finish(self, req: ServingRequest) -> None:
        self.scheduler.finish(req)
        self.completed[req.rid] = req
        self.stats.completed += 1
        self.stats.record_request(req)

    def _run_decode(self) -> int:
        """One slot-free decode step over the block tables for every
        DECODE-state request (rows beyond the active set are padding
        aimed at the null block)."""
        sched = self.scheduler
        with _obs.span("serving/decode_inputs", "serving"):
            batch = []
            for req in sorted(sched.decoding(), key=lambda r: r.admit_seq):
                if req.state != RequestState.DECODE:
                    continue        # evicted by an earlier ensure_block
                if sched.ensure_block(req):
                    batch.append(req)
            # ensure_block may have evicted members picked earlier
            batch = [r for r in batch if r.state == RequestState.DECODE]
            if not batch:
                return 0
            B, W = self.max_batch, self.max_blocks_per_req
            bt = np.zeros((B, W), dtype=np.int32)
            toks = np.zeros((B, 1), dtype=np.int32)
            lens = np.zeros((B,), dtype=np.int32)
            for i, req in enumerate(batch):
                bt[i, :len(req.blocks)] = req.blocks
                toks[i, 0] = req.output[-1]
                lens[i] = req.length
            inputs = (jnp.asarray(bt), jnp.asarray(toks), jnp.asarray(lens))
        logits, self.pools = self._decode(self.params, self.pools, *inputs)
        with _obs.span("serving/sample", "serving"):
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                             dtype=np.int32)              # one host sync
        now = time.perf_counter()
        self.stats.decode_steps += 1
        for i, req in enumerate(batch):
            req.length += 1
            req.emit(int(nxt[i]), now)
            self.stats.generated_tokens += 1
            if req.hit_stop():
                self._finish(req)
        return len(batch)

    def tick(self) -> int:
        """One engine step: admit+prefill, then decode every active
        request by one token. Returns the number of requests advanced."""
        self.stats.ticks += 1
        traced = _obs.active()
        with _obs.span("serving/tick", "serving") as tick_span:
            with _obs.span("serving/schedule", "serving"):
                admits = self.scheduler.schedule_admissions()
            if admits:
                with (_obs.span(
                        "serving/prefill_batch", "serving",
                        admitted=len(admits),
                        tokens=int(sum(len(a.prompt) for a in admits)),
                        rids=[a.req.rid for a in admits])
                      if traced else _obs.span("serving/prefill_batch")):
                    self._run_prefill(admits)
            with _obs.span("serving/decode_step", "serving") as dec_span:
                decoded = self._run_decode()
                if traced:
                    dec_span.set_metadata(batch=decoded)
            if traced:
                tick_span.set_metadata(admitted=len(admits),
                                       decoded=decoded)
        advanced = decoded + len(admits)
        self.stats.peak_active = max(self.stats.peak_active,
                                     len(self.scheduler.active))
        self.stats.peak_blocks_in_use = self.allocator.peak_in_use
        if _obs.enabled():
            _obs.counter("serving/pool", "serving",
                         blocks_in_use=self.allocator.num_in_use,
                         active=len(self.scheduler.active),
                         waiting=len(self.scheduler.waiting))
        return advanced

    def run_until_drained(self, max_ticks: int = 100000
                          ) -> dict[int, ServingRequest]:
        while not self.scheduler.drained:
            if self.tick() == 0:
                raise RuntimeError(
                    "serving engine stalled: queued requests cannot be "
                    "admitted (prompt larger than the pool?)")
            if self.stats.ticks >= max_ticks:
                raise RuntimeError(f"exceeded max_ticks={max_ticks}")
        self.scheduler.check_invariants()
        self.stats.leaked_blocks = self.allocator.num_in_use
        if self.plan is not None:
            self.plan.report.serving = self.stats.to_dict()
        if self._trace_path is not None:
            self.write_trace(self._trace_path)
        return self.completed

    def write_trace(self, path: str) -> str:
        """Export the serving trace from the ``repro.obs`` buffer: one
        engine lane (admission batches, decode steps, pool-occupancy
        counters) plus one lane per completed request (queued+prefill
        span from arrival to first token, decode span to the last token,
        eviction markers). When the tracer was on before the engine, its
        whole buffer also goes to the host lanes (pid 0), as
        :func:`repro.obs.export_spans` would write it; else the engine
        turns it off again and drops what it recorded."""
        from repro.obs.trace import SERVING_PID, TraceBuilder
        tracer = _obs.get_tracer()
        b = TraceBuilder()
        b.process(SERVING_PID, "serving")
        b.thread(SERVING_PID, 0, "engine")
        epoch = tracer.epoch()

        def us(t: float) -> float:
            return (t - epoch) * 1e6

        for ph, name, _cat, _pid, _tid, ts, dur, args in tracer.events:
            if ts < self._trace_t0:
                continue
            if ph == PH_COMPLETE and name in ("serving/prefill_batch",
                                              "serving/decode_step"):
                if name == "serving/decode_step" \
                        and not (args or {}).get("batch"):
                    continue        # a tick with nothing to decode
                b.complete(SERVING_PID, 0, name.removeprefix("serving/"),
                           ts, dur, cat="serving", args=args)
            elif ph == PH_COUNTER and name == "serving/pool":
                b.counter(SERVING_PID, 0, "pool", ts, args, cat="serving")
            elif ph == PH_INSTANT and name == "serving/evict":
                b.instant(SERVING_PID, 1 + int(args["rid"]), "evicted",
                          ts, cat="serving", args=args)
        for rid, req in sorted(self.completed.items()):
            tid = 1 + rid
            b.thread(SERVING_PID, tid, f"request {rid}")
            if req.first_token_s is None:
                continue
            b.complete(SERVING_PID, tid, "queued+prefill",
                       us(req.arrival_s),
                       (req.first_token_s - req.arrival_s) * 1e6,
                       cat="serving",
                       args={"rid": rid, "prompt_tokens": len(req.prompt),
                             "admissions": req.admissions})
            if len(req.token_times) > 1:
                b.complete(SERVING_PID, tid, "decode",
                           us(req.first_token_s),
                           (req.token_times[-1] - req.first_token_s) * 1e6,
                           cat="serving",
                           args={"rid": rid, "tokens": len(req.output),
                                 "evictions": req.evictions})
        if self._obs_was_on:
            b.add_spans(tracer)
        else:
            _obs.enable(False)
            tracer.clear()
        return b.save(path)


# ---------------------------------------------------------------------------
# plan-backed construction
# ---------------------------------------------------------------------------
def serving_geometry(block_size: int = 16, num_blocks: int = 64,
                     max_batch: int = 8, max_len: int = 256) -> dict:
    return {"block_size": int(block_size), "num_blocks": int(num_blocks),
            "max_batch": int(max_batch), "max_len": int(max_len)}


def partition_for_serving(cfg: ModelConfig, params, *, devices,
                          memory=None, options=None, meta=None,
                          **geometry):
    """Trace the paged decode step for ``(cfg, params)`` at the given
    serving geometry and partition it into a deployable
    :class:`~repro.api.PartitionPlan`.

    The geometry is recorded in ``plan.meta["serving"]`` so
    ``plan.serve(cfg, params)`` can rebuild the exact engine the plan
    was computed for (the graph fingerprint enforces the match).
    """
    import repro
    geo = serving_geometry(**geometry)
    eng = ServingEngine(cfg, params, jit=False, **geo)
    traced = repro.trace(eng._decode_impl, *eng._decode_example_args(),
                         record=True)
    meta = dict(meta or {})
    meta["serving"] = dict(geo)
    meta.setdefault("arch", cfg.name)
    return repro.partition(traced, devices=devices, memory=memory,
                           options=options, meta=meta)


__all__ = ["Request", "ServingRequest", "ServingEngine", "ServingStats",
           "partition_for_serving", "serving_geometry"]
