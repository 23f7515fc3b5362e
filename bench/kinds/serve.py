"""Serving cells: ``plan.serve()`` under open-loop traffic.

Set-up makes the weights on the chip from the seed, partitions the paged
decode step (``partition_for_serving``), builds the engine with
``plan.serve()`` and the admission limits of the configuration file, and
warms up every shape the cell's traffic can reach: each (batch, length)
prefill bucket the scheduler can form under those limits, the cache
writes of every block length, and the decode step.

The window replays the traffic's schedule: a request is submitted once
its due time has passed, and ``ServingEngine.tick`` runs whenever there
is work. Latency is timed from the due time, so a stall that delays
submissions shows in the first-token time of the requests behind it.
At the window's close submission stops and the run drains the requests
due in it, for at most ``drain_s``.

The check takes a sample of the finished requests, drawn from the seed
and holding the longest one, and runs the plain reference over each
prompt with its served tokens: a served token's logit may lie below the
reference's best by at most the cell's limit.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, reference, stats, traffic, weights
from bench.model import bytes_limit, program_config


@dataclass
class State:
    cfg: Any
    spec: Any
    plan: Any
    engine: Any
    requests: list
    engine_cfg: dict
    served: dict = field(default_factory=dict)   # rid -> ServingRequest
    submit_s: dict = field(default_factory=dict)


def ceil_pow2(n: int, floor: int = 1) -> int:
    p = max(int(floor), 1)
    while p < n:
        p *= 2
    return p


def prefill_buckets(min_len: int, max_len: int, token_budget: int,
                    max_batch: int) -> list[tuple[int, int, int]]:
    """Every padded prefill shape ``(B, S)`` the engine can form from
    prompts of ``min_len..max_len`` tokens, as ``(B, S, L)`` with ``L``
    the shortest longest-prompt that lands in length bucket ``S``.

    The engine pads a batch of ``k`` admissions to ``B = ceil_pow2(k)``
    rows and ``S = ceil_pow2(longest, 8)`` positions; the scheduler
    admits a first request whatever its length and further ones while
    the batch's prompt tokens stay within ``token_budget``.
    """
    out = []
    s = ceil_pow2(min_len, 8)
    while True:
        lo = max(min_len, s // 2 + 1) if s > ceil_pow2(min_len, 8) \
            else min_len
        if lo > max_len:
            break
        k = 1
        if lo + min_len <= token_budget:
            k = 1 + (token_budget - lo) // min_len
        k = min(k, max_batch)
        b = 1
        while b <= ceil_pow2(k):
            out.append((b, s, lo))
            b *= 2
        s *= 2
    return out


def block_lengths(s: int, min_len: int, max_len: int, block: int) -> list:
    """The lengths of a prompt's last cache block, for prompts of a
    bucket of ``s`` positions."""
    top = min(s, max_len)
    n = top - min_len + 1
    if n >= block:
        return list(range(1, block + 1))
    return sorted({(L - 1) % block + 1 for L in range(min_len, top + 1)})


def _wrap(run, engine) -> None:
    """Spans around the engine's own steps (the instance's attributes,
    so the engine's ``tick`` calls them)."""
    engine._run_prefill = run.spans.wrap(engine._run_prefill, "prefill")
    engine._run_decode = run.spans.wrap(engine._run_decode, "decode")
    engine.scheduler.schedule_admissions = run.spans.wrap(
        engine.scheduler.schedule_admissions, "admit")


def warm_up(run, st: State) -> int:
    """Compile every shape the traffic can reach; returns the number of
    warm-up requests served."""
    from repro.serving import Request, kvcache
    tr, ec, eng = run.traffic, st.engine_cfg, st.engine
    lo, hi = int(tr["prompt"]["min"]), int(tr["prompt"]["max"])
    bs = int(ec["block_size"])
    rid = -1
    rng = np.random.default_rng(0)

    def prompt(n):
        return rng.integers(0, st.cfg.vocab_size, size=n, dtype=np.int32)

    buckets = prefill_buckets(lo, hi, int(ec["token_budget"]),
                              int(ec["max_batch"]))
    served = 0
    for b, s, longest in buckets:
        count = 1 if b == 1 else b // 2 + 1
        for j in range(count):
            eng.submit(Request(rid=rid, prompt=prompt(longest if j == 0
                                                      else lo),
                               max_new_tokens=1))
            rid -= 1
        eng.tick()
        served += count
    # the cache writes of every last-block length, per length bucket
    for s in sorted({s for _, s, _ in buckets}):
        _, caches = eng._prefill_fn(1, s)(
            eng.params, jnp.zeros((1, s), jnp.int32),
            jnp.ones((1,), jnp.int32))
        blk = eng.allocator.alloc()
        for n in block_lengths(s, lo, hi, bs):
            eng.pools = kvcache.write_prompt(eng.pools, [blk], caches, 0, n,
                                             bs)
        eng.allocator.free(blk)
    # the decode step
    eng.submit(Request(rid=rid, prompt=prompt(lo), max_new_tokens=3))
    eng.run_until_drained()
    jax.block_until_ready(eng.pools)
    eng.completed.clear()
    run.log(f"warm-up: {len(buckets)} prefill buckets "
            f"{[(b, s) for b, s, _ in buckets]}, {served + 1} requests")
    return served + 1


def setup(run) -> State:
    from repro.models import params_spec
    from repro.serving import partition_for_serving
    cfg = program_config(run.config)
    ec = dict(run.config["serving"])
    dev = run.devices[0]
    spec = params_spec(cfg)
    params = weights.make_params(spec, traffic.key_for(run.seed, 0),
                                 [dev] * len(jax.tree_util.tree_leaves(spec)))
    geometry = {k: int(ec[k]) for k in
                ("block_size", "num_blocks", "max_batch", "max_len")}
    with run.spans.span("plan"):
        plan = partition_for_serving(cfg, params, devices=[dev],
                                     memory=bytes_limit(dev), **geometry)
        report = plan.verify()
    if not plan.feasible or report.has_errors():
        raise RuntimeError("the serving plan is infeasible or refused:\n"
                           + report.render(max_findings=10))
    engine = plan.serve(cfg, params, token_budget=int(ec["token_budget"]))
    _wrap(run, engine)
    st = State(cfg=cfg, spec=spec, plan=plan, engine=engine,
               requests=traffic.serve_requests(run.traffic, run.seconds,
                                               run.seed, cfg.vocab_size),
               engine_cfg=ec)
    warm_up(run, st)
    return st


def _tick(run, engine) -> None:
    with run.spans.span("tick") as a:
        admitted, steps = engine.stats.admitted, engine.stats.decode_steps
        engine.tick()
        a["admitted"] = engine.stats.admitted - admitted
        a["decoded"] = engine.stats.decode_steps - steps


def window(run, st: State) -> None:
    from repro.serving import Request
    tr = run.traffic
    eng = st.engine
    pending = list(reversed(st.requests))
    t0 = run.start_window()
    close = t0 + run.seconds
    limit = close + float(tr["drain_s"])
    open_ = True
    while True:
        now = time.perf_counter()
        if open_ and now >= close:
            run.end_window(close)
            open_ = False
        if now >= limit:
            break
        with run.spans.span("host"):
            while pending and t0 + pending[-1].due_s <= now:
                r = pending.pop()
                req = Request(rid=r.rid, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens)
                eng.submit(req)
                st.served[r.rid] = req
                st.submit_s[r.rid] = now
        if eng.scheduler.drained:
            if not pending:
                if not open_:
                    break
                wait = close - time.perf_counter()
            else:
                wait = t0 + pending[-1].due_s - time.perf_counter()
            if wait > 0:
                with run.spans.span("idle"):
                    time.sleep(min(wait, 1e-3))
            continue
        _tick(run, eng)
    if open_:
        run.end_window(close)
    run.count_compiles()
    run.info["drain_end"] = time.perf_counter()
    summarize(run, st, t0)


def summarize(run, st: State, t0: float) -> None:
    """The window's end-to-end metrics and the per-request record."""
    close = t0 + run.seconds
    ttft, gaps, lag = [], [], []
    emitted = failed = 0
    end = run.info["drain_end"]
    for r in st.requests:
        req = st.served.get(r.rid)
        due = t0 + r.due_s
        if req is None or req.first_token_s is None:
            failed += 1
            ttft.append(end - due)
            continue
        lag.append(st.submit_s[r.rid] - due)
        ttft.append(req.first_token_s - due)
        gaps.extend(np.diff(req.token_times).tolist())
        emitted += sum(1 for t in req.token_times if t <= close)
    run.attempted, run.failed = len(st.requests), failed
    run.end_to_end.update({
        "serve_tokens_per_s": emitted / run.seconds,
        "serve_itl_p95_ms": 1e3 * stats.percentile(gaps, 95)})
    run.counters["window_flops"] = window_flops(run, st)
    run.counters["serving"] = {
        "requests": len(st.requests), "failed": failed,
        "tokens_in_window": emitted, "inter_token_gaps": len(gaps),
        "submit_lag_p95_ms": 1e3 * (stats.percentile(lag, 95) or 0.0),
        "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
        "itl_p50_ms": 1e3 * (stats.percentile(gaps, 50) or 0.0),
        "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90),
        "ttft_top_ms": [round(1e3 * t, 1)
                        for t in sorted(ttft, reverse=True)[:12]]}
    run.log(f"window: {len(st.requests)} requests due, {failed} failed, "
            f"{emitted} tokens in the window; {run.counters['serving']}")


def window_flops(run, st: State) -> float:
    """Model FLOPs of the work done in the window: each prefill whose
    first token came in it, and each decoded token emitted in it."""
    t0, t1 = run.window_t0, run.window_t0 + run.seconds
    total = 0.0
    for r in st.requests:
        req = st.served.get(r.rid)
        if req is None:
            continue
        plen = len(r.prompt)
        for j, t in enumerate(req.token_times):
            if t0 <= t <= t1:
                total += (flops.prefill_flops(run.config, plen) if j == 0
                          else flops.decode_flops(run.config, plen + j - 1))
    return total


def sample(run, st: State) -> list:
    """The requests the check compares: the longest finished one, then
    others drawn from the seed until ``sample_tokens`` served tokens."""
    done = [st.served[r.rid] for r in st.requests
            if r.rid in st.served and st.served[r.rid].done]
    if not done:
        return []
    done.sort(key=lambda q: len(q.prompt) + len(q.output))
    picked = [done.pop()]
    rng = traffic.rng_for(run.seed, 1)
    order = rng.permutation(len(done))
    want = int(run.traffic["sample_tokens"])
    for i in order:
        if sum(len(q.output) for q in picked) >= want:
            break
        picked.append(done[int(i)])
    return picked


def reference_gaps(run, st: State, picked: list, prec: str = "f32",
                   pick_prec: str | None = None) -> list[np.ndarray]:
    """Per sampled request, each served token's logit gap below the
    reference's best (or, with ``pick_prec``, the gap of the token that
    ``pick_prec`` puts first)."""
    dev = run.devices[0]
    params = weights.make_params(st.spec, traffic.key_for(run.seed, 0),
                                 [dev] * len(jax.tree_util.tree_leaves(
                                     st.spec)))
    pad = int(run.traffic["reference_pad"])
    fn = jax.jit(lambda p, t, pos, ch: reference.logit_gaps(
        run.config, p, t, pos, ch, prec, pick_prec))
    out = []
    for q in picked:
        seq = np.concatenate([q.prompt, np.asarray(q.output[:-1], np.int32)])
        n = -(-len(seq) // pad) * pad
        toks = np.zeros((1, n), np.int32)
        toks[0, :len(seq)] = seq
        pos = np.arange(len(q.prompt) - 1, len(seq), dtype=np.int32)
        out.append(np.asarray(fn(params, jnp.asarray(toks),
                                 jnp.asarray(pos),
                                 jnp.asarray(q.output, jnp.int32))))
    return out


def check(run, st: State) -> None:
    picked = sample(run, st)
    st.engine.pools = None
    st.engine = st.plan = None
    if not picked:
        run.check("logit_gap", float("inf"), run.limits["logit_gap"])
        return
    gaps = reference_gaps(run, st, picked)
    worst = max(float(g.max()) for g in gaps)
    run.info["sampled_tokens"] = int(sum(g.size for g in gaps))
    run.log(f"vs reference: {len(picked)} requests, "
            f"{run.info['sampled_tokens']} served tokens, the longest "
            f"{len(picked[0].prompt)} + {len(picked[0].output)}")
    run.check("logit_gap", worst, run.limits["logit_gap"])


def control(make_run, seeds: list) -> list:
    """Readings for the limit, at the cell's own load, in one process:
    per seed a short window, then over the sampled requests the widest
    gap of a served token (the lower reading) and of the token that the
    float8 reference puts first (the control)."""
    run = make_run(seeds[0])
    st = setup(run)
    out = []
    for seed in seeds:
        run = make_run(seed)
        dev = run.devices[0]
        st.engine.params = weights.make_params(
            st.spec, traffic.key_for(seed, 0),
            [dev] * len(jax.tree_util.tree_leaves(st.spec)))
        st.requests = traffic.serve_requests(run.traffic, run.seconds, seed,
                                             st.cfg.vocab_size)
        st.served, st.submit_s = {}, {}
        window(run, st)
        st.engine.run_until_drained()
        picked = sample(run, st)
        lower = max(float(g.max()) for g in reference_gaps(run, st, picked))
        ctl = max(float(g.max()) for g in reference_gaps(
            run, st, picked, pick_prec="fp8"))
        row = {"seed": seed, "requests": len(picked),
               "tokens": int(sum(len(q.output) for q in picked)),
               "program": lower, "control_fp8": ctl,
               "failed": run.failed}
        run.log(f"readings {row}")
        out.append(row)
    return out
