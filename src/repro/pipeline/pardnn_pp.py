"""ParDNN-planned pipeline parallelism.

The paper's partitioner decides *where* operator clusters live; under
XLA's single-program model the realizable form of that decision at pod
scale is the **layer → pipeline-stage map** (DESIGN.md §2). This module
provides:

  * ``plan_stages``    — ParDNN specialized to the layer chain: minimize
    the pipeline bottleneck (= makespan of the steady-state schedule)
    subject to per-stage memory capacity, via binary search over the
    bottleneck + greedy packing (optimal for contiguous chain
    partitioning), with the memory model of ParDNN Step-2 (weights +
    in-flight microbatch activations, 90% cap);
  * ``plan_stages_emulated`` — validates a plan on the stage-clustered
    cost graph with the paper's FIFO scheduler emulator;
  * ``pipeline_apply`` — the runtime: GPipe-style microbatching under
    ``shard_map`` over a ``stage`` mesh axis, activations handed to the
    next stage with ``jax.lax.ppermute`` (reverse permutation generated
    automatically for the backward pass). Unequal ParDNN boundaries are
    expressed with padded layer slots + an active mask, so stage shapes
    stay static.

Compared to the uniform L/P split every PP system defaults to, ParDNN's
cost-aware boundaries matter exactly when layer costs are heterogeneous —
Jamba's mamba/attn/MoE interleave, DeepSeek's dense prelude
(benchmarks/bench_pipeline_plan.py quantifies it).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.graph import CostGraph
from repro.core.emulator import emulate


# --------------------------------------------------------------- planning
@dataclass
class StagePlan:
    boundaries: list[tuple[int, int]]     # per stage [start, end)
    bottleneck: float                     # max stage compute
    stage_mem: list[float]
    feasible: bool

    @property
    def layers_per_stage(self) -> list[int]:
        return [e - s for s, e in self.boundaries]


def plan_stages(layer_costs, layer_mem, act_bytes: float, num_stages: int,
                mem_cap: float | None = None, inflight: int | None = None,
                mem_fraction: float = 0.9) -> StagePlan:
    """Contiguous chain partition minimizing the bottleneck stage cost
    subject to memory. ``inflight`` microbatch activations are resident
    per stage in GPipe steady state (default: num_stages)."""
    costs = np.asarray(layer_costs, dtype=np.float64)
    mems = np.asarray(layer_mem, dtype=np.float64)
    L = len(costs)
    num_stages = min(num_stages, L)
    inflight = inflight if inflight is not None else num_stages
    cap = (mem_cap * mem_fraction) if mem_cap is not None else np.inf
    act_resident = act_bytes * inflight

    def feasible(T: float) -> list[tuple[int, int]] | None:
        bounds = []
        s = 0
        for _ in range(num_stages):
            if s >= L:
                break
            c = 0.0
            m = act_resident
            e = s
            while e < L and c + costs[e] <= T and m + mems[e] <= cap:
                c += costs[e]
                m += mems[e]
                e += 1
            if e == s:
                return None  # single layer exceeds T or cap
            bounds.append((s, e))
            s = e
        return bounds if s >= L else None

    lo = float(np.max(costs))
    # epsilon headroom: the greedy packer accumulates in a different order
    # than np.sum, so exact-equality targets can spuriously fail
    hi = float(np.sum(costs)) * (1.0 + 1e-9) + 1e-12
    best = feasible(hi)
    if best is None:
        # memory-infeasible even serially: report the degenerate plan
        per = max(L // num_stages, 1)
        bounds = [(i * per, min((i + 1) * per, L))
                  for i in range(num_stages)]
        bounds[-1] = (bounds[-1][0], L)
        sm = [float(np.sum(mems[s:e]) + act_resident) for s, e in bounds]
        return StagePlan(bounds, float("inf"), sm, feasible=False)
    for _ in range(48):
        mid = 0.5 * (lo + hi)
        b = feasible(mid)
        if b is not None:
            best, hi = b, mid
        else:
            lo = mid
    sm = [float(np.sum(mems[s:e]) + act_resident) for s, e in best]
    bot = max(float(np.sum(costs[s:e])) for s, e in best)
    ok = all(m <= cap for m in sm)
    return StagePlan(best, bot, sm, feasible=ok)


def uniform_plan(L: int, num_stages: int) -> list[tuple[int, int]]:
    per = L // num_stages
    extra = L % num_stages
    bounds = []
    s = 0
    for i in range(num_stages):
        e = s + per + (1 if i < extra else 0)
        bounds.append((s, e))
        s = e
    return bounds


def plan_stages_emulated(g_layers: CostGraph, plan: StagePlan,
                         num_micro: int) -> float:
    """Validate a plan with the paper's FIFO emulator on the microbatch-
    expanded stage graph; returns the emulated pipeline makespan."""
    P_ = len(plan.boundaries)
    stage_cost = [sum(g_layers.comp[s:e]) for s, e in plan.boundaries]
    g = CostGraph()
    ids = {}
    for m in range(num_micro):
        for p in range(P_):
            ids[(m, p)] = g.add_node(comp=stage_cost[p],
                                     name=f"mb{m}_st{p}")
    for m in range(num_micro):
        for p in range(P_ - 1):
            g.add_edge(ids[(m, p)], ids[(m, p + 1)], comm=0.0)
    g.finalize()
    assign = np.array([p for m in range(num_micro) for p in range(P_)])
    sched = emulate(g, assign, P_)
    return sched.makespan


# ---------------------------------------------------------------- runtime
def stack_stage_params(layer_params, boundaries: list[tuple[int, int]]):
    """layer_params: pytree stacked on layer dim (L, ...). Returns
    (stage_params (P, Lmax, ...), mask (P, Lmax))."""
    Lmax = max(e - s for s, e in boundaries)
    P_ = len(boundaries)

    def pack(x):
        outs = []
        for s, e in boundaries:
            sl = x[s:e]
            pad = Lmax - (e - s)
            if pad:
                sl = jnp.concatenate(
                    [sl, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
            outs.append(sl)
        return jnp.stack(outs)

    mask = np.zeros((P_, Lmax), dtype=np.float32)
    for i, (s, e) in enumerate(boundaries):
        mask[i, :e - s] = 1.0
    return jax.tree_util.tree_map(pack, layer_params), jnp.asarray(mask)


def pipeline_apply(mesh: Mesh, layer_fn, stage_params, mask,
                   x_micro: jax.Array, *, stage_axis: str = "stage"):
    """GPipe forward over ``stage_axis``.

    layer_fn(layer_params, h) -> h        (single layer)
    stage_params: (P, Lmax, ...) sharded P(stage_axis) on dim 0
    mask: (P, Lmax)
    x_micro: (M, mb, ...) microbatched input (replicated)

    Returns (M, mb, ...) outputs (valid on every device — broadcast from
    the last stage). Fully differentiable: jax autodiff reverses the
    ppermute chain, yielding the GPipe backward schedule.
    """
    num_stages = mesh.shape[stage_axis]
    M = x_micro.shape[0]
    T = M + num_stages - 1

    def stage_body(sp, smask, xm):
        # inside shard_map: sp (1, Lmax, ...), xm (M, mb, ...) replicated
        sp = jax.tree_util.tree_map(lambda a: a[0], sp)
        smask = smask[0]
        Lmax = smask.shape[0]
        sid = jax.lax.axis_index(stage_axis)
        perm = [(i, i + 1) for i in range(num_stages - 1)]

        def run_stage(h):
            for j in range(Lmax):
                pj = jax.tree_util.tree_map(lambda a: a[j], sp)
                h = jnp.where(smask[j] > 0, layer_fn(pj, h), h)
            return h

        def step(carry, t):
            recv = carry
            first_in = x_micro_local(xm, t, M)
            h_in = jnp.where(sid == 0, first_in, recv)
            h_out = run_stage(h_in)
            sent = jax.lax.ppermute(h_out, stage_axis, perm) \
                if num_stages > 1 else h_out
            return sent, h_out

        _, ys = jax.lax.scan(step, jnp.zeros_like(xm[0]),
                             jnp.arange(T))
        # outputs of the last stage live at steps P-1 .. P-1+M-1
        outs = jax.lax.dynamic_slice_in_dim(ys, num_stages - 1, M, axis=0)
        # broadcast last stage's result to everyone (psum of masked)
        is_last = (sid == num_stages - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * is_last, stage_axis)
        return outs

    def x_micro_local(xm, t, M):
        idx = jnp.clip(t, 0, M - 1)
        return jax.lax.dynamic_index_in_dim(xm, idx, axis=0,
                                            keepdims=False)

    pspec = jax.tree_util.tree_map(
        lambda _: P(stage_axis), stage_params)
    out = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(pspec, P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, mask, x_micro)
    return out


# ----------------------------------------------------------- cost model
def layer_flops(cfg, kind: str, tokens: float, seq: int = 4096) -> float:
    """Per-layer forward FLOPs at ``tokens`` tokens (coarse analytic).

    The layer-chain cost model behind :func:`config_stage_plan` and the
    pipeline benchmarks — heterogeneity here (mamba vs attn vs MoE) is
    exactly what makes ParDNN boundaries beat the uniform L/P split.
    """
    D = cfg.d_model
    f = 0.0
    if kind.startswith(("attn", "swa")):
        f += 2 * tokens * D * (2 * cfg.q_dim + 2 * cfg.kv_dim)
        kv_eff = (min(cfg.sliding_window, seq) if kind.startswith("swa")
                  else seq / 2)          # causal average vs window
        f += 4 * tokens * kv_eff * cfg.head_dim * cfg.num_heads
    elif kind.startswith("mla"):
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        f += 2 * tokens * D * (cfg.num_heads * qk + cfg.kv_lora_rank * 4)
    elif kind.startswith("mamba"):
        di = D * cfg.mamba.expand
        f += 2 * tokens * D * 2 * di + 2 * tokens * di * D
        f += 6 * tokens * di * cfg.mamba.d_state
    elif kind == "rwkv":
        f += 2 * tokens * D * 4 * D
    if kind.endswith("moe"):
        m = cfg.moe
        f += 2 * tokens * m.experts_per_token * 3 * D * m.d_ff
        f += 2 * tokens * (3 if cfg.gated_mlp else 2) * D * m.d_ff \
            * m.num_shared_experts
    elif not kind.startswith("rwkv"):
        f += 2 * tokens * (3 if cfg.gated_mlp else 2) * D * cfg.d_ff
    else:
        f += 2 * tokens * 2 * D * cfg.d_ff
    return f


def config_stage_plan(cfg, num_stages: int, *, tokens: float = 1e6,
                      act_bytes: float = 1e8,
                      mem_cap: float | None = None) -> StagePlan:
    """ParDNN-PP plan for a config's full layer chain.

    Builds the per-layer cost/memory vectors from the architecture
    (prelude + repeated block pattern, embedding table riding with the
    first layer, untied LM head with the last) and runs
    :func:`plan_stages`. This is the pipeline side of
    :meth:`repro.api.PartitionPlan.to_pipeline_stages`.
    """
    kinds = list(cfg.prelude) + list(cfg.block_pattern) * cfg.num_periods
    costs = [layer_flops(cfg, k, tokens) for k in kinds]
    per_layer = cfg.param_count() / max(cfg.num_layers, 1)
    mems = [per_layer * 2.0] * len(costs)
    embed_b = cfg.vocab_size * cfg.d_model * 2.0
    if mems:
        mems[0] += embed_b
        if not cfg.tie_embeddings:
            mems[-1] += embed_b
    return plan_stages(costs, mems, act_bytes=act_bytes,
                       num_stages=num_stages, mem_cap=mem_cap)
