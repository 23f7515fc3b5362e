"""Training cells: the ParDNN-partitioned training step, driven for the
window.

Set-up builds one object, the step: ``repro.trace`` of the loss's value
and gradient, ``repro.partition`` over the cell's chips under each
chip's own ``bytes_limit``, ``plan.verify()``, weights made on the chips
the plan puts them on, and SGD with float32 master weights. It drives
that step from the seed through its first ``check_steps`` steps, on
batches that all differ, and hands the same step to the window.

A step is ``plan.execute`` on the compiled runtime (async dispatch),
which returns once the step's outputs are ready, and the SGD update,
dispatched without a wait: the next step's segments queue behind it, as
in a training loop that reads its loss later. The window runs whole
steps until ``--seconds`` have passed and ends with
``block_until_ready`` on the last step's loss and weights;
``train_tokens_per_s`` is every token of every step over the time those
steps took.

The check follows the first steps with the plain reference
(``bench/reference.py``), from the same weights and batches: each step's
loss, the first gradient as the update applied it, and the master
weights' change after the last of those steps.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, reference, traffic, weights
from bench.model import bytes_limit, program_config

F32 = jnp.float32

_to_f32 = jax.jit(lambda xs, _: [jnp.copy(x).astype(F32) for x in xs])
_copy = jax.jit(lambda xs, _: [jnp.copy(x) for x in xs])
_change = jax.jit(lambda ms, ps: reference.leaf_norms(
    [m - p.astype(F32) for m, p in zip(ms, ps)]))
_train_batch = jax.jit(traffic.train_batch, static_argnums=(1, 2, 3))


@dataclass
class State:
    cfg: Any
    plan: Any
    spec: Any
    leaf_devices: list
    n_params: int
    params: Any
    master: Any
    batches: list
    sgd: Any
    losses: list = field(default_factory=list)
    grad_norms: list = field(default_factory=list)
    change_norms: list = field(default_factory=list)
    window_losses: list = field(default_factory=list)


def loss_and_grad(cfg):
    """The traced function: ``((loss, parts), grads)`` of the LM loss."""
    from repro.models import loss_fn
    return jax.value_and_grad(lambda p, b: loss_fn(cfg, p, b), has_aux=True)


def batch_spec(batch: int, seq: int) -> dict:
    s = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return {"tokens": s, "targets": s}


def make_sgd(lr: float, leaf_devices: list):
    """The update ``(master, params, grads) -> (master, params)``: float32
    master weights step against the gradient, the stored weights are
    the master rounded to their dtype. One jitted call per chip, over
    the leaves that chip holds; the old master and weights are donated."""
    by_device: dict = {}
    for i, dev in enumerate(leaf_devices):
        by_device.setdefault(dev, []).append(i)

    def update(ms, ps, gs):
        new_m = [m - lr * g.astype(F32) for m, g in zip(ms, gs)]
        return new_m, [m.astype(p.dtype) for m, p in zip(new_m, ps)]

    jitted = jax.jit(update, donate_argnums=(0, 1))

    def sgd(master, params, grads):
        ms, treedef = jax.tree_util.tree_flatten(master)
        ps = jax.tree_util.tree_leaves(params)
        gs = jax.tree_util.tree_leaves(grads)
        new_m, new_p = list(ms), list(ps)
        for idx in by_device.values():
            m2, p2 = jitted([ms[i] for i in idx], [ps[i] for i in idx],
                            [gs[i] for i in idx])
            for j, i in enumerate(idx):
                new_m[i], new_p[i] = m2[j], p2[j]
        return (jax.tree_util.tree_unflatten(treedef, new_m),
                jax.tree_util.tree_unflatten(treedef, new_p))
    return sgd


def execute(plan, params, batch):
    """The partitioned step: ``((loss, parts), grads)``."""
    return plan.execute(params, batch, runtime="compiled", mode="async")


def step(run, st: State, batch):
    """One training step through the window's own call; returns the
    loss (on the device) and the runtime's own seconds of the call:
    its dispatch of the segments and the whole call, wait included."""
    with run.spans.span("step"):
        out = execute(st.plan, st.params, batch)
    with run.spans.span("optimizer"):
        st.master, st.params = st.sgd(st.master, st.params, out[1])
    rt = st.plan.report.runtime
    return out[0][0], (rt["dispatch_seconds"][-1], rt["execute_seconds"])


def make_batches(seed: int, cfg, tr: dict, devices: list, count: int):
    """``count`` batches drawn from the seed, each leaf made on its
    device."""
    out = []
    for i in range(count):
        b = _train_batch(traffic.key_for(seed, 1 + i), cfg.vocab_size,
                         int(tr["batch"]), int(tr["seq"]))
        leaves, treedef = jax.tree_util.tree_flatten(b)
        out.append(jax.tree_util.tree_unflatten(
            treedef, [jax.device_put(x, d) for x, d in zip(leaves, devices)]))
    return out


def build(run) -> State:
    """The plan and the update, with no weights yet."""
    import repro
    from repro.models import params_spec
    cfg = program_config(run.config)
    tr = run.traffic
    devices = list(run.devices)
    limits = [bytes_limit(d) for d in devices]
    with run.spans.span("plan"):
        traced = repro.trace(loss_and_grad(cfg), params_spec(cfg),
                             batch_spec(tr["batch"], tr["seq"]), record=True)
        plan = repro.partition(
            traced, devices=devices,
            memory=None if None in limits else limits)
        report = plan.verify()
    if not plan.feasible or report.has_errors():
        raise RuntimeError("the plan is infeasible or refused:\n"
                           + report.render(max_findings=10))
    leaf_devices = [devices[int(plan.assignment[n])]
                    for n in plan.traced.program.input_nodes]
    spec = params_spec(cfg)
    n_params = len(jax.tree_util.tree_leaves(spec))
    run.log(f"{cfg.name}: {cfg.param_count() / 1e9:.4f} B parameters, "
            f"batch {tr['batch']} x {tr['seq']}; {plan.n} nodes over "
            f"{plan.k} chip(s), Step-2 peaks "
            f"{[round(float(x) / 1e9, 4) for x in plan.peak_mem]} GB")
    return State(cfg=cfg, plan=plan, spec=spec, leaf_devices=leaf_devices,
                 n_params=n_params, params=None, master=None, batches=[],
                 sgd=make_sgd(float(tr["lr"]), leaf_devices[:n_params]))


def first_steps(run, st: State) -> None:
    """Weights and batches from ``run.seed``, then the first steps
    through the window's own call, keeping what the check compares:
    each loss, the first gradient as the update applied it and the
    master's change after the last of these steps."""
    tr = run.traffic
    pdevs = st.leaf_devices[:st.n_params]
    params = weights.make_params(st.spec, traffic.key_for(run.seed, 0),
                                 pdevs)
    treedef = jax.tree_util.tree_structure(params)
    st.params = params
    st.master = jax.tree_util.tree_unflatten(
        treedef, _by_device(_to_f32, params, params, pdevs))
    st.batches = make_batches(run.seed, st.cfg, tr,
                              st.leaf_devices[st.n_params:],
                              int(tr["batches"]))
    p0 = jax.tree_util.tree_unflatten(
        treedef, _by_device(_copy, params, params, pdevs))
    lr = float(tr["lr"])
    st.losses = []
    for i in range(int(tr["check_steps"])):
        st.losses.append(float(step(run, st, st.batches[i])[0]))
        if i == 0:
            st.grad_norms = [float(x) / lr for x in _by_device(
                _change, st.master, p0, pdevs)]
    st.change_norms = [float(x) for x in _by_device(
        _change, st.master, p0, pdevs)]
    rt = st.plan.report.runtime
    run.counters["runtime"] = rt
    run.log(f"{rt['num_segments']} segment(s), compiled in "
            f"{rt['compile_seconds']:.4f} s; first losses {st.losses}")


def setup(run) -> State:
    st = build(run)
    first_steps(run, st)
    return st


def _by_device(fn, a, b, leaf_devices):
    """``fn(a_leaves, b_leaves)`` applied per chip to the leaves it holds;
    the results in leaf order."""
    ms = jax.tree_util.tree_leaves(a)
    ps = jax.tree_util.tree_leaves(b)
    out: list = [None] * len(ms)
    groups: dict = {}
    for i, d in enumerate(leaf_devices):
        groups.setdefault(d, []).append(i)
    for idx in groups.values():
        for i, v in zip(idx, fn([ms[i] for i in idx], [ps[i] for i in idx])):
            out[i] = v
    return out


def window(run, st: State) -> None:
    tr = run.traffic
    n, i, steps = len(st.batches), int(tr["check_steps"]), 0
    runtime_s = np.zeros(2)
    t0 = run.start_window()
    while True:
        loss, rt_s = step(run, st, st.batches[i % n])
        st.window_losses.append(loss)
        runtime_s += rt_s
        i += 1
        steps += 1
        if time.perf_counter() - t0 >= run.seconds:
            break
    with run.spans.span("wait"):
        jax.block_until_ready((loss, st.params, st.master))
    run.end_window()
    losses = np.asarray([float(x) for x in st.window_losses])
    run.attempted = steps
    run.failed = int(np.sum(~np.isfinite(losses)))
    tokens = steps * int(tr["batch"]) * int(tr["seq"])
    run.end_to_end["train_tokens_per_s"] = tokens / run.window_s
    run.counters["window_flops"] = tokens * flops.train_flops_per_token(
        run.config, int(tr["seq"]))
    run.counters["steps"] = steps
    run.counters["tokens"] = tokens
    run.counters["runtime"] = st.plan.report.runtime
    spent = {name: sum(e - b for n, b, e, _ in run.spans.rows
                       if n == name and b >= t0)
             for name in ("step", "optimizer")}
    starts = [b for n, b, _, _ in run.spans.rows if n == "step" and b >= t0]
    gaps = np.diff(starts + [run.window_t1])
    med = float(np.median(gaps))
    run.log(f"window: {steps} steps, {tokens} tokens in "
            f"{run.window_s:.4f} s; last loss {losses[-1]!r}; host "
            f"seconds in plan.execute {spent['step']!r} (the runtime's "
            f"own {runtime_s[1]!r}, of which dispatching segments "
            f"{runtime_s[0]!r}), in the update's dispatch "
            f"{spent['optimizer']!r}; step to step median {med!r} s, "
            f"max {float(gaps.max())!r} s, {int(np.sum(gaps > 1.5 * med))} "
            f"over 1.5 x the median")


def leaf_gap(prog: list, ref: list, ref_grads: list) -> tuple[float, int]:
    """The worst leaf's gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median leaf's. Leaves whose reference gradient is under a thousandth
    of the median leaf's are left out: round-off alone moves them."""
    med_g = float(np.median(ref_grads))
    med = float(np.median(ref))
    worst, at = 0.0, -1
    for i, (p, r) in enumerate(zip(prog, ref)):
        if ref_grads[i] < 1e-3 * med_g:
            continue
        gap = abs(p - r) / max(r, med, 1e-30)
        if gap >= worst:
            worst, at = gap, i
    return worst, at


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers a training run compares: ``prog`` and ``ref`` each
    hold ``losses``, ``grad_norms`` and ``change_norms``."""
    grad_gap, gi = leaf_gap(prog["grad_norms"], ref["grad_norms"],
                            ref["grad_norms"])
    change_gap, ci = leaf_gap(prog["change_norms"], ref["change_norms"],
                              ref["grad_norms"])
    med = float(np.median(ref["grad_norms"]))
    return {"loss_gap": max(abs(p - r) / abs(r) for p, r in
                            zip(prog["losses"], ref["losses"])),
            "grad_norm_gap": grad_gap, "change_norm_gap": change_gap,
            "grad_leaf": gi, "change_leaf": ci,
            "left_out": [i for i, g in enumerate(ref["grad_norms"])
                         if g < 1e-3 * med]}


def compare(run, prog: dict, ref: dict, names: list) -> None:
    g = gaps(prog, ref)
    run.log(f"vs reference: losses {prog['losses']} vs {ref['losses']}; "
            f"worst gradient leaf {names[g['grad_leaf']]}, worst change "
            f"leaf {names[g['change_leaf']]}; leaves left out "
            f"{[names[i] for i in g['left_out']]}")
    for key in ("loss_gap", "grad_norm_gap", "change_norm_gap"):
        run.check(key, g[key], run.limits[key])


def reference_readings(run, st: State, prec: str = "f32",
                       half_batch: bool = False) -> dict:
    """The plain reference over the cell's first steps, on the first
    chip, from the weights and batches remade from the seed."""
    tr = run.traffic
    dev = run.devices[0]
    params = weights.make_params(st.spec, traffic.key_for(run.seed, 0),
                                 [dev] * st.n_params)
    batches = make_batches(run.seed, st.cfg, tr, [dev, dev],
                           int(tr["check_steps"]))
    if half_batch:
        half = int(tr["batch"]) // 2
        batches = [jax.tree_util.tree_map(lambda x: x[:half], b)
                   for b in batches]
    return reference.sgd_steps(run.config, params, batches,
                               float(tr["lr"]), prec)


def check(run, st: State) -> None:
    prog = {"losses": st.losses, "grad_norms": st.grad_norms,
            "change_norms": st.change_norms}
    names = [weights.leaf_name(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(st.spec)[0]]
    st.params = st.master = st.batches = st.plan = None
    run.info["reference"] = reference_readings(run, st)
    run.info["program"] = prog
    compare(run, prog, run.info["reference"], names)


def control(make_run, seeds: list) -> list:
    """Readings for the limits, at the cell's own size, in one process:
    per seed the program's gaps to the reference (the lower reading),
    the float8 reference's (the control) and those of the reference
    with half of each batch left out (a fault)."""
    st = build(make_run(seeds[0]))
    out = []
    for seed in seeds:
        run = make_run(seed)
        first_steps(run, st)
        prog = {"losses": st.losses, "grad_norms": st.grad_norms,
                "change_norms": st.change_norms}
        st.params = st.master = st.batches = None
        ref = reference_readings(run, st)
        row = {"seed": seed, "program": gaps(prog, ref),
               "control_fp8": gaps(reference_readings(run, st, "fp8"), ref),
               "half_batch": gaps(reference_readings(
                   run, st, half_batch=True), ref)}
        run.log(f"readings {row}")
        out.append(row)
    return out
