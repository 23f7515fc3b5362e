"""Training cells whose step is placed over several chips, because it
does not fit one.

The step, the window and the comparison are ``bench/kinds/train.py``'s,
with these differences:

* the traced step computes each layer again in the backward pass
  (``loss_fn``'s ``remat_policy``, ``REMAT``), as a step this large is
  trained: without it the saved activations of 8 Qwen2.5-14B layers at
  2 x 1024 tokens leave no plan over four chips;
* ``repro.partition`` is told the bytes kept beside each weight leaf on
  whichever chip holds it: the SGD's float32 master, 4 bytes an element
  (``resident=``); each chip is planned to ``MEMORY_FRACTION`` of its
  ``bytes_limit``;
* the plan dispatches its segments one at a time (``mode="sync"``,
  :class:`Serialized`), a workaround: async dispatch runs ahead of the
  chips, the copies it issues early are not in the verifier's
  certificate, and on four v5e it ran out of memory;
* the update first moves each gradient to the chip that holds its leaf,
  copies that no planner model charges;
* the weights are made a leaf at a time on their chips, and no chip
  keeps a copy of its starting weights: the change is measured against
  weights made again from the seed;
* the reference is ``bench/reference_staged.py``, over the cell's chips.

Counters besides the training cell's: per step of the window, the
runtime's transfers and bytes moved between chips and the gradient bytes
the update moved; the plan's emulated makespan; per chip, Step-2's peak,
the verifier's certified peak and the measured ``peak_bytes_in_use``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference_staged, traffic, weights
from bench.kinds import train as base
from bench.model import bytes_limit, program_config

#: ``loss_fn``'s remat policy: every layer recomputed in the backward pass
REMAT = "full"
#: Step-2's share of each chip's bytes_limit: at 0.86 the plan of
#: ``train-qwen14b-4chip`` is feasible and its certified peaks leave at
#: least 2.3 GB of each chip, more than the largest segment's own
#: temporaries (1.56 GB compiled for a v5e); at 0.9 the fullest chip is
#: certified at 16.7 of 16.9 GB
MEMORY_FRACTION = 0.86


class Serialized:
    """The plan, executed with its segments dispatched one at a time and
    each waited for (``mode="sync"``), so that no copy is issued ahead of
    the segment that needs it. With async dispatch the host ran ahead of
    the chips, and on four TPU v5e one chip ran out of memory loading a
    segment (the certificate had said 14.50 of 16.91 GB). Serialized,
    the measured peaks still lie 1.1-2.9 GB a chip above the
    certificate: the runtime's run-ahead and the update's gradient
    copies are in no model yet."""

    def __init__(self, plan):
        self.plan = plan

    def __getattr__(self, name):
        return getattr(self.plan, name)

    def execute(self, *args, **kwargs):
        return self.plan.execute(*args, **{**kwargs, "mode": "sync"})


class Update:
    """The update of a placed step: each gradient moved to the chip that
    holds its leaf, then ``bench/kinds/train.py``'s SGD. It also keeps
    each step's transfer counters from the runtime's last call."""

    def __init__(self, plan, lr: float, leaf_devices: list):
        self.plan = plan
        self.leaf_devices = leaf_devices
        self.sgd = base.make_sgd(lr, leaf_devices)
        self.transfers: list = []
        self.transfer_bytes: list = []
        self.grad_move_bytes: list = []

    def __call__(self, master, params, grads):
        rt = self.plan.report.runtime
        self.transfers.append(int(rt["transfers"]))
        self.transfer_bytes.append(float(rt["transfer_bytes"]))
        flat, treedef = jax.tree_util.tree_flatten(grads)
        moved = 0
        for i, (g, dev) in enumerate(zip(flat, self.leaf_devices)):
            if g.devices() != {dev}:
                moved += g.nbytes
                flat[i] = jax.device_put(g, dev)
        self.grad_move_bytes.append(float(moved))
        return self.sgd(master, params,
                        jax.tree_util.tree_unflatten(treedef, flat))


def resident_bytes(spec, batch: dict) -> list:
    """Bytes kept beside each flat input leaf of the step: a float32
    master beside every weight leaf, nothing beside the batch."""
    return ([4.0 * x.size for x in jax.tree_util.tree_leaves(spec)]
            + [0.0] * len(jax.tree_util.tree_leaves(batch)))


def build(run) -> base.State:
    """The plan and the update, with no weights yet."""
    import repro
    from repro.models import loss_fn, params_spec
    cfg = program_config(run.config)
    tr = run.traffic
    devices = list(run.devices)
    limits = [bytes_limit(d) for d in devices]
    spec = params_spec(cfg)
    batch = base.batch_spec(tr["batch"], tr["seq"])
    step_fn = jax.value_and_grad(
        lambda p, b: loss_fn(cfg, p, b, remat_policy=REMAT),
        has_aux=True)
    with run.spans.span("plan"):
        traced = repro.trace(step_fn, spec, batch, record=True)
        plan = repro.partition(
            traced, devices=devices,
            memory=None if None in limits else limits,
            options=repro.PardnnOptions(memory_fraction=MEMORY_FRACTION),
            resident=resident_bytes(spec, batch))
        report = plan.verify()
    counters = plan.report.counters
    run.counters["plan"] = {
        "makespan_s": float(plan.makespan),
        "step2_peak_bytes": [float(x) for x in plan.peak_mem],
        "certified_peak_bytes": counters["certified_peaks"],
        "cert_rounds": counters["cert_rounds"],
        "bytes_limit": limits}
    run.log(f"{cfg.name}: {cfg.param_count() / 1e9:.4f} B parameters, "
            f"batch {tr['batch']} x {tr['seq']}; {plan.n} nodes over "
            f"{plan.k} chips, emulated makespan {plan.makespan!r} s; "
            f"per chip, Step-2 peak {_gb(plan.peak_mem)} GB, certified "
            f"peak {_gb(counters['certified_peaks'])} GB "
            f"({counters['cert_rounds']} re-planning round(s)), "
            f"bytes_limit {limits} B")
    if not plan.feasible or report.has_errors():
        raise RuntimeError("the plan is infeasible or refused:\n"
                           + report.render(max_findings=10))
    leaf_devices = [devices[int(plan.assignment[n])]
                    for n in plan.traced.program.input_nodes]
    n_params = len(jax.tree_util.tree_leaves(spec))
    return base.State(cfg=cfg, plan=Serialized(plan), spec=spec,
                      leaf_devices=leaf_devices, n_params=n_params,
                      params=None, master=None, batches=[],
                      sgd=Update(plan, float(tr["lr"]),
                                 leaf_devices[:n_params]))


_change = jax.jit(lambda m, p: jnp.sqrt(jnp.sum(jnp.square(
    m - p.astype(jnp.float32)))))


def change_norms(st: base.State, key) -> list:
    """Per weight leaf, the norm of its master less the weights it
    started from, made again from the seed a leaf at a time on the
    leaf's chip: no chip keeps a copy of its starting weights."""
    return [float(_change(m, reference_staged.make_leaf(st.spec, key, i,
                                                        dev)))
            for i, (m, dev) in enumerate(zip(
                jax.tree_util.tree_leaves(st.master),
                st.leaf_devices[:st.n_params]))]


def first_steps(run, st: base.State) -> None:
    """``bench/kinds/train.py``'s first steps, with the weights made a
    leaf at a time on their chips and the change measured against
    weights made again from the seed (``change_norms``)."""
    tr = run.traffic
    pdevs = st.leaf_devices[:st.n_params]
    key = traffic.key_for(run.seed, 0)
    leaves = [reference_staged.make_leaf(st.spec, key, i, dev)
              for i, dev in enumerate(pdevs)]
    treedef = jax.tree_util.tree_structure(st.spec)
    st.params = jax.tree_util.tree_unflatten(treedef, leaves)
    st.master = jax.tree_util.tree_unflatten(
        treedef, base._by_device(base._to_f32, st.params, st.params, pdevs))
    st.batches = base.make_batches(run.seed, st.cfg, tr,
                                   st.leaf_devices[st.n_params:],
                                   int(tr["batches"]))
    lr = float(tr["lr"])
    st.losses = []
    for i in range(int(tr["check_steps"])):
        st.losses.append(float(base.step(run, st, st.batches[i])[0]))
        if i == 0:
            st.grad_norms = [x / lr for x in change_norms(st, key)]
    st.change_norms = change_norms(st, key)
    rt = st.plan.report.runtime
    run.counters["runtime"] = rt
    run.log(f"{rt['num_segments']} segment(s) "
            f"{rt['segments_per_device']}, compiled in "
            f"{rt['compile_seconds']:.4f} s; first losses {st.losses}")


def setup(run) -> base.State:
    st = build(run)
    first_steps(run, st)
    return st


def window(run, st: base.State) -> None:
    upd = st.sgd
    upd.transfers, upd.transfer_bytes, upd.grad_move_bytes = [], [], []
    base.window(run, st)
    run.counters["transfers_per_step"] = upd.transfers
    run.counters["transfer_bytes_per_step"] = upd.transfer_bytes
    run.counters["grad_move_bytes_per_step"] = upd.grad_move_bytes
    run.log(f"window: per step {upd.transfers[-1]} transfers between "
            f"chips, {upd.transfer_bytes[-1] / 1e9!r} GB in the runtime "
            f"and {upd.grad_move_bytes[-1] / 1e9!r} GB of gradients moved "
            f"to their leaves' chips")


def reference_readings(run, st: base.State, prec: str = "f32",
                       half_batch: bool = False) -> dict:
    """The staged reference over the cell's first steps, from the weights
    and batches remade from the seed."""
    tr = run.traffic
    dev = run.devices[0]
    batches = base.make_batches(run.seed, st.cfg, tr, [dev, dev],
                                int(tr["check_steps"]))
    if half_batch:
        half = int(tr["batch"]) // 2
        batches = [jax.tree_util.tree_map(lambda x: x[:half], b)
                   for b in batches]
    return reference_staged.sgd_steps(
        run.config, st.spec, traffic.key_for(run.seed, 0),
        list(run.devices), batches, float(tr["lr"]), prec)


def _gb(xs) -> list | None:
    return None if xs is None else [
        None if x is None else round(float(x) / 1e9, 4) for x in xs]


def _measured_peaks(run) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in run.devices]


def _program(st: base.State) -> dict:
    return {"losses": st.losses, "grad_norms": st.grad_norms,
            "change_norms": st.change_norms}


def _release(st: base.State) -> None:
    """Drop the program's weights, batches and plan, so that the
    reference has the chips."""
    st.params = st.master = st.batches = st.plan = st.sgd = None


def check(run, st: base.State) -> None:
    measured = _measured_peaks(run)
    run.counters["plan"]["measured_peak_bytes"] = measured
    planned = run.counters["plan"]
    run.log(f"per chip peak_bytes_in_use {_gb(measured)} GB, against "
            f"Step-2 {_gb(planned['step2_peak_bytes'])} and certified "
            f"{_gb(planned['certified_peak_bytes'])} GB")
    prog = _program(st)
    names = [weights.leaf_name(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(st.spec)[0]]
    _release(st)
    run.info["reference"] = reference_readings(run, st)
    run.info["program"] = prog
    base.compare(run, prog, run.info["reference"], names)


def control(make_run, seeds: list) -> list:
    """``bench/kinds/train.py``'s readings for the limits, with the
    staged reference: per seed the program's gaps to the reference, the
    float8 reference's and those of the reference with half of each
    batch left out."""
    st = build(make_run(seeds[0]))
    keep = (st.plan, st.sgd)
    out = []
    for seed in seeds:
        run = make_run(seed)
        st.plan, st.sgd = keep
        first_steps(run, st)
        prog = _program(st)
        _release(st)
        ref = reference_readings(run, st)
        row = {"seed": seed, "program": base.gaps(prog, ref),
               "control_fp8": base.gaps(reference_readings(run, st, "fp8"),
                                        ref),
               "half_batch": base.gaps(reference_readings(
                   run, st, half_batch=True), ref)}
        run.log(f"readings {row}")
        out.append(row)
    return out


__all__ = ["Update", "resident_bytes", "build", "first_steps",
           "change_norms", "setup", "window", "check", "control",
           "reference_readings"]
