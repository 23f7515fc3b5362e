"""Step-2 and the verifier's memory certificate on one model of what a
chip holds: ``repro.partition`` re-plans where the certificate overflows,
so a plan it calls feasible is never refused by RP020/RP040; resident
bytes (state kept beside an input leaf, such as an optimizer's float32
master) are charged by both, exactly; and calls without them plan as
before. On a reduced Qwen2.5 (untied head, QKV biases) over four
described devices."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.analysis import certified_peaks
from repro.core.emulator import emulate
from repro.core.memops import compute_profile
from repro.core.partitioner import pardnn_partition

K = 4


@pytest.fixture(scope="module")
def step():
    from repro.configs import get_config
    from repro.models import loss_fn, params_spec
    cfg = dataclasses.replace(
        get_config("qwen2.5-14b"), num_layers=3, d_model=128, num_heads=4,
        num_kv_heads=2, head_dim=32, d_ff=384, vocab_size=4096)
    assert cfg.qkv_bias and not cfg.tie_embeddings
    spec = params_spec(cfg)
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    traced = repro.trace(
        jax.value_and_grad(lambda p, b: loss_fn(cfg, p, b, remat_policy="full"),
                           has_aux=True),
        spec, {"tokens": tok, "targets": tok}, record=True)
    masters = [4.0 * x.size for x in jax.tree_util.tree_leaves(spec)] + \
        [0.0, 0.0]
    one = float(repro.partition(traced, devices=1).peak_mem[0])
    return traced, masters, one


def _rp(report, code):
    return [d for d in report.errors if d.code == code]


@pytest.mark.parametrize("masters", [False, True])
@pytest.mark.parametrize("share", [0.3, 0.35, 0.4, 0.45, 0.55, 0.7])
def test_a_feasible_plan_is_never_refused(step, share, masters):
    traced, res, one = step
    plan = repro.partition(traced, devices=K, memory=share * one,
                           resident=res if masters else None)
    report = plan.verify()
    if plan.feasible:
        assert not _rp(report, "RP020") and not _rp(report, "RP040"), \
            report.render(max_findings=5)
        cert = plan.report.counters["certified_peaks"]
        assert max(cert) <= share * one


def test_the_certificate_re_plans_some_cap(step):
    """At least one of the caps above needs the certificate's rounds:
    the op-level Step-2 alone would have claimed a plan the verifier
    refuses."""
    traced, res, one = step
    rounds = [repro.partition(traced, devices=K, memory=s * one,
                              resident=res).report.counters["cert_rounds"]
              for s in (0.3, 0.35, 0.4, 0.45, 0.55, 0.7)]
    assert max(rounds) > 0, rounds


def _per_pe(plan, res):
    nodes = plan.traced.program.input_nodes
    return np.bincount(plan.assignment[nodes], weights=res, minlength=K)


@pytest.mark.parametrize("engine", ["vector", "scalar"])
def test_resident_bytes_move_the_peaks_by_their_size(step, engine):
    traced, res, one = step
    plan = repro.partition(traced, devices=K, memory=0.5 * one)
    g, a = traced.graph, plan.assignment
    per_node = np.zeros(g.n)
    per_node[traced.program.input_nodes] = res
    expect = _per_pe(plan, res)
    assert expect.sum() == sum(res) and np.count_nonzero(expect) > 1
    cert = certified_peaks(traced.program, a, K, g)
    cert_res = certified_peaks(traced.program, a, K, g, per_node)
    np.testing.assert_allclose(cert_res - cert, expect, rtol=0, atol=1.0)
    sched = emulate(g, a, K, engine=engine)
    prof = compute_profile(g, a, sched, K, engine=engine)
    prof_res = compute_profile(g, a, sched, K, engine=engine,
                               resident=per_node)
    np.testing.assert_allclose(prof_res.peak - prof.peak, expect, rtol=0,
                               atol=1.0)


@pytest.mark.parametrize("share", [0.5, 0.7, None])
def test_without_resident_bytes_the_plan_is_unchanged(step, share):
    traced, res, one = step
    memory = None if share is None else share * one
    plain = repro.partition(traced, devices=K, memory=memory)
    zeros = repro.partition(traced, devices=K, memory=memory,
                            resident=[0.0] * len(res))
    np.testing.assert_array_equal(plain.assignment, zeros.assignment)
    if plain.report.counters["cert_rounds"] == 0:
        # the certificate held at once: Step-2's own placement stands
        bare = pardnn_partition(traced.graph, K, mem_caps=memory)
        np.testing.assert_array_equal(plain.assignment, bare.assignment)


def test_the_step_with_its_masters_is_refused_on_one_chip(step):
    traced, res, one = step
    plan = repro.partition(traced, devices=1, memory=0.9 * one,
                           resident=res)
    assert not plan.feasible
    assert repro.partition(traced, devices=1, memory=1.2 * one).feasible


def test_resident_bytes_are_saved_with_the_plan(step, tmp_path):
    traced, res, one = step
    plan = repro.partition(traced, devices=K, memory=0.7 * one,
                           resident=res)
    path = plan.save(str(tmp_path / "plan.json"))
    back = repro.PartitionPlan.load(path, traced=traced)
    np.testing.assert_array_equal(back.resident, plan.resident)
    assert back.verify().counts() == plan.verify().counts()


def test_resident_bytes_need_one_entry_per_input_leaf(step):
    traced, res, one = step
    with pytest.raises(ValueError, match="input leaves"):
        repro.partition(traced, devices=K, resident=res[:-1])
    with pytest.raises(ValueError, match="record=True"):
        repro.partition(traced.graph, devices=K, resident=res)


#: random DAGs (seed, cap as a share of the uncapped plan's peak) whose
#: Step-2 left an overflow when the knapsack skipped every device with
#: less global headroom than the node's M_pot
PREFILTERED = [(6, 0.8), (21, 0.8), (27, 0.6), (35, 0.6), (37, 0.8),
               (43, 0.8), (51, 0.7), (57, 0.7)]


@pytest.mark.parametrize("seed,share", PREFILTERED)
def test_the_knapsack_tries_devices_the_prefilter_ruled_out(
        monkeypatch, seed, share):
    """A device whose peak lies at another time than the overflow can
    host a node whose M_pot exceeds its global headroom: the exact
    tracker accepts such a move, and with it the plan fits."""
    from repro.core import partitioner
    from repro.core.graph import random_dag
    rng = np.random.default_rng(seed)
    g = random_dag(int(rng.integers(40, 200)), avg_deg=2.5, seed=seed,
                   frac_residual=0.1)
    k = int(rng.integers(2, 5))
    cap = share * float(max(pardnn_partition(g, k).peak_mem))
    below = []
    knapsack = partitioner.address_overflow

    def watched(g, assignment, pe, amount, pots, headroom, *args, **kw):
        before = headroom.copy()
        res = knapsack(g, assignment, pe, amount, pots, headroom, *args,
                       **kw)
        if res.moved:
            node, _, target = res.moved[0]
            below.append(before[target] < pots[node])
        return res

    monkeypatch.setattr(partitioner, "address_overflow", watched)
    plan = pardnn_partition(g, k, mem_caps=cap)
    assert plan.feasible and max(plan.peak_mem) <= cap
    assert any(below)
