"""A placed training cell at toy widths on four host devices (CPU): a
reduced Qwen2.5 (untied head, QKV biases) through ``bench/kinds/
train_placed.py``. The partitioned step's loss, gradients and master
change match the staged reference; the staged reference equals
``bench/reference.py`` on one device; a planted fault (half of each
batch) and the float8 control come out as not correct.

All of it runs in one child process, which sees four devices."""
import json
import subprocess
import sys
import textwrap

import pytest

from repro.conformance.subproc import forced_mesh_env

from benchtools import REPO

CHILD = r"""
import json, os, sys, tempfile, time
sys.path[:0] = [os.path.join(REPO, "tests", "bench"), REPO,
                os.path.join(REPO, "src")]
import jax
import numpy as np
from benchtools import make_root
from bench import harness, reference, reference_staged, traffic, weights

base = tempfile.mkdtemp()
root = make_root(base)

def load(*p):
    with open(os.path.join(root, *p)) as f:
        return json.load(f)

def dump(obj, *p):
    with open(os.path.join(root, *p), "w") as f:
        json.dump(obj, f)

cfg = load("configs", "qwen2.5-14b-8l.json")
cfg.update(name="qwen-tiny", hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, intermediate_size=128,
           vocab_size=512, num_hidden_layers=4, torch_dtype="float32")
dump(cfg, "configs", "qwen-tiny.json")
dump({**load("traffic", "train-2x1024.json"), "seq": 16},
     "traffic", "placed-tiny.json")
wl = load("workloads", "train-qwen14b-4chip.json")
wl.update(config="qwen-tiny", traffic="placed-tiny")
dump(wl, "workloads", "placed-tiny.json")
devices = jax.devices()[:4]
out = {"limits": wl["limits"], "devices": len(devices)}

def run(cell, seed):
    return harness.run_cell(cell, seed=seed, seconds=0.5, trace=False,
                            t_start=time.perf_counter(), devices=devices,
                            out_dir=os.path.join(base, "runs"))

c = harness.find_cell("placed-tiny", root=root)
r = run(c, 2**31 + 7)
line = harness.result_line(r)
out["sound"] = {"correct": line["correct"], "checks": line["checks"],
                "window_compiles": r.window_compiles,
                "transfers": r.counters["transfers_per_step"],
                "transfer_bytes": r.counters["transfer_bytes_per_step"],
                "chips": sorted(set(int(d.id) for d in r.devices))}

real = c.kind.base.execute
def half(plan, params, batch):
    b = batch["tokens"].shape[0] // 2
    import jax.numpy as jnp
    return real(plan, params, jax.tree_util.tree_map(
        lambda x: jnp.concatenate([x[:b], x[:b]]), batch))
c.kind.base.execute = half
out["half_batch_run"] = harness.result_line(run(c, 2**31 + 11))["correct"]
c.kind.base.execute = real

def make_run(seed):
    return harness.Run(cell=c, seed=seed, seconds=0.5, trace=False,
                       t_start=time.perf_counter(), devices=devices,
                       compiles=harness.CompileWatch())
(out["control"],) = c.kind.control(make_run, [2**31 + 5])

from bench.model import program_config
from repro.models import params_spec
spec = params_spec(program_config(cfg))
key = traffic.key_for(2**31 + 3, 0)
batches = [traffic.train_batch(traffic.key_for(2**31 + 3, 1 + i),
                               cfg["vocab_size"], 2, 16) for i in range(2)]
plain = reference.sgd_steps(
    cfg, weights.make_params(spec, key, [devices[0]] *
                             len(jax.tree_util.tree_leaves(spec))),
    batches, 0.01)
out["plain"] = plain
out["staged"] = {n: reference_staged.sgd_steps(cfg, spec, key, devs,
                                               batches, 0.01)
                 for n, devs in (("one", devices[:1]), ("four", devices))}
print("PLACED_JSON:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def placed():
    code = "REPO = " + repr(REPO) + "\n" + textwrap.dedent(CHILD)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=forced_mesh_env(4))
    assert r.returncode == 0, r.stderr[-4000:]
    line = [x for x in r.stdout.splitlines()
            if x.startswith("PLACED_JSON:")][-1]
    return json.loads(line[len("PLACED_JSON:"):])


def test_sound_placed_run_is_correct(placed):
    sound = placed["sound"]
    assert placed["devices"] == 4
    assert sound["correct"], sound["checks"]
    assert sound["window_compiles"] == 0
    for name, lim in placed["limits"].items():
        assert sound["checks"][name]["value"] <= lim


def test_the_step_moves_data_between_chips(placed):
    sound = placed["sound"]
    assert sound["transfers"] and min(sound["transfers"]) > 0
    assert min(sound["transfer_bytes"]) > 0
    # one plan, so every step of the window moves the same bytes
    assert len(set(sound["transfer_bytes"])) == 1


def test_half_the_batch_left_out_is_not_correct(placed):
    assert placed["half_batch_run"] is False


@pytest.mark.parametrize("reading", ["control_fp8", "half_batch"])
def test_controls_fail_a_limit(placed, reading):
    lim, row = placed["limits"], placed["control"]
    assert all(row["program"][k] <= lim[k] for k in lim)
    assert any(row[reading][k] > lim[k] for k in lim)


@pytest.mark.parametrize("chips", ["one", "four"])
@pytest.mark.parametrize("key", ["losses", "grad_norms", "change_norms"])
def test_staged_reference_equals_the_plain_one(placed, chips, key):
    staged, plain = placed["staged"][chips][key], placed["plain"][key]
    assert len(staged) == len(plain)
    assert staged == pytest.approx(plain, rel=1e-5, abs=1e-7)


#: a TPU v5e's ``bytes_limit`` as its runtime reports it
V5E_BYTES_LIMIT = 16909334528.0


@pytest.fixture(scope="module")
def cell_step():
    """The cell's own step at full size, traced at abstract shapes, and
    the float32 masters the kind declares beside its weights."""
    import jax
    import repro
    from bench import harness
    from bench.kinds import train as base
    from bench.kinds import train_placed as kind
    from bench.model import program_config
    from repro.models import loss_fn, params_spec
    cell = harness.find_cell("train-qwen14b-4chip")
    cfg = program_config(cell.config)
    spec = params_spec(cfg)
    batch = base.batch_spec(cell.traffic["batch"], cell.traffic["seq"])
    traced = repro.trace(jax.value_and_grad(
        lambda p, b: loss_fn(cfg, p, b, remat_policy=kind.REMAT),
        has_aux=True), spec, batch, record=True)
    return traced, kind.resident_bytes(spec, batch), kind.MEMORY_FRACTION


@pytest.mark.parametrize("chips", [4, 1])
def test_the_cell_step_fits_four_chips_and_not_one(cell_step, chips):
    """At the cell's depth and tokens the plan over four v5e is feasible
    and verified (no RP020/RP040, every certified peak under the
    limit); the same step is refused on one."""
    import repro
    traced, masters, fraction = cell_step
    plan = repro.partition(traced, devices=chips,
                           memory=[V5E_BYTES_LIMIT] * chips,
                           options=repro.PardnnOptions(
                               memory_fraction=fraction),
                           resident=masters)
    assert plan.feasible is (chips == 4)
    if chips == 4:
        report = plan.verify()
        assert not [d for d in report.errors
                    if d.code in ("RP020", "RP040")], report.render(5)
        assert max(plan.report.counters["certified_peaks"]) \
            <= V5E_BYTES_LIMIT
