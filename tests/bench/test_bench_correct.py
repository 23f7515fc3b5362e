"""The correctness check against the plain reference: sound runs pass,
and each fault that the timed path of a cell can have, and the float8
control, come out as not correct (at toy widths on the CPU)."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness

from benchtools import run_tiny


def _planted(root, cell, plant):
    import os
    import time
    c = harness.find_cell(cell, root=root)
    plant(c.kind)
    return harness.run_cell(c, seed=2**31 + 11, seconds=0.5, trace=False,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:1],
                            out_dir=os.path.join(os.path.dirname(root),
                                                 "runs", cell))


def _correct(run) -> bool:
    return harness.result_line(run)["correct"]


@pytest.mark.parametrize("cell", ["train-tiny", "serve-tiny"])
def test_sound_runs_are_correct(tiny_root, cell):
    run = run_tiny(tiny_root, cell)
    assert _correct(run), [(c.name, c.value, c.limit) for c in run.checks]
    assert run.window_compiles == 0


def test_state_left_unchanged(tiny_root):
    def plant(kind):
        kind.make_sgd = lambda lr, devices: (lambda m, p, g: (m, p))
    run = _planted(tiny_root, "train-tiny", plant)
    assert not _correct(run)
    assert {c.name for c in run.checks if not c.ok} >= {"change_norm_gap"}


def test_half_the_batch_left_out(tiny_root):
    def plant(kind):
        real = kind.execute

        def half(plan, params, batch):
            # the mean over the first half of the rows only
            b = batch["tokens"].shape[0] // 2
            return real(plan, params, jax.tree_util.tree_map(
                lambda x: jnp.concatenate([x[:b], x[:b]]), batch))
        kind.execute = half
    run = _planted(tiny_root, "train-tiny", plant)
    assert not _correct(run)


def test_a_served_token_altered(tiny_root):
    def plant(kind):
        real = kind._wrap

        def wrap(run, engine):
            real(run, engine)
            decode = engine._run_decode

            def altered():
                n = decode()
                for req in engine.scheduler.active:
                    if req.rid >= 0 and len(req.output) == 2:
                        req.output[-1] = (req.output[-1] + 1) % 512
                return n
            engine._run_decode = altered
        kind._wrap = wrap
    run = _planted(tiny_root, "serve-tiny", plant)
    assert not _correct(run)


def test_float8_control_fails_the_train_limits(tiny_root):
    import time
    c = harness.find_cell("train-tiny", root=tiny_root)

    def make_run(seed):
        return harness.Run(cell=c, seed=seed, seconds=0.5, trace=False,
                           t_start=time.perf_counter(),
                           devices=jax.devices()[:1],
                           compiles=harness.CompileWatch())
    (row,) = c.kind.control(make_run, [2**31 + 5])
    lim = c.workload["limits"]
    assert all(row["program"][k] <= lim[k] for k in lim)
    assert any(row["control_fp8"][k] > lim[k] for k in lim)
    assert any(row["half_batch"][k] > lim[k] for k in lim)


def test_float8_control_fails_the_serve_limit(tiny_root):
    import time
    c = harness.find_cell("serve-tiny", root=tiny_root)

    def make_run(seed):
        return harness.Run(cell=c, seed=seed, seconds=1.0, trace=False,
                           t_start=time.perf_counter(),
                           devices=jax.devices()[:1],
                           compiles=harness.CompileWatch())
    (row,) = c.kind.control(make_run, [2**31 + 5])
    lim = c.workload["limits"]["logit_gap"]
    assert row["failed"] == 0 and row["tokens"] > 0
    assert row["program"] <= lim < row["control_fp8"]
