"""The traffic generators: deterministic per seed, the same sizes for
every seed, and the distributions and clips the traffic files state."""
import os
import statistics

import numpy as np

from bench import harness, traffic

SERVE = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                       "poisson-chat-4k.json"))
BIG_SEED = 2**31 + 12345


def test_same_seed_same_schedule():
    a = traffic.serve_requests(SERVE, 30, BIG_SEED, 49152)
    b = traffic.serve_requests(SERVE, 30, BIG_SEED, 49152)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_the_schedule_not_the_tokens():
    a = traffic.serve_requests(SERVE, 30, 1, 49152)
    b = traffic.serve_requests(SERVE, 30, 2**33 + 1, 49152)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert not np.array_equal(a[0].prompt[:32], b[0].prompt[:32])
    # another order_seed orders the same sizes and gaps another way
    c = traffic.serve_requests({**SERVE, "order_seed": 1}, 30, 1, 49152)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)
    assert np.allclose(sorted(np.diff([0] + [r.due_s for r in a])),
                       sorted(np.diff([0] + [r.due_s for r in c])))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]


def test_lognormal_lengths_and_clips():
    spec = SERVE["prompt"]
    n = 4000
    lens = traffic.quantile_lengths(n, spec)
    assert lens.min() == spec["min"] and lens.max() == spec["max"]
    assert abs(np.median(lens) - spec["median"]) <= 1
    # share clipped at the top: P(median * e^(sigma z) > max)
    top = 1 - statistics.NormalDist().cdf(
        np.log(spec["max"] / spec["median"]) / spec["sigma"])
    assert abs(np.mean(lens == spec["max"]) - top) < 2 / n + 1e-3
    # the unclipped middle follows the lognormal: its log-quartiles
    q1, q3 = np.percentile(np.log(lens), [25, 75])
    z = statistics.NormalDist().inv_cdf(0.75)
    assert abs((q3 - q1) / 2 - spec["sigma"] * z) < 0.01
    out = traffic.quantile_lengths(n, SERVE["output"])
    assert out.min() >= SERVE["output"]["min"]
    assert out.max() <= SERVE["output"]["max"]
    assert abs(np.median(out) - SERVE["output"]["median"]) <= 1


def test_uniform_lengths():
    lens = traffic.quantile_lengths(
        100, {"dist": "uniform", "min": 32, "max": 128})
    assert lens.min() >= 32 and lens.max() <= 128
    assert abs(lens.mean() - 80) < 1


def test_requests_fit_the_engine_and_the_window():
    reqs = traffic.serve_requests(SERVE, 25, 7, 49152)
    assert len(reqs) == round(SERVE["rate_per_s"] * 25)
    due = np.array([r.due_s for r in reqs])
    assert due[0] > 0 and np.all(np.diff(due) > 0) and due[-1] < 25
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert abs(gaps.mean() * SERVE["rate_per_s"] - 1) < 0.05
    assert 0.8 < gaps.std() / gaps.mean() < 1.2      # exponential: cv 1
    for r in reqs:
        assert len(r.prompt) + r.max_new_tokens <= SERVE["max_total"]
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 49152


def test_train_batches_follow_the_seed():
    import jax
    a = traffic.train_batch(traffic.key_for(BIG_SEED, 1), 512, 2, 16)
    b = traffic.train_batch(traffic.key_for(BIG_SEED, 1), 512, 2, 16)
    c = traffic.train_batch(traffic.key_for(BIG_SEED, 2), 512, 2, 16)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool((x == y).all()), a, b))
    assert not bool((a["tokens"] == c["tokens"]).all())
    assert a["tokens"].shape == (2, 16) and int(a["tokens"].max()) < 512


def test_sweep_knee_stops_at_the_first_growing_rate():
    sweep = harness.load_module(os.path.join(harness.BENCH, "sweep.py"),
                                "sweep")
    flat = np.full(40, 0.8)
    rising = np.linspace(0.5, 6.0, 40)
    # an overloaded window: already backlogged by its second quarter,
    # and still climbing by more than a request
    high = np.linspace(10.0, 30.0, 40)
    assert not sweep.grows(flat)
    assert sweep.grows(rising) and sweep.grows(high)
    rows = [{"rate_per_s": r, "grows": g, "failed": f} for r, g, f in
            ((2.0, False, 0), (2.5, True, 0), (3.0, False, 0),
             (1.5, False, 0))]
    assert sweep.knee_of(rows) == 2.0
    assert sweep.knee_of([{**rows[3], "failed": 1}]) is None
