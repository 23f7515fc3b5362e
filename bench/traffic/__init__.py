"""Traffic generators: one general generator per kind of cell, driven by
the parameters in ``bench/traffic/<traffic>.json``.

The sizes and gaps are the quantiles ``(i + 0.5) / n`` of the stated
distributions, put in an order drawn from the traffic file's own
``order_seed``: every run replays the same schedule of lengths and due
times, and the run's seed draws the weights and the token ids. So two
seeds ask the same work of the system, and the spread between runs is
the system's and not the draw's (with the order drawn from the run's
seed, a serving window's tail latency swung with where the longest
prompts fell).
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass

import numpy as np


def quantile_lengths(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of
    ``spec["dist"]``, rounded and clipped to ``[min, max]``.

    ``lognormal``: ``median * exp(sigma * z)``; ``uniform``: evenly
    spaced over ``[min, max]``.
    """
    lo, hi = int(spec["min"]), int(spec["max"])
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        vals = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def poisson_gaps(n: int, seconds: float) -> np.ndarray:
    """``n`` exponential gaps at the quantiles ``(i + 0.5) / n``, scaled
    to add up to ``seconds * n / (n + 1)``: the ``n`` arrivals they space
    out fall inside the window, a mean gap before its close."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    return gaps * (seconds * n / (n + 1) / gaps.sum())


@dataclass
class Request:
    rid: int
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use of the seed (any whole number)."""
    return np.random.default_rng([int(stream), int(seed) & (2**64 - 1)])


def serve_requests(traffic: dict, seconds: float, seed: int,
                   vocab: int) -> list[Request]:
    """The open-loop schedule of one run: ``rate_per_s * seconds``
    requests due inside the window, in the order of their due times;
    lengths and due times from ``traffic["order_seed"]``, token ids from
    ``seed``."""
    n = max(int(round(float(traffic["rate_per_s"]) * seconds)), 1)
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    order = rng_for(int(traffic["order_seed"]), 0)
    prompts = order.permutation(quantile_lengths(n, traffic["prompt"]))
    outputs = order.permutation(quantile_lengths(n, traffic["output"]))
    outputs = np.minimum(outputs, int(traffic["max_total"]) - prompts)
    if np.any(outputs < 1):
        raise ValueError("max_total leaves no room for an output token")
    due = np.cumsum(order.permutation(poisson_gaps(n, seconds)))
    ids = rng_for(seed, 0)
    return [Request(rid=i, due_s=float(due[i]),
                    prompt=ids.integers(0, vocab, size=int(prompts[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(outputs[i]))
            for i in range(n)]


def key_for(seed: int, stream: int):
    """A JAX key for one use of the seed (any whole number)."""
    import jax
    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, int(seed) >> 31),
                              int(stream))


def train_batch(key, vocab: int, batch: int, seq: int) -> dict:
    """One training batch of uniform token ids and targets (traceable)."""
    import jax
    import jax.numpy as jnp
    kx, kt = jax.random.split(key)
    return {"tokens": jax.random.randint(kx, (batch, seq), 0, vocab,
                                         jnp.int32),
            "targets": jax.random.randint(kt, (batch, seq), 0, vocab,
                                          jnp.int32)}


__all__ = ["quantile_lengths", "poisson_gaps", "Request",
           "serve_requests", "rng_for", "key_for",
           "train_batch"]
