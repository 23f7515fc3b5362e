#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, through the user-facing entry points.

    python3 chip_smoke.py              # train + serve phases on one chip
    python3 chip_smoke.py --chips 4    # the four-chip placement phase only

Model: granite-8b at its published widths (d_model 4096, 32/8 heads,
head_dim 128, d_ff 14336, vocab 49152, bf16), cut in depth only, with
random weights drawn from ``--seed``.

Phases:

* **train** (2 layers, 4 x 1024 tokens) — ``repro.trace`` of
  ``value_and_grad(loss_fn)`` → ``repro.partition`` on the chip under its
  own ``bytes_limit`` → ``plan.verify()`` → ``plan.execute`` on the
  compiled runtime for 3 SGD steps. Step 1's loss and gradients are held
  against the un-partitioned ``jax.jit`` of the same function on the
  same chip.
* **serve** (the same 2-layer model) — ``partition_for_serving`` →
  ``plan.serve()`` answers 8 requests (prompts of 128-512 tokens, 32 new
  tokens each). Every emitted token is held against the sequential,
  un-batched, un-partitioned reference fed the same history.
* **place** (``--chips 4``; 4 layers, 2 x 1024 tokens) — the step is
  partitioned over the four chips under a per-chip cap of half of what
  one chip would need, executed with ``mode="async"`` and ``mode="sync"`` (which
  must agree bit for bit), and held against the un-partitioned
  ``jax.jit`` on chip 0.

The script refuses to run anywhere but on a TPU and never falls back to
the CPU. Every phase prints its measurements; the last line of standard
output is one JSON object, printed only when every phase passed. A
failed check or any exception exits non-zero. Everything runs in this
one process: a child process could not reach the chip this one holds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
import numpy as np                                       # noqa: E402
from jax.sharding import SingleDeviceSharding            # noqa: E402

import repro                                             # noqa: E402
from repro.configs import get_config                     # noqa: E402
from repro.models import (decode_step, init_params, loss_fn,  # noqa: E402
                          params_spec, prefill)
from repro.serving import Request, partition_for_serving  # noqa: E402

ARCH = "granite-8b"

TRAIN_LAYERS = 2          # ~0.84 B parameters
TRAIN_BATCH, TRAIN_SEQ = 4, 1024
TRAIN_STEPS = 3
SGD_LR = 1e-2

SERVE_PROMPT_LENS = (128, 512, 256, 384, 384, 256, 512, 128)
SERVE_NEW_TOKENS = 32
SERVE_BLOCK_SIZE = 16

PLACE_LAYERS = 4          # ~1.27 B parameters
PLACE_BATCH, PLACE_SEQ = 2, 1024
PLACE_CHIPS = 4
# Per-chip cap for the four-chip plan, as a share of Step-2's own
# one-device peak for the same step (11.98 GB): no chip may hold half
# the step, and Step-2 has to move nodes to meet the cap. At 0.45 Step-2
# finds no placement; just above 0.5 the placement it finds is refused
# by plan.verify(), whose schedule certificate exceeds Step-2's peak.
PLACE_CAP_FRACTION = 0.5

# Tolerances of the partitioned step against the un-partitioned jax.jit.
# Both run bf16 weights; XLA fuses the partitioned program (replayed op
# by op, cut into segments) differently from the original, so some bf16
# intermediates are rounded at other points (bf16 eps = 2^-7).
# The loss is a mean of f32 NLL terms over thousands of tokens: rounding
# differences of single logits average out to far below one bf16 eps.
LOSS_RTOL = 2e-3
# Per gradient leaf, ||g - g_ref|| / ||g_ref||: a few bf16 roundings
# accumulated through the backward pass, about 2.5 eps. The worst leaves
# on a v5e read 1.2e-2 (the embedding's scatter-add of bf16 rows, one
# chip) and 1.7e-2 (an attention projection cut into 119 segments over
# four chips); any float32 run lands orders of magnitude below.
GRAD_RTOL = 2e-2
# Serving: each emitted token's reference logit may fall below the
# reference's top logit by at most this many eps of the parameter dtype
# times the top logit's magnitude. Logits leave a bf16 matmul, and the
# engine differs from the reference in batch shape, padding and cache
# layout, so near-ties can flip the argmax; a wrong cache, position or
# placement instead leaves the token whole units below the top.
GAP_EPS = 8


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def granite(layers: int):
    """granite-8b at its published widths, cut to ``layers`` layers."""
    return dataclasses.replace(get_config(ARCH),
                               num_layers=layers).validate()


def _gb(nbytes) -> str:
    return "not reported" if nbytes is None else f"{nbytes / 1e9:.4f} GB"


def _memory_stat(device, key: str):
    stats = device.memory_stats()
    return None if stats is None else stats.get(key)


def _bytes_limit(device) -> float:
    limit = _memory_stat(device, "bytes_limit")
    _check(limit is not None, f"{device} reports no bytes_limit")
    return float(limit)


def _describe(cfg, batch: int, seq: int) -> str:
    return (f"{cfg.name} {cfg.num_layers} layers, d_model {cfg.d_model}, "
            f"heads {cfg.num_heads}/{cfg.num_kv_heads}, head_dim "
            f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
            f"{cfg.dtype}: {cfg.param_count() / 1e9:.3f} B parameters; "
            f"batch {batch} x {seq} tokens")


def train_step_fn(cfg):
    """The traced function: ``((loss, parts), grads)`` of the LM loss."""
    return jax.value_and_grad(lambda p, b: loss_fn(cfg, p, b), has_aux=True)


def batch_spec(batch: int, seq: int) -> dict:
    s = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return {"tokens": s, "targets": s}


def random_batch(cfg, key, batch: int, seq: int) -> dict:
    kx, kt = jax.random.split(key)
    return {"tokens": jax.random.randint(kx, (batch, seq), 0,
                                         cfg.vocab_size, jnp.int32),
            "targets": jax.random.randint(kt, (batch, seq), 0,
                                          cfg.vocab_size, jnp.int32)}


def init_params_on(cfg, key, leaf_devices):
    """``init_params(cfg, key)`` with leaf ``i`` made directly on
    ``leaf_devices[i]``: one jit per device returns that device's leaves
    (XLA drops the rest), so no device ever holds the whole model."""
    treedef = jax.tree_util.tree_structure(params_spec(cfg))
    leaves = [None] * len(leaf_devices)
    by_device: dict = {}
    for i, dev in enumerate(leaf_devices):
        by_device.setdefault(dev, []).append(i)
    for dev, idx in by_device.items():
        def pick(k, idx=tuple(idx)):
            flat = jax.tree_util.tree_leaves(init_params(cfg, k))
            return [flat[i] for i in idx]
        vals = jax.jit(pick, out_shardings=SingleDeviceSharding(dev))(
            jax.device_put(key, dev))
        for i, v in zip(idx, vals):
            leaves[i] = v
    return jax.tree_util.tree_unflatten(treedef, leaves)


def input_devices(plan, devices) -> list:
    """The device of every flat input leaf: where the plan put its graph
    input node."""
    return [devices[int(plan.assignment[nid])]
            for nid in plan.traced.program.input_nodes]


def place_tree(tree, leaf_devices):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [jax.device_put(x, d) for x, d in zip(leaves, leaf_devices)])


def trace_step(cfg, batch: int, seq: int, phase: str):
    """``repro.trace`` of the training step at abstract shapes."""
    t = time.perf_counter()
    traced = repro.trace(train_step_fn(cfg), params_spec(cfg),
                         batch_spec(batch, seq), record=True)
    log(phase, f"trace {time.perf_counter() - t:.3f} s: {traced.n} nodes")
    return traced


def partition_step(traced, devices, memory: float, phase: str):
    """Partition and verify a traced step; fails unless Step-2 met the
    cap and the verifier found no error."""
    t0 = time.perf_counter()
    plan = repro.partition(traced, devices=list(devices), memory=memory)
    t1 = time.perf_counter()
    report = plan.verify()
    counts = report.counts()
    log(phase, f"partition {t1 - t0:.3f} s onto {plan.k} device(s) under "
               f"{_gb(memory)} each: feasible={plan.feasible}, Step-2 "
               f"moved {plan.report.moved_nodes} | verify "
               f"{time.perf_counter() - t1:.3f} s: {counts['error']} "
               f"error(s), {counts['warn']} warning(s)")
    _check(plan.feasible, f"Step-2 found no placement under {_gb(memory)}")
    _check(not report.has_errors(), report.render(max_findings=10))
    return plan


def compare_step(out, ref, phase: str) -> dict:
    """Hold a step's ``((loss, parts), grads)`` (host copies) against the
    reference's under LOSS_RTOL / GRAD_RTOL."""
    (loss, _), grads = out
    (rloss, _), rgrads = ref
    loss, rloss = float(loss), float(rloss)
    loss_rel = abs(loss - rloss) / max(abs(rloss), 1e-30)
    worst, worst_name = 0.0, ""
    for (path, r), g in zip(jax.tree_util.tree_flatten_with_path(rgrads)[0],
                            jax.tree_util.tree_leaves(grads)):
        name = jax.tree_util.keystr(path)
        g = np.asarray(g, np.float64)
        r = np.asarray(r, np.float64)
        _check(np.all(np.isfinite(g)), f"non-finite gradient at {name}")
        rel = float(np.linalg.norm(g - r) / max(np.linalg.norm(r), 1e-30))
        if rel >= worst:
            worst, worst_name = rel, name
    log(phase, f"vs un-partitioned jit: loss {loss!r} vs {rloss!r} (rel "
               f"{loss_rel:.3e}, tol {LOSS_RTOL}); worst gradient leaf "
               f"{worst_name} rel L2 {worst:.3e} (tol {GRAD_RTOL})")
    _check(np.isfinite(loss), f"non-finite loss {loss}")
    _check(loss_rel <= LOSS_RTOL, f"loss off the reference by {loss_rel:.3e}")
    _check(worst <= GRAD_RTOL,
           f"gradient {worst_name} off the reference by {worst:.3e}")
    return {"loss": loss, "ref_loss": rloss, "loss_rel": loss_rel,
            "worst_grad_rel": worst, "worst_grad_leaf": worst_name}


def _block(tree):
    return jax.block_until_ready(tree)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def train_phase(cfg, *, device, batch: int = TRAIN_BATCH,
                seq: int = TRAIN_SEQ, steps: int = TRAIN_STEPS,
                seed: int = 0, memory: float | None = None) -> dict:
    """Partitioned training step on one device, against the jit."""
    phase = "train"
    log(phase, _describe(cfg, batch, seq))
    memory = _bytes_limit(device) if memory is None else float(memory)
    plan = partition_step(trace_step(cfg, batch, seq, phase), [device],
                          memory, phase)
    kp, kb = jax.random.split(jax.random.PRNGKey(seed))
    params = init_params_on(cfg, kp, [device] * len(
        jax.tree_util.tree_leaves(params_spec(cfg))))
    data = jax.device_put(random_batch(cfg, kb, batch, seq), device)
    sgd = jax.jit(lambda p, g: jax.tree_util.tree_map(
        lambda a, b: (a - SGD_LR * b).astype(a.dtype), p, g))

    p, first, losses, secs = params, None, [], []
    for i in range(steps):
        t = time.perf_counter()
        out = _block(plan.execute(p, data, runtime="compiled"))
        secs.append(time.perf_counter() - t)
        rt = plan.report.runtime
        losses.append(float(out[0][0]))
        log(phase, f"step {i + 1}: {secs[-1]:.4f} s (compile "
                   f"{rt['compile_seconds']:.4f} s so far), loss "
                   f"{losses[-1]!r}")
        _check(np.isfinite(losses[-1]), f"step {i + 1}: loss {losses[-1]}")
        if i == 0:
            first = jax.device_get(out)
        if i + 1 < steps:
            p = _block(sgd(p, out[1]))
        del out
    del p
    peak = _memory_stat(device, "peak_bytes_in_use")
    rt = plan.report.runtime
    log(phase, f"{rt['num_segments']} segment(s); peak_bytes_in_use "
               f"{_gb(peak)} | Step-2 certificate {_gb(plan.peak_mem[0])} | "
               f"runtime count {_gb(rt['peak_live_bytes'][0])} | "
               f"bytes_limit {_gb(memory)}")

    t = time.perf_counter()
    ref = jax.device_get(_block(jax.jit(train_step_fn(cfg))(params, data)))
    log(phase, f"un-partitioned jit: {time.perf_counter() - t:.4f} s "
               f"(compile included)")
    res = compare_step(first, ref, phase)
    return {**res, "losses": losses, "step_seconds": secs,
            "nodes": plan.n, "segments": rt["num_segments"],
            "compile_seconds": rt["compile_seconds"],
            "peak_bytes_in_use": peak,
            "certificate_bytes": float(plan.peak_mem[0]),
            "runtime_peak_bytes": rt["peak_live_bytes"][0]}


def _serving_reference(cfg, params, max_len: int):
    """Sequential, un-batched, un-partitioned greedy decoding, fed a
    given token history: returns the logits before every new token."""
    pre = jax.jit(lambda p, t: prefill(cfg, p, {"tokens": t}, max_len))
    dec = jax.jit(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos))

    def logits_along(prompt, output) -> np.ndarray:
        lg, caches = pre(params, jnp.asarray(prompt, jnp.int32)[None])
        rows = [lg[0, -1]]
        pos = len(prompt)
        for tok in output[:-1]:
            lg, caches = dec(params, caches,
                             jnp.asarray([[tok]], jnp.int32),
                             jnp.asarray(pos, jnp.int32))
            rows.append(lg[0, -1])
            pos += 1
        return np.asarray(jnp.stack(rows), np.float64)

    return logits_along


def serve_phase(cfg, *, device, prompt_lens=SERVE_PROMPT_LENS,
                new_tokens: int = SERVE_NEW_TOKENS,
                block_size: int = SERVE_BLOCK_SIZE, seed: int = 0,
                memory: float | None = None) -> dict:
    """``plan.serve()`` on one device, against the sequential reference."""
    phase = "serve"
    n = len(prompt_lens)
    max_len = -(-(max(prompt_lens) + new_tokens) // block_size) * block_size
    log(phase, f"{cfg.name} {cfg.num_layers} layers: {n} requests, "
               f"prompts {min(prompt_lens)}-{max(prompt_lens)} tokens, "
               f"{new_tokens} new tokens each; max_len {max_len}")
    memory = _bytes_limit(device) if memory is None else float(memory)
    params = init_params_on(cfg, jax.random.PRNGKey(seed), [device] * len(
        jax.tree_util.tree_leaves(params_spec(cfg))))
    t0 = time.perf_counter()
    plan = partition_for_serving(
        cfg, params, devices=[device], memory=memory,
        block_size=block_size, max_len=max_len, max_batch=n,
        num_blocks=n * (max_len // block_size) + 1)
    report = plan.verify()
    log(phase, f"decode step traced + partitioned + verified in "
               f"{time.perf_counter() - t0:.3f} s: {plan.n} nodes, "
               f"feasible={plan.feasible}, {report.counts()['error']} "
               f"error(s)")
    _check(plan.feasible, "serving plan infeasible")
    _check(not report.has_errors(), report.render(max_findings=10))

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=L).astype(np.int32)
               for L in prompt_lens]
    engine = plan.serve(cfg, params)
    for i, pr in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=pr, max_new_tokens=new_tokens))
    t = time.perf_counter()
    done = engine.run_until_drained()
    wall = time.perf_counter() - t
    st = engine.stats
    log(phase, f"served {st.completed} requests, {st.generated_tokens} "
               f"tokens in {wall:.4f} s (compile included): "
               f"{st.prefill_calls} prefill call(s), {st.decode_steps} "
               f"decode steps, {st.preempted} preemptions, "
               f"{st.leaked_blocks} leaked blocks")
    _check(sorted(done) == list(range(n)), f"completed {sorted(done)}")
    _check(st.leaked_blocks == 0, f"{st.leaked_blocks} leaked blocks")

    t = time.perf_counter()
    logits_along = _serving_reference(cfg, params, max_len)
    gaps, tols, agree = [], [], []
    for i, pr in enumerate(prompts):
        out = np.asarray(done[i].output)
        _check(len(out) == new_tokens, f"request {i}: {len(out)} tokens")
        ref = logits_along(pr, out)
        top = ref.max(axis=1)
        gaps.append(top - ref[np.arange(len(out)), out])
        tols.append(GAP_EPS * float(jnp.finfo(jnp.dtype(cfg.dtype)).eps)
                    * np.maximum(np.abs(top), 1.0))
        agree.append(ref.argmax(axis=1) == out)
    gaps, tols, agree = map(np.concatenate, (gaps, tols, agree))
    j = int(np.argmax(gaps / tols))
    log(phase, f"vs sequential un-batched reference "
               f"({time.perf_counter() - t:.4f} s): worst logit gap below "
               f"the reference's top {gaps[j]:.6g} (tol {tols[j]:.6g}); "
               f"token agreement {int(agree.sum())}/{agree.size} "
               f"(information only)")
    _check(bool(np.all(gaps <= tols)),
           f"{int(np.sum(gaps > tols))} token(s) fall below the "
           f"reference's top logit by more than the tolerance")
    return {"requests": n, "tokens": st.generated_tokens,
            "worst_gap": float(gaps[j]), "gap_tol": float(tols[j]),
            "token_agreement": float(agree.mean()), "seconds": wall}


def placement_phase(cfg, *, devices, batch: int = PLACE_BATCH,
                    seq: int = PLACE_SEQ, seed: int = 0,
                    cap_fraction: float = PLACE_CAP_FRACTION) -> dict:
    """The step placed over several chips under a binding per-chip cap:
    async == sync bit for bit, both against the jit on ``devices[0]``."""
    phase = "place"
    devices = list(devices)
    log(phase, _describe(cfg, batch, seq) + f"; {len(devices)} chips")
    traced = trace_step(cfg, batch, seq, phase)
    one = float(repro.partition(traced, devices=1).peak_mem[0])
    cap = cap_fraction * one
    log(phase, f"Step-2 peak on one device {_gb(one)}; per-chip cap "
               f"{cap_fraction} of it: {_gb(cap)}")
    plan = partition_step(traced, devices, cap, phase)
    used = np.bincount(plan.assignment, minlength=len(devices))
    log(phase, f"nodes per chip {used.tolist()}; Step-2 certificate "
               f"{[_gb(x) for x in plan.peak_mem]}")
    _check(bool(np.all(used > 0)), f"plan leaves a chip idle: {used}")

    kp, kb = jax.random.split(jax.random.PRNGKey(seed))
    leaf_devs = input_devices(plan, devices)
    n_params = len(jax.tree_util.tree_leaves(params_spec(cfg)))
    params = init_params_on(cfg, kp, leaf_devs[:n_params])
    data = place_tree(random_batch(cfg, kb, batch, seq),
                      leaf_devs[n_params:])

    outs = {}
    for mode in ("async", "sync"):
        t = time.perf_counter()
        out = _block(plan.execute(params, data, runtime="compiled",
                                  mode=mode))
        secs = time.perf_counter() - t
        rt = plan.report.runtime
        peaks = [_memory_stat(d, "peak_bytes_in_use") for d in devices]
        log(phase, f"{mode}: {secs:.4f} s (compile {rt['compile_seconds']:.4f}"
                   f" s so far), loss {float(out[0][0])!r}; "
                   f"{rt['num_segments']} segments "
                   f"{rt['segments_per_device']}; {rt['transfers']} "
                   f"transfers, {rt['transfer_bytes'] / 1e9:.4f} GB; "
                   f"{rt['prefetched_transfers']} prefetched, "
                   f"{rt['deferred_transfers']} deferred")
        log(phase, f"{mode}: per chip peak_bytes_in_use "
                   f"{[_gb(x) for x in peaks]} | runtime count "
                   f"{[_gb(x) for x in rt['peak_live_bytes']]}")
        outs[mode] = jax.device_get(out)
        del out
    bits = [np.asarray(x).tobytes() for x in
            jax.tree_util.tree_leaves(outs["async"])]
    same = all(b == np.asarray(y).tobytes() for b, y in
               zip(bits, jax.tree_util.tree_leaves(outs["sync"])))
    log(phase, f"async == sync bit for bit: {same}")
    _check(same, "async and sync dispatch disagree")

    params = jax.device_put(params, devices[0])
    data = jax.device_put(data, devices[0])
    t = time.perf_counter()
    ref = jax.device_get(_block(jax.jit(train_step_fn(cfg))(params, data)))
    ref_peak = _memory_stat(devices[0], "peak_bytes_in_use")
    log(phase, f"un-partitioned jit on chip 0: {time.perf_counter() - t:.4f}"
               f" s (compile included); chip 0 peak_bytes_in_use now "
               f"{_gb(ref_peak)}")
    res = compare_step(outs["async"], ref, phase)
    return {**res, "nodes": plan.n, "nodes_per_chip": used.tolist(),
            "cap_bytes": cap, "one_device_bytes": one,
            "async_equals_sync": same}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, PLACE_CHIPS), default=1,
                    help=f"1: train + serve phases; {PLACE_CHIPS}: the "
                         f"multi-chip placement phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    log("setup", f"{len(devices)} x {devices[0].device_kind}; compile "
                 f"cache {enable_compile_cache()}")

    if args.chips == PLACE_CHIPS:
        placement_phase(granite(PLACE_LAYERS),
                        devices=devices[:PLACE_CHIPS], seed=args.seed)
    else:
        cfg = granite(TRAIN_LAYERS)
        train_phase(cfg, device=devices[0], seed=args.seed)
        serve_phase(cfg, device=devices[0], seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
