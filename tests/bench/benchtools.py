"""Helpers of the benchmark's CPU tests: a copy of ``bench/`` in a
temporary directory, with tiny cells added as new files, and a function
that runs a cell there on the CPU (the look for a chip skipped)."""
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"name": "tiny", "hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "intermediate_size": 128, "vocab_size": 512,
              "torch_dtype": "float32"}


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(base: str) -> str:
    """``<base>/bench``: the benchmark's files plus the tiny cells
    ``train-tiny`` and ``serve-tiny`` (the real cells' traffic, limits
    and engine settings, at toy widths and lengths)."""
    root = os.path.join(base, "bench")
    shutil.copytree(os.path.join(REPO, "bench"), root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    with open(os.path.join(root, "configs", "granite-8b-2l.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY_MODEL)
    cfg["serving"].update(max_len=128, num_blocks=4 * 8 + 1, max_batch=4,
                          token_budget=64)
    _dump(os.path.join(root, "configs", "tiny.json"), cfg)
    with open(os.path.join(root, "traffic", "train-4x1024.json")) as f:
        tr = json.load(f)
    _dump(os.path.join(root, "traffic", "train-tiny.json"),
          {**tr, "seq": 32})
    with open(os.path.join(root, "traffic", "poisson-chat-4k.json")) as f:
        ts = json.load(f)
    ts.update(rate_per_s=20, max_total=128, drain_s=30, sample_tokens=200,
              reference_pad=32,
              prompt={**ts["prompt"], "median": 24, "min": 8, "max": 64},
              output={**ts["output"], "median": 12, "min": 2, "max": 48})
    _dump(os.path.join(root, "traffic", "serve-tiny.json"), ts)
    for name, real in (("train-tiny", "train-granite8b-1chip"),
                       ("serve-tiny", "serve-granite8b-poisson")):
        with open(os.path.join(root, "workloads", f"{real}.json")) as f:
            wl = json.load(f)
        wl.update(config="tiny", traffic=name)
        _dump(os.path.join(root, "workloads", f"{name}.json"), wl)
        bm["workloads"].append({"name": name, "config": "tiny",
                                "traffic": name, "chips": 1, "why": "test"})
        for m in bm["end_to_end"] + bm["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"].append(name)
    _dump(os.path.join(base, "BENCHMARK.json"), bm)
    return root


def run_tiny(root: str, cell: str, *, seed: int = 2**31 + 7,
             seconds: float = 1.0, trace: bool = False):
    """Set-up, window and check of ``cell`` on the CPU; returns the
    harness's ``Run``."""
    import jax
    from bench import harness
    c = harness.find_cell(cell, root=root)
    return harness.run_cell(c, seed=seed, seconds=seconds, trace=trace,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:1],
                            out_dir=os.path.join(os.path.dirname(root),
                                                 "runs", cell))
