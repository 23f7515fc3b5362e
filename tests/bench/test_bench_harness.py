"""The harness: cells found by name from files alone, ``BENCHMARK.json``
in step with those files, the result line's keys, and no result where
there is no chip."""
import json
import os
import re
import subprocess
import sys
import time

import jax
import pytest

from bench import harness

from benchtools import REPO, run_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

NEW_KIND = '''
def setup(run):
    return {}


def window(run, state):
    run.start_window()
    run.end_window()
    run.end_to_end["echo_per_s"] = 1.5
    run.attempted = 1


def check(run, state):
    run.check("echo_gap", 0.0, run.limits["echo_gap"])
'''


def _write(path, obj):
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def test_new_files_are_found_by_name(tiny_root):
    base = os.path.dirname(tiny_root)
    _write(os.path.join(tiny_root, "kinds", "echo.py"), NEW_KIND)
    _write(os.path.join(tiny_root, "metrics", "echo_layer.py"),
           "def read(run):\n    return 42.0\n")
    _write(os.path.join(tiny_root, "traffic", "echo-mix.json"),
           {"kind": "echo"})
    _write(os.path.join(tiny_root, "configs", "echo-model.json"),
           {"name": "echo-model"})
    _write(os.path.join(tiny_root, "workloads", "echo-cell.json"),
           {"config": "echo-model", "traffic": "echo-mix", "chips": 1,
            "limits": {"echo_gap": 0.0}})
    with open(os.path.join(base, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["workloads"].append({"name": "echo-cell", "config": "echo-model",
                            "traffic": "echo-mix", "chips": 1, "why": "t"})
    bm["end_to_end"].append({"name": "echo_per_s", "unit": "1/s",
                             "better": "higher", "bound": 0.1,
                             "source": "host_clock",
                             "workloads": ["echo-cell"]})
    bm["per_layer"].append({"name": "echo_layer", "unit": "%",
                            "better": "higher", "source": "host_clock",
                            "layer": "echo", "moves": "echo_per_s",
                            "workloads": ["echo-cell"]})
    _write(os.path.join(base, "BENCHMARK.json"), bm)
    cell = harness.find_cell("echo-cell", root=tiny_root)
    assert cell.config == {"name": "echo-model"}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "echo_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["echo_layer"]
    run = harness.run_cell(cell, seed=1, seconds=0.1, trace=False,
                           t_start=time.perf_counter(),
                           devices=jax.devices()[:1],
                           out_dir=os.path.join(base, "runs", "echo"))
    line = harness.result_line(run)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "echo_per_s"}
    run.trace = True
    assert harness.result_line(run)["metrics"] == {
        "echo_layer": {"value": 42.0, "unit": "%"}}


def test_benchmark_json_matches_its_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    for p in bm["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    configs = {c["name"]: c for c in bm["configs"]}
    metric_names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    e2e = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    cells = [w["name"] for w in bm["workloads"]]
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        wl = harness.load_json(os.path.join(REPO, "bench", "workloads",
                                            f"{w['name']}.json"))
        assert (wl["config"], wl["traffic"], wl["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        tr = harness.load_json(os.path.join(REPO, "bench", "traffic",
                                            f"{w['traffic']}.json"))
        assert os.path.isfile(os.path.join(REPO, "bench", "kinds",
                                           f"{tr['kind']}.py"))
        assert w["config"] in configs
        reported = [m for m in bm["end_to_end"]
                    if "workloads" not in m or w["name"] in m["workloads"]]
        assert len(reported) >= 2
    for c in bm["configs"]:
        path = os.path.join(REPO, c["file"])
        cfg = harness.load_json(path)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert c["file"].startswith("bench/configs/")
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bm["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(REPO, "bench", "metrics",
                                           f"{m['name']}.py"))


def test_last_line_has_the_contract_keys(tiny_root):
    run = run_tiny(tiny_root, "train-tiny")
    line = json.loads(json.dumps(harness.result_line(run)))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_no_tpu_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train-granite8b-1chip", "--seed", str(2**31 + 3), "--seconds",
         "1", "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_config_runs_as_its_file_states():
    from bench.model import program_config
    conf = harness.load_json(os.path.join(REPO, "bench", "configs",
                                          "granite-8b-2l.json"))
    cfg = program_config(conf)
    assert (cfg.num_layers, cfg.qkv_bias, cfg.tie_embeddings,
            cfg.rope_theta, cfg.norm_eps) == (2, True, True, 1e7, 1e-5)
    # biases the program has no code for are refused, not dropped
    for key in ("attention_bias", "mlp_bias"):
        with pytest.raises(ValueError, match=key):
            program_config({**conf, key: True})
