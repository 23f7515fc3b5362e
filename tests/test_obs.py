"""Unified telemetry (``repro.obs``): spans, Perfetto trace export,
and the metrics registry.

Pins the contracts the rest of the repo leans on:

* the disabled span fast path is a shared no-op singleton — zero
  allocations in hot loops (checked with ``tracemalloc``);
* span recording is correct under nesting and across threads
  (``list.append`` is the GIL-atomic record path);
* emitted trace documents satisfy the Chrome trace-event shape that
  ``validate_trace`` (and the CI schema step) checks: pid/tid/ts/dur
  per event, nondecreasing timestamps within each lane;
* a real ``plan.execute(trace=...)`` on a forced 4-device mesh emits
  *both* the measured runtime lanes and the predicted emulator lanes
  for the same segments, recoverable via ``predicted_vs_measured``;
* the metrics envelope round-trips, rejects unknown schema versions,
  and passes legacy bare-dict baselines through unchanged.
"""
import json
import os
import subprocess
import sys
import threading

import pytest

from repro.conformance.subproc import forced_mesh_env, repo_src_path
from repro.obs import spans
from repro.obs.metrics import (METRICS_FORMAT, METRICS_SCHEMA_VERSION,
                               MetricsRegistry, MetricsValidationError,
                               read_metrics, validate_doc, wrap_metrics)
from repro.obs.metrics import main as metrics_main
from repro.obs.stats import (dispersion, latency_summary, median,
                             median_mad, percentile)
from repro.obs.trace import (MEASURED_PID, PREDICTED_PID, TraceBuilder,
                             export_spans, load_trace,
                             predicted_vs_measured, validate_trace)


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with the global tracer disabled and
    empty — telemetry state must never leak between tests."""
    spans.enable(False)
    spans.get_tracer().clear()
    yield
    spans.enable(False)
    spans.get_tracer().clear()


# ------------------------------------------------------------- stats
def test_percentile_interpolates_and_filters_none():
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert percentile([None, 3.0, None, 1.0], 0) == pytest.approx(1.0)
    assert percentile([], 99) is None
    assert percentile([None, None], 50) is None
    assert median([5.0]) == pytest.approx(5.0)


def test_median_mad_matches_definition():
    med, mad = median_mad([1.0, 2.0, 3.0, 100.0])
    assert med == pytest.approx(2.5)
    assert mad == pytest.approx(1.0)  # |x - 2.5| -> [1.5, .5, .5, 97.5]
    med, mad = median_mad([7.0])
    assert (med, mad) == (7.0, 0.0)


def test_dispersion_guards_empty_and_zero_median():
    assert dispersion([]) == 0.0
    assert dispersion([0.0, 0.0]) == 0.0
    assert dispersion([None, 2.0, 2.0, 2.0]) == 0.0
    assert dispersion([1.0, 2.0, 3.0]) > 0.0


def test_latency_summary_keys_and_empty_form():
    s = latency_summary([0.1, 0.2, 0.3], prefix="ttft_")
    assert set(s) == {"ttft_p50_s", "ttft_p99_s", "ttft_median_s",
                      "ttft_mad_s", "ttft_n"}
    assert s["ttft_n"] == 3
    assert s["ttft_median_s"] == pytest.approx(0.2)
    empty = latency_summary([], prefix="x_")
    assert empty == {"x_p50_s": None, "x_p99_s": None, "x_median_s": None,
                     "x_mad_s": None, "x_n": 0}


def test_measure_module_reexports_the_shared_median_mad():
    from repro.obs import stats
    from repro.profiling import measure
    assert measure.median_mad is stats.median_mad


# ------------------------------------------------------------- spans
def test_disabled_span_is_the_shared_null_singleton():
    assert not spans.enabled()
    s1, s2 = spans.span("a"), spans.span("b", cat="other")
    assert s1 is s2 is spans._NULL_SPAN
    with s1:
        pass
    assert spans.get_tracer().events == []


@pytest.mark.parametrize("jax_imported", [False, True],
                         ids=["jax-not-imported", "jax-imported-no-profiler"])
def test_disabled_span_allocates_nothing(jax_imported):
    # measured in a fresh interpreter: inside the suite, jax worker
    # threads allocate concurrently and make tracemalloc numbers
    # order-dependent; a bare process pins the claim deterministically.
    # With JAX imported the profiler probe runs on every span.
    code = (
        "import sys, tracemalloc\n"
        "from repro.obs import spans\n"
        "assert 'jax' not in sys.modules, 'repro.obs imported jax'\n"
        + ("import jax\n" if jax_imported else "")
        + "def hot(n):\n"
        "    for _ in range(n):\n"
        "        with spans.span('hot'):\n"
        "            pass\n"
        "hot(10)\n"
        "tracemalloc.start()\n"
        "hot(1000)\n"
        "current, _peak = tracemalloc.get_traced_memory()\n"
        "assert current == 0, f'{current} bytes leaked'\n"
        "print('ZERO_ALLOC_OK')\n")
    env = dict(os.environ, PYTHONPATH=repo_src_path(), JAX_PLATFORMS="cpu")
    env.pop("REPRO_TRACE", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "ZERO_ALLOC_OK" in r.stdout


_PROFILER_SNIPPET = """
import glob, json, os, sys
import jax
from jax.profiler import ProfileData
from repro.obs import spans

def names_in(d):
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("repro/")]
    return out

on, off = sys.argv[1], sys.argv[2]
with spans.span("before"):
    pass
jax.profiler.start_trace(on)
with spans.span("outer", step=3):
    with spans.span("inner"):
        jax.numpy.ones(4).block_until_ready()
    with spans.span("late") as s:
        s.set_metadata(done=1)
jax.profiler.stop_trace()
with spans.span("after"):
    pass
null_after_stop = spans.span("x") is spans._NULL_SPAN
jax.profiler.start_trace(off)
jax.profiler.stop_trace()
print("PROF:" + json.dumps({"on": names_in(on), "off": names_in(off),
                            "null_after_stop": null_after_stop,
                            "buffered": len(spans.get_tracer().events)}))
"""


def test_spans_reach_the_jax_profiler(tmp_path):
    """With a profiler session active and the in-memory tracer off, the
    spans are profiler events named ``repro/<name>``, nested as they
    were opened, carrying their arguments; outside it they are not."""
    env = dict(os.environ, PYTHONPATH=repo_src_path(), JAX_PLATFORMS="cpu")
    env.pop("REPRO_TRACE", None)
    r = subprocess.run([sys.executable, "-c", _PROFILER_SNIPPET,
                        str(tmp_path / "on"), str(tmp_path / "off")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.splitlines()[-1].removeprefix("PROF:"))
    ev = {name.split("#")[0]: (a, b, stats)
          for name, a, b, stats in out["on"]}
    assert sorted(ev) == ["repro/inner", "repro/late", "repro/outer"]
    o0, o1, ostats = ev["repro/outer"]
    for child in ("repro/inner", "repro/late"):
        assert o0 <= ev[child][0] <= ev[child][1] <= o1
    assert ev["repro/inner"][1] <= ev["repro/late"][0]
    assert ostats == {"step": 3}
    assert ev["repro/late"][2] == {"done": 1}
    assert out["off"] == []
    assert out["null_after_stop"]
    assert out["buffered"] == 0     # the in-memory sink stayed off


def test_disabled_instant_and_counter_record_nothing():
    spans.instant("evt", rid=1)
    spans.counter("pool", used=3)
    assert spans.get_tracer().events == []


def test_span_nesting_containment_and_order():
    spans.enable()
    with spans.span("outer", phase="p"):
        with spans.span("inner"):
            pass
    events = spans.get_tracer().drain()
    assert [e[1] for e in events] == ["inner", "outer"]  # LIFO close
    (_, _, _, _, _, i_ts, i_dur, _), (_, _, _, _, _, o_ts, o_dur, oargs) \
        = events
    assert o_ts <= i_ts
    assert i_ts + i_dur <= o_ts + o_dur + 1e-6
    assert oargs == {"phase": "p"}


def test_traced_decorator_only_records_when_enabled():
    calls = []

    @spans.traced("fn/work", cat="test")
    def work(x):
        calls.append(x)
        return x * 2

    assert work(2) == 4
    assert spans.get_tracer().events == []
    spans.enable()
    assert work(3) == 6
    (ev,) = spans.get_tracer().drain()
    assert ev[0] == spans.PH_COMPLETE and ev[1] == "fn/work"
    assert calls == [2, 3]


def test_spans_are_thread_safe_and_lane_tagged():
    spans.enable()
    n_threads, per_thread = 8, 200
    # thread idents are recycled once a thread exits; the barrier keeps
    # all workers alive together so each records under a distinct id
    barrier = threading.Barrier(n_threads)

    def worker():
        barrier.wait()
        for i in range(per_thread):
            with spans.span("w"):
                pass
        barrier.wait()

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    events = spans.get_tracer().drain()
    assert len(events) == n_threads * per_thread
    tids = {e[4] for e in events}
    assert len(tids) == n_threads  # one lane per recording thread


def test_enabled_spans_fold_into_a_valid_trace(tmp_path):
    spans.enable()
    spans.get_tracer().name_thread("main")
    with spans.span("stage", cat="partition", k=4):
        spans.instant("marker", cat="partition")
        spans.counter("queue", cat="partition", depth=2)
    path = export_spans(str(tmp_path / "spans.json"))
    doc = load_trace(path)
    assert validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"stage", "marker", "queue", "thread_name"} <= names
    # the span buffer was drained into the file
    assert spans.get_tracer().events == []


def test_repro_trace_env_exports_at_exit(tmp_path):
    out = tmp_path / "atexit.trace.json"
    env = dict(os.environ, REPRO_TRACE=str(out),
               PYTHONPATH=repo_src_path())
    code = ("import repro.obs.spans as s\n"
            "assert s.enabled()\n"
            "with s.span('from-env'):\n"
            "    pass\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    doc = load_trace(str(out))
    assert validate_trace(doc) == []
    assert any(e.get("name") == "from-env" for e in doc["traceEvents"])


# ----------------------------------------------------- trace builder
def _sample_builder():
    b = TraceBuilder()
    b.process(MEASURED_PID, "measured (runtime)")
    b.thread(MEASURED_PID, 0, "device 0")
    b.complete(MEASURED_PID, 0, "seg1", 50.0, 10.0, cat="measured")
    b.complete(MEASURED_PID, 0, "seg0", 10.0, 30.0, cat="measured")
    b.instant(MEASURED_PID, 0, "wake", 20.0)
    b.counter(MEASURED_PID, 0, "pool", 25.0, {"used": 3})
    return b


def test_builder_sorts_each_lane_and_validates():
    doc = _sample_builder().to_dict()
    assert validate_trace(doc) == []
    xs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert {m["name"] for m in meta} == {"process_name",
                                        "process_sort_index",
                                        "thread_name"}
    assert doc["displayTimeUnit"] == "ms"


def test_builder_clamps_negative_durations():
    b = TraceBuilder()
    b.complete(0, 0, "jitter", 10.0, -5.0)
    (ev,) = b.to_dict()["traceEvents"]
    assert ev["dur"] == 0.0


def test_validate_trace_reports_shape_violations():
    assert validate_trace({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 0, "tid": 0, "ts": 5.0,
         "dur": 1.0},
        {"ph": "X", "name": "b", "pid": 0, "tid": 0, "ts": 1.0,
         "dur": 1.0},                                   # ts decreases
        {"ph": "X", "name": "c", "pid": 0, "tid": 1, "ts": 0.0},  # no dur
        {"ph": "i", "pid": 0, "tid": 1, "ts": "soon"},  # no name, bad ts
    ]}
    problems = validate_trace(bad)
    assert any("decreases" in p for p in problems)
    assert any("dur" in p for p in problems)
    assert any("missing 'name'" in p for p in problems)
    assert any("bad ts" in p for p in problems)


def test_validate_trace_unreadable_path(tmp_path):
    p = tmp_path / "nope.json"
    assert validate_trace(str(p)) and "unreadable" in \
        validate_trace(str(p))[0]
    p.write_text("{not json")
    assert "unreadable" in validate_trace(str(p))[0]


def test_predicted_vs_measured_matches_names_across_pids():
    b = _sample_builder()
    b.process(PREDICTED_PID, "predicted (emulator)")
    b.thread(PREDICTED_PID, 0, "device 0")
    b.complete(PREDICTED_PID, 0, "seg0", 0.0, 15.0, cat="predicted")
    b.complete(PREDICTED_PID, 0, "seg9", 15.0, 5.0, cat="predicted")
    rows = predicted_vs_measured(b.to_dict())
    assert [r["name"] for r in rows] == ["seg0"]  # seg1/seg9 unmatched
    (r,) = rows
    assert r["predicted_s"] == pytest.approx(15e-6)
    assert r["measured_s"] == pytest.approx(30e-6)
    assert r["ratio"] == pytest.approx(2.0)


# ---------------------------------------------------------- metrics
def test_metrics_registry_round_trip(tmp_path):
    reg = MetricsRegistry("test_obs", meta={"arch": "tiny"})
    reg.record("speedup", 2.5)
    reg.group("levels", [{"concurrency": 1, "tokens_per_s": 10.0}])
    reg.update({"extra": 1})
    path = str(tmp_path / "m.json")
    reg.save(path)
    back = MetricsRegistry.load(path)
    assert back.source == "test_obs" and back.meta == {"arch": "tiny"}
    assert back.metrics == reg.metrics
    assert read_metrics(path) == reg.metrics


def test_metrics_envelope_shape_and_version():
    doc = wrap_metrics("src", {"a": 1}, meta={"b": 2})
    assert doc["format"] == METRICS_FORMAT
    assert doc["schema_version"] == METRICS_SCHEMA_VERSION
    assert validate_doc(doc) == []


def test_metrics_save_rejects_non_finite_and_non_json(tmp_path):
    reg = MetricsRegistry("bad")
    reg.record("nan", float("nan"))
    with pytest.raises(MetricsValidationError, match="non-finite"):
        reg.save(str(tmp_path / "bad.json"))
    reg2 = MetricsRegistry("bad2")
    reg2.record("obj", object())
    with pytest.raises(MetricsValidationError, match="non-JSON"):
        reg2.save(str(tmp_path / "bad2.json"))


def test_metrics_unknown_schema_version_rejected(tmp_path):
    doc = wrap_metrics("future", {"a": 1})
    doc["schema_version"] = 99
    with pytest.raises(MetricsValidationError, match="schema_version"):
        read_metrics(doc)
    path = tmp_path / "future.json"
    path.write_text(json.dumps(doc))
    with pytest.raises(MetricsValidationError):
        MetricsRegistry.load(str(path))


def test_read_metrics_passes_legacy_bare_dicts_through(tmp_path):
    legacy = {"records": {"arch": {"ok": True}}, "devices": 4}
    path = tmp_path / "legacy.json"
    path.write_text(json.dumps(legacy))
    assert read_metrics(str(path)) == legacy
    assert read_metrics(legacy) is legacy


def test_metrics_cli_exit_codes(tmp_path, capsys):
    good = tmp_path / "good.json"
    good.write_text(json.dumps(wrap_metrics("cli", {"x": 1})))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"format": "other"}))
    assert metrics_main([str(good)]) == 0
    assert metrics_main([str(good), str(bad)]) == 1
    assert metrics_main([]) == 2
    out = capsys.readouterr().out
    assert f"ok      {good}" in out and f"INVALID {bad}" in out


def test_benchmarks_common_write_metrics_envelopes(tmp_path):
    from benchmarks.common import write_metrics
    path = str(tmp_path / "BENCH_x.json")
    doc = write_metrics(path, "bench_x", {"speedup": 3.0},
                        meta={"tiny": True})
    assert validate_doc(doc) == []
    assert read_metrics(path) == {"speedup": 3.0}


def test_serving_stats_carries_the_shared_latency_block():
    from repro.serving.engine import ServingStats
    s = ServingStats()
    s.ttft_s.extend([0.1, 0.2])
    d = s.to_dict()
    assert d["ttft_n"] == 2
    assert d["ttft_median_s"] == pytest.approx(0.15)
    assert d["inter_token_n"] == 0 and d["inter_token_p99_s"] is None


# ------------------------------------------- plan traces, end to end
def test_execute_trace_rejects_interpret_runtime():
    import jax
    import jax.numpy as jnp

    import repro

    def f(w, x):
        return jnp.sum(jnp.tanh(x @ w))

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (4, 4))
    x = jax.random.normal(key, (2, 4))
    plan = repro.partition(repro.trace(f, w, x, record=True), devices=1)
    with pytest.raises(ValueError, match="compiled runtime"):
        plan.execute(w, x, runtime="interpret", trace="never.json")


_PLAN_TRACE_SNIPPET = """
import json
import jax, jax.numpy as jnp
import repro
from repro.obs.trace import (load_trace, predicted_vs_measured,
                             validate_trace)

def mlp(params, x):
    def layer(h, p):
        w1, w2 = p
        h = jnp.tanh(h @ w1) @ w2
        return h, jnp.sum(h)
    h, sums = jax.lax.scan(layer, x, params)
    return jnp.mean(h ** 2) + jnp.sum(sums)

assert jax.device_count() == 4
key = jax.random.PRNGKey(0)
L, D, H = 4, 8, 16
params = (jax.random.normal(key, (L, D, H)) * 0.1,
          jax.random.normal(key, (L, H, D)) * 0.1)
x = jax.random.normal(key, (2, D))
t = repro.trace(mlp, params, x, record=True)
plan = repro.partition(t, devices=4)
out = plan.execute(params, x, trace={path!r})
ref = mlp(params, x)
doc = load_trace({path!r})
problems = validate_trace(doc)
rows = predicted_vs_measured(doc)
pids = sorted({{e["pid"] for e in doc["traceEvents"]
               if e.get("ph") == "X"}})
print("OBS_JSON:" + json.dumps({{
    "problems": problems,
    "matched": len(rows),
    "pids": pids,
    "all_positive": all(r["predicted_s"] >= 0 and r["measured_s"] >= 0
                        for r in rows),
    "drift": float(abs(out - ref)),
    "runtime_recorded": bool(plan.report.runtime),
}}))
"""


def test_plan_trace_merges_predicted_and_measured_lanes(tmp_path):
    """plan.execute(trace=...) on a forced 4-device mesh: the emitted
    document validates and carries the same ``seg{sid}`` names in both
    the measured (pid 1) and predicted (pid 2) lane groups — the
    acceptance criterion for the merged trace."""
    path = str(tmp_path / "plan.trace.json")
    code = _PLAN_TRACE_SNIPPET.format(path=path)
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=600,
                       env=forced_mesh_env(4))
    assert r.returncode == 0, r.stderr[-4000:]
    payload = json.loads(
        r.stdout.splitlines()[-1].removeprefix("OBS_JSON:"))
    assert payload["problems"] == []
    assert payload["matched"] > 0
    assert MEASURED_PID in payload["pids"]
    assert PREDICTED_PID in payload["pids"]
    assert payload["all_positive"]
    assert payload["drift"] <= 1e-4
    assert payload["runtime_recorded"]


def _by_name(events):
    out = {}
    for ph, name, _cat, _pid, _tid, ts, dur, args in events:
        if ph == spans.PH_COMPLETE:
            out.setdefault(name, []).append((ts, ts + dur, args or {}))
    return out


def test_runtime_spans_nest_in_plan_execute():
    """The compiled runtime's live spans: one ``plan/execute`` per call
    holding ``runtime/call``, ``runtime/inputs``, one ``runtime/dispatch``
    per segment (with its segment and device), ``runtime/compile`` on
    the first call only, and ``runtime/wait``."""
    import jax
    import jax.numpy as jnp

    import repro

    def f(w, x):
        return jnp.sum(jnp.tanh(x @ w))

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (4, 4))
    x = jax.random.normal(key, (2, 4))
    plan = repro.partition(repro.trace(f, w, x, record=True), devices=1)
    spans.enable()
    plan.execute(w, x)
    first = _by_name(spans.get_tracer().drain())
    plan.execute(w, x)
    second = _by_name(spans.get_tracer().drain())
    nseg = plan.report.runtime["num_segments"]
    assert len(first["runtime/compile"]) == nseg
    assert "runtime/compile" not in second
    for ev in (first, second):
        (outer,) = ev["plan/execute"]
        for name in ("runtime/call", "runtime/inputs", "runtime/wait"):
            (inner,) = ev[name]
            assert outer[0] <= inner[0] <= inner[1] <= outer[1]
        assert len(ev["runtime/dispatch"]) == nseg
        assert {a["segment"] for _, _, a in ev["runtime/dispatch"]} == \
            set(range(nseg))
        assert all("device" in a for _, _, a in ev["runtime/dispatch"])
        (call,) = ev["runtime/call"]
        assert {"mode", "segments", "transfers"} <= set(call[2])


_TRANSFERS = """
import json
import jax, jax.numpy as jnp
import repro
from repro.obs import spans

def f(w, x):
    h = jnp.tanh(x @ w)
    for _ in range(3):
        h = jnp.tanh(h @ w) + h
    return jnp.sum(h * h)

key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (64, 64))
x = jax.random.normal(key, (8, 64))
traced = repro.trace(jax.grad(f), w, x, record=True)
plan = repro.partition(traced, devices=jax.devices()[:4])
out = []
for mode in ("async", "sync"):
    spans.enable()
    spans.get_tracer().drain()
    plan.execute(w, x, mode=mode)
    ev = spans.get_tracer().drain()
    spans.enable(False)
    rt = plan.report.runtime
    out.append({"mode": mode, "transfers": rt["transfers"],
                "transfer_bytes": rt["transfer_bytes"],
                "prefetched": rt["prefetched_transfers"],
                "events": [[e[1], e[5], e[6], e[7]] for e in ev
                           if e[0] == spans.PH_COMPLETE]})
print(json.dumps(out))
"""


def test_transfer_spans_nest_in_the_call_and_sum_to_the_counter():
    """On four host devices, each cross-device copy is one
    ``runtime/transfer`` span inside the call's ``runtime/call``,
    carrying ``bytes``, ``src``, ``dst`` and ``prefetched``; their bytes
    and count are the runtime's ``transfer_bytes`` and ``transfers``, in
    both dispatch modes."""
    from repro.conformance import run_py
    for call in json.loads(run_py(_TRANSFERS, devices=4).splitlines()[-1]):
        ev = call["events"]
        xfers = [e for e in ev if e[0] == "runtime/transfer"]
        (outer,) = [e for e in ev if e[0] == "runtime/call"]
        assert call["transfers"] > 0 and len(xfers) == call["transfers"]
        for _, ts, dur, args in xfers:
            assert outer[1] <= ts and ts + dur <= outer[1] + outer[2]
            assert {"bytes", "src", "dst", "prefetched"} <= set(args)
            assert args["src"] != args["dst"]
            assert 0 <= args["src"] < 4 and 0 <= args["dst"] < 4
        assert sum(a["bytes"] for *_, a in xfers) == \
            pytest.approx(call["transfer_bytes"])
        assert sum(bool(a["prefetched"]) for *_, a in xfers) == \
            call["prefetched"]
        assert call["mode"] == "async" or call["prefetched"] == 0


def test_serving_engine_spans():
    """A tick's spans: schedule, prefill_batch holding prefill_forward,
    sample and one write_prompt per admitted request (blocks, tokens),
    decode_step holding decode_inputs and sample; the tick's own span
    carries what it admitted and decoded."""
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serving import Request, ServingEngine

    cfg = reduced(get_config("granite-8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, block_size=8, num_blocks=32,
                        max_batch=2, max_len=64)
    for rid, n in enumerate((5, 11)):
        eng.submit(Request(rid=rid, prompt=np.arange(1, n + 1,
                                                     dtype=np.int32),
                           max_new_tokens=3))
    spans.enable()
    eng.tick()
    ev = _by_name(spans.get_tracer().drain())
    (tick,) = ev["serving/tick"]
    assert tick[2] == {"admitted": 2, "decoded": 2}
    for name in ("serving/schedule", "serving/prefill_batch",
                 "serving/decode_step"):
        (inner,) = ev[name]
        assert tick[0] <= inner[0] <= inner[1] <= tick[1]
    (prefill,) = ev["serving/prefill_batch"]
    (forward,) = ev["serving/prefill_forward"]
    assert prefill[0] <= forward[0] <= forward[1] <= prefill[1]
    writes = ev["serving/write_prompt"]
    assert sorted((a["blocks"], a["tokens"]) for _, _, a in writes) == \
        [(1, 5), (2, 11)]
    assert all(prefill[0] <= a <= b <= prefill[1] for a, b, _ in writes)
    (decode,) = ev["serving/decode_step"]
    (inputs,) = ev["serving/decode_inputs"]
    assert decode[0] <= inputs[0] <= inputs[1] <= decode[1]
    assert decode[2] == {"batch": 2}
    assert len(ev["serving/sample"]) == 2     # one sync each phase


def test_serving_engine_trace_export(tmp_path):
    """A tiny in-process serving run with ``trace=`` writes a valid doc
    with engine + request lanes at drain time."""
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models import init_params
    from repro.serving import Request, ServingEngine

    cfg = reduced(get_config("granite-8b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / "serve.trace.json")
    eng = ServingEngine(cfg, params, block_size=8, num_blocks=32,
                        max_batch=2, max_len=64, trace=path)
    rng = np.random.default_rng(0)
    for rid in range(3):
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(1, cfg.vocab_size, 5).astype(np.int32),
            max_new_tokens=4))
    done = eng.run_until_drained(max_ticks=1000)
    assert len(done) == 3
    doc = load_trace(path)
    assert validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    assert "decode_step" in names and "prefill_batch" in names
    assert "queued+prefill" in names and "decode" in names
