"""The readers of the program's spans (``bench/metrics/_program.py``) on
small hand-built traces, and the loader on a profile recorded on the
CPU."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import harness
from bench.metrics import _program

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# window 1000..11000 ns. Chip 0 is busy over 1000-2000, 3000-4000 and
# 5200-11000, so it idles 2000-3000 (mid 2500: in a write_prompt) and
# 4000-5200 (mid 4600: a write_prompt ends at 4500, so "prefill").
DEVICE = {0: [("fusion.1", 0.0, 2000.0), ("copy.3", 3000.0, 1000.0),
              ("fusion.2", 5200.0, 6000.0)]}
WINDOW = ("bench/window", 1000.0, 10000.0)
# training: two steps in the window, one across its start and one after
# its end; one name carries the span's metadata
TRAIN = {"device": DEVICE, "host": [
    WINDOW,
    ("repro/plan/execute", 500.0, 1000.0),
    ("repro/runtime/wait", 1200.0, 200.0),
    ("repro/plan/execute", 2000.0, 2000.0),
    ("repro/runtime/wait", 3000.0, 800.0),
    ("repro/plan/execute#segments=1#", 6000.0, 1000.0),
    ("repro/runtime/wait", 6500.0, 100.0),
    ("repro/plan/execute", 20000.0, 1000.0),
    ("repro/runtime/wait", 20500.0, 100.0),
]}
# serving: a prefill with two writes in the window and one after it,
# then decode steps
SERVE = {"device": DEVICE, "host": [
    WINDOW,
    ("repro/serving/prefill_batch", 1500.0, 3700.0),
    ("bench/prefill", 1600.0, 3500.0),
    ("repro/serving/write_prompt#blocks=2,tokens=20#", 2000.0, 1500.0),
    ("repro/serving/write_prompt", 3500.0, 1000.0),
    ("repro/serving/write_prompt", 12000.0, 1000.0),
    ("repro/serving/decode_step", 7000.0, 2000.0),
    ("repro/runtime/wait#x=1#", 7500.0, 1200.0),
    ("repro/serving/decode_step", 9100.0, 400.0),
    ("repro/runtime/wait", 9200.0, 100.0),
    ("repro/serving/decode_step", 9600.0, 100.0),
    ("repro/serving/decode_step", 9800.0, 100.0),
    ("repro/runtime/wait", 9850.0, 10.0),
]}

READERS = ("runtime_host_ms_per_step.train", "write_prompt_ms_per_req.serve",
           "write_prompt_idle_pct.serve", "decode_host_ms_per_tick.serve")


def fake_run(events):
    return SimpleNamespace(info={"program_events": events},
                           devices=[SimpleNamespace(id=0)],
                           trace_dir="never-read")


def read(name, events):
    return harness.metric_reader(name).read(fake_run(events))


def test_runtime_host_ms_per_step():
    # (2000 - 800) and (1000 - 100) ns: the steps in the window only
    assert read("runtime_host_ms_per_step.train", TRAIN) == pytest.approx(
        (1200 + 900) / 2 * 1e-6)


def test_write_prompt_ms_per_req():
    assert read("write_prompt_ms_per_req.serve", SERVE) == pytest.approx(
        (1500 + 1000) / 2 * 1e-6)


def test_write_prompt_idle_pct():
    assert read("write_prompt_idle_pct.serve", SERVE) == pytest.approx(
        100.0 * 1000 / 10000)
    idle = dict(_program.idle_by_span(fake_run(SERVE))["idle_gaps"])
    assert idle == pytest.approx({"repro/serving/write_prompt": 1000e-9,
                                  "prefill": 1200e-9})


def test_decode_host_ms_per_tick():
    # steps holding a wait: 2000 - 1200, 400 - 100, 100 - 10; the step
    # with no wait ran nothing
    assert read("decode_host_ms_per_tick.serve", SERVE) == pytest.approx(
        300e-6)


@pytest.mark.parametrize("name", READERS)
def test_no_program_spans_read_none(name):
    harness_only = {"device": DEVICE,
                    "host": [e for e in TRAIN["host"] + SERVE["host"]
                             if not e[0].startswith("repro/")]}
    assert read(name, harness_only) is None
    assert read(name, None) is None


@pytest.mark.parametrize("name", READERS)
def test_spans_outside_the_window_read_none(name):
    late = {"device": DEVICE,
            "host": [WINDOW] + [(n, s + 1e6, d)
                                for n, s, d in TRAIN["host"] + SERVE["host"]
                                if n.startswith("repro/")]}
    assert read(name, late) is None


_RECORD = """
import json, sys
import jax, jax.numpy as jnp
from repro.obs import spans
from bench.metrics import _program
d = sys.argv[1]
jax.profiler.start_trace(d)
with jax.profiler.TraceAnnotation("bench/window"):
    with spans.span("plan/execute"):
        with spans.span("runtime/dispatch", segment=0, device=0):
            jnp.ones(8).block_until_ready()
        with spans.span("runtime/wait"):
            pass
jax.profiler.stop_trace()
ev = _program.load_events(d)
print("EVENTS:" + json.dumps(ev))
"""


def test_loader_reads_a_recorded_profile(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
    env.pop("REPRO_TRACE", None)
    r = subprocess.run([sys.executable, "-c", _RECORD, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-4000:]
    ev = json.loads(r.stdout.splitlines()[-1].removeprefix("EVENTS:"))
    names = [_program.span_name(n) for n, _, _ in ev["host"]]
    assert sorted(names) == ["bench/window", "repro/plan/execute",
                             "repro/runtime/dispatch", "repro/runtime/wait"]
    assert ev["device"] == {}
    (execute,) = _program.spans(ev, "plan/execute")
    (dispatch,) = _program.spans(ev, "runtime/dispatch")
    assert execute[0] <= dispatch[0] <= dispatch[1] <= execute[1]
    (host_ms,) = _program.self_ms(ev, "plan/execute", "runtime/wait")
    assert 0 < host_ms <= (execute[1] - execute[0]) * 1e-6
