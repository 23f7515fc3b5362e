"""``bench/trace_reduce.py``: busy and idle time, top operations and
idle gaps by host span, on small traces."""
import json
import os

import numpy as np
import pytest

from bench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))

# window 50..350 ns; chip 0 runs ops over 50-100, 150-220 (two ops that
# overlap), 300-350 once clipped; chip 1 is busy 100-200 only
EVENTS = {
    "device": {
        0: [("fusion.1", 0.0, 100.0), ("fusion.2", 150.0, 50.0),
            ("copy", 180.0, 40.0), ("fusion.1", 300.0, 100.0),
            ("late", 400.0, 10.0)],
        1: [("fusion.1", 100.0, 100.0)],
    },
    "host": [("bench/window", 50.0, 300.0), ("bench/step", 40.0, 120.0),
             ("bench/wait", 190.0, 170.0), ("bench/other", 0.0, 20.0)],
}


def test_busy_idle_and_gaps_by_span():
    one = trace_reduce.reduce(EVENTS, devices=[0])
    assert one["window_s"] == pytest.approx(300e-9)
    assert one["busy_s"] == pytest.approx(170e-9)
    assert dict(one["idle_gaps"]) == pytest.approx(
        {"step": 50e-9, "wait": 80e-9})
    assert dict(one["device_ops"]) == pytest.approx(
        {"fusion.1": 100e-9, "fusion.2": 50e-9, "copy": 40e-9})
    assert [k for k, _ in one["device_ops"]] == ["fusion.1", "fusion.2",
                                                 "copy"]


def test_averaged_over_chips():
    both = trace_reduce.reduce(EVENTS)
    assert both["devices"] == [0, 1]
    assert both["busy_s"] == pytest.approx((170e-9 + 100e-9) / 2)
    # chip 1 idles 50-100 (step) and 200-350 (mid 275: wait)
    assert dict(both["idle_gaps"]) == pytest.approx(
        {"step": (50e-9 + 50e-9) / 2, "wait": (80e-9 + 150e-9) / 2})
    busy = both["busy_s"]
    assert sum(v for _, v in both["idle_gaps"]) == pytest.approx(
        both["window_s"] - busy)


def test_gap_outside_every_span():
    ev = {"device": {0: [("op", 10.0, 10.0)]},
          "host": [("bench/window", 0.0, 100.0)]}
    out = trace_reduce.reduce(ev)
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"outside spans": 90e-9})


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"device": {0: []}, "host": []})


@pytest.mark.parametrize("name", ["trace_sample_train.json",
                                  "trace_sample_serve.json"])
def test_recorded_chip_trace(name):
    """A slice of a trace recorded on a TPU v5e: the reduction's shares
    add up, and every idle gap is put down to a harness span."""
    with open(os.path.join(HERE, "data", name)) as f:
        raw = json.load(f)
    ev = {"device": {int(k): [tuple(e) for e in v]
                     for k, v in raw["device"].items()},
          "host": [tuple(e) for e in raw["host"]]}
    out = trace_reduce.reduce(ev)
    assert 0 < out["busy_s"] <= out["window_s"]
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-9)
    assert sum(v for _, v in out["device_ops"]) >= out["busy_s"] * (1 - 1e-9)
    # busy time counted another way: the window cut into 100 ns ticks,
    # each tick busy when some operation covers it
    (w0, wd), = [(s, d) for n, s, d in ev["host"] if n == "bench/window"]
    ticks = np.zeros(int(wd // 100) + 1, bool)
    busy = []
    for dev, ops in ev["device"].items():
        ticks[:] = False
        for _, s, d in ops:
            a = max(int(np.ceil((s - w0) / 100)), 0)
            b = min(int(np.ceil((s + d - w0) / 100)), len(ticks))
            ticks[a:b] = True
        busy.append(ticks.sum() * 100e-9)
    assert out["busy_s"] == pytest.approx(np.mean(busy), rel=1e-3)
