"""The reader of a placed training cell's transfer counter per step, on
small hand-built runs."""
from types import SimpleNamespace

import pytest

from bench import harness


def fake_run(events=None, counters=None):
    return SimpleNamespace(info={"program_events": events},
                           devices=[SimpleNamespace(id=0)],
                           trace_dir="never-read", counters=counters or {})


def read(name, run):
    return harness.metric_reader(name).read(run)


@pytest.mark.parametrize("steps,gb", [
    ([2.5e9, 2.5e9, 2.5e9], 2.5), ([1e9, 3e9], 2.0), ([7.25e8], 0.725)])
def test_transfer_gb_per_step(steps, gb):
    run = fake_run(counters={"transfer_bytes_per_step": steps})
    assert read("transfer_gb_per_step.train", run) == pytest.approx(gb)


def test_transfer_gb_per_step_without_the_counter_reads_none():
    assert read("transfer_gb_per_step.train", fake_run()) is None
    assert read("transfer_gb_per_step.train", fake_run(
        counters={"transfer_bytes_per_step": []})) is None
