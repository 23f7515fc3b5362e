"""Serving latency is timed from each request's due time: a stall of the
engine that delays submissions shows in the first-token times."""
import time
from types import SimpleNamespace

import numpy as np

from bench import harness, traffic
from bench.kinds import serve

STALL_S = 0.5


class StallingEngine:
    """Emits one token per request per tick; its first tick stalls."""

    def __init__(self):
        self.active = []
        self.ticks = 0
        self.stats = SimpleNamespace(admitted=0, decode_steps=0)
        self.scheduler = self

    @property
    def drained(self):
        return not self.active

    def submit(self, req):
        req.arrival_s = time.perf_counter()
        self.active.append(req)

    def tick(self):
        if self.ticks == 0:
            time.sleep(STALL_S)
        self.ticks += 1
        now = time.perf_counter()
        for req in list(self.active):
            req.emit(1, now)
            if req.hit_stop():
                self.active.remove(req)


def test_a_stall_shows_in_ttft():
    reqs = [traffic.Request(rid=i, due_s=0.05 * i,
                            prompt=np.zeros(4, np.int32), max_new_tokens=3)
            for i in range(10)]
    config = harness.load_json(f"{harness.BENCH}/configs/granite-8b-2l.json")
    cell = harness.Cell(name="stall", root="", workload={}, config=config,
                        traffic={"drain_s": 5.0}, kind=serve,
                        end_to_end=[], per_layer=[])
    run = harness.Run(cell=cell, seed=0, seconds=1.0, trace=False,
                      t_start=time.perf_counter(),
                      compiles=SimpleNamespace(count=0))
    st = serve.State(cfg=None, spec=None, plan=None, engine=StallingEngine(),
                     requests=reqs, engine_cfg={})
    serve.window(run, st)
    # the requests due during the stall are submitted after it, so their
    # time from submission hides the stall and their time from due does
    # not: ttft from due is at least the stall less the due offset
    stalled = [r for r in reqs if 0 < r.due_s < STALL_S]
    assert len(stalled) >= 5
    for r in stalled:
        q = st.served[r.rid]
        assert q.ttft_s() < 0.1
        assert q.first_token_s - (run.window_t0 + r.due_s) \
            >= STALL_S - r.due_s - 1e-3
    assert run.counters["serving"]["ttft_p90_ms"] >= 1e3 * (STALL_S - 0.1)
    assert run.failed == 0 and run.attempted == 10
