"""Compiled segment runtime: segment cutting, liveness, and equality
against the op-by-op interpreter and the un-partitioned reference.

Equality contract: identical dtype/shape and values equal to within a
few float ulp (XLA fuses ops *within* a jitted segment, e.g.
``mean(h**2)``, whose reduction rounding can differ from the eager
interpreter's op-at-a-time execution by 1-2 ulp — the same slack any
``jax.jit`` has against eager). Repeated calls of the same compiled
runtime are pinned exactly equal (deterministic executables).

In-process tests run on the default (single) device; multi-device
behaviour runs in subprocesses with forced host devices (the device
count must be fixed before jax initializes) via the shared helper in
``repro.conformance.subproc``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.conformance import run_py
from repro.core import pardnn_partition
from repro.core.errors import PlanValidationError
from repro.core.executor import compute_liveness, execute
from repro.core.runtime import (DEFAULT_TRANSFER_WINDOW_BYTES,
                                CompiledRuntime, resolve_runtime_mode)
from repro.core.segments import cut_segments, device_topo_order
from repro.core.tracing import trace_cost_graph


def _mlp(params, x):
    def layer(h, p):
        w1, w2 = p
        h = jnp.tanh(h @ w1) @ w2
        return h, jnp.sum(h)
    h, sums = jax.lax.scan(layer, x, params)
    return jnp.mean(h ** 2) + jnp.sum(sums)


def _multi(params, x):
    """Multi-result pytree output: dict of scalars + an array."""
    def layer(h, p):
        w1, w2 = p
        h = jnp.tanh(h @ w1) @ w2
        return h, jnp.max(h)
    h, maxes = jax.lax.scan(layer, x, params)
    return {"loss": jnp.mean(h ** 2), "h": h, "maxes": maxes,
            "x_through": x}


def _example(L=4, D=16, H=32):
    key = jax.random.PRNGKey(0)
    params = (jax.random.normal(key, (L, D, H)) * 0.1,
              jax.random.normal(key, (L, H, D)) * 0.1)
    x = jax.random.normal(key, (3, D))
    return params, x


def assert_matches(actual, desired):
    """dtype/shape exact; values within a few float32 ulp (see module
    docstring for why exact bit-equality vs eager is not well-defined)."""
    a, d = np.asarray(actual), np.asarray(desired)
    assert a.dtype == d.dtype and a.shape == d.shape
    np.testing.assert_allclose(a, d, rtol=2e-6, atol=1e-8)


# ---------------------------------------------------------------- liveness
def test_trace_records_liveness_table():
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    assert prog.consumers is not None and prog.output_nodes is not None
    # the trace-time table must equal the recomputed-from-program one
    cons, outs = compute_liveness(prog)
    assert prog.consumers == cons
    assert prog.output_nodes == outs
    # consumer ids ascend and last_consumer is their max
    for p, cs in cons.items():
        assert list(cs) == sorted(cs)
        assert prog.last_consumer(p) == cs[-1]
    assert prog.last_consumer(10 ** 9) == -1


# ---------------------------------------------------------------- segments
def test_cut_segments_covers_program_acyclically():
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 3)
    sched = cut_segments(prog, p.assignment, k=3)
    seen = []
    pos = {}
    for seg in sched.segments:
        assert all(int(p.assignment[n]) == seg.device for n in seg.nodes)
        for n in seg.nodes:
            pos[n] = seg.sid
        seen.extend(seg.nodes)
    assert sorted(seen) == sorted(prog.program)     # exact cover
    # dataflow only points backwards across segments (acyclic schedule)
    for seg in sched.segments:
        for src, _ in seg.inputs:
            if src in pos:
                assert pos[src] < seg.sid
    # adjacent segments differ in device (maximality of runs)
    for a, b in zip(sched.segments, sched.segments[1:]):
        assert a.device != b.device


def test_device_affine_order_is_topological():
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 4)
    order = device_topo_order(prog, p.assignment)
    rank = {n: i for i, n in enumerate(order)}
    for nid, (_, _, inputs) in prog.program.items():
        for inp in inputs:
            if inp[0] == "slot" and inp[1] in rank:
                assert rank[inp[1]] < rank[nid]
    # and it coalesces devices at least as well as raw id order
    def runs(seq):
        return sum(1 for i, n in enumerate(seq)
                   if i == 0 or p.assignment[n] != p.assignment[seq[i - 1]])
    assert runs(order) <= runs(sorted(prog.program))


def test_segment_refcounts_match_consumption():
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 3)
    sched = cut_segments(prog, p.assignment, k=3)
    seg_of = {n: s.sid for s in sched.segments for n in s.nodes}
    for src, rc in sched.node_refcount.items():
        consuming = {s.sid for s in sched.segments
                     if any(sl[0] == src for sl in s.read_slots)}
        expect = len(consuming) + (1 if src in prog.output_nodes else 0)
        assert rc == expect, (src, rc, expect)
        if consuming:
            assert sched.last_consumer_seg[src] == max(consuming)
    del seg_of


def test_cut_segments_rejects_too_few_devices():
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 4)
    if int(np.max(p.assignment[sorted(prog.program)])) < 1:
        pytest.skip("partition collapsed to one pe")
    with pytest.raises(PlanValidationError, match="PEs"):
        cut_segments(prog, p.assignment, k=1)


# ---------------------------------------------------- executor strictness
def test_interpreter_rejects_pe_wraparound():
    """A plan with more PEs than devices must raise, not silently alias
    PEs via modulo (the old ``% len(devices)`` behaviour)."""
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 4)
    if int(np.max(p.assignment)) < 1:
        pytest.skip("partition collapsed to one pe")
    with pytest.raises(PlanValidationError, match="device_map"):
        execute(prog, p.assignment, [jax.devices()[0]], params, x)
    # an explicitly expanded device list is the sanctioned aliasing path
    devs = [jax.devices()[0]] * 4
    out = execute(prog, p.assignment, devs, params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_mlp(params, x)),
                               rtol=1e-5)


def test_runtime_rejects_pe_wraparound():
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 4)
    if int(np.max(p.assignment)) < 1:
        pytest.skip("partition collapsed to one pe")
    with pytest.raises(PlanValidationError):
        CompiledRuntime(prog, p.assignment, [jax.devices()[0]])


# ------------------------------------------------------- single-device eq
def test_compiled_reference_mode_matches():
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    ref = _mlp(params, x)
    rt = CompiledRuntime(prog, None, None)
    out = rt(params, x)
    assert_matches(out, ref)
    # second call reuses compiled segments and is exactly deterministic
    out2 = rt(params, x)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))
    assert rt.stats.calls == 2
    assert rt.stats.num_segments == 1
    assert rt.stats.transfers == 0


def test_compiled_matches_interpreter_aliased_devices():
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 3)
    devs = [jax.devices()[0]] * 3
    ref = execute(prog, p.assignment, devs, params, x)
    rt = CompiledRuntime(prog, p.assignment, devs)
    out = rt(params, x)
    assert_matches(out, ref)
    assert rt.stats.num_segments >= 1
    # aliased devices: cross-pe reads are no-copy no-ops, so no
    # executed transfers are counted (the static edge count remains)
    assert rt.stats.transfers == 0


def test_compiled_multi_result_pytree_outputs():
    params, x = _example()
    g, prog = trace_cost_graph(_multi, params, x, record=True)
    p = pardnn_partition(g, 2)
    devs = [jax.devices()[0]] * 2
    ref = _multi(params, x)
    out = CompiledRuntime(prog, p.assignment, devs)(params, x)
    assert set(out) == set(ref)
    for key in ref:
        assert_matches(out[key], ref[key])


def test_compiled_without_donation_matches():
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 2)
    devs = [jax.devices()[0]] * 2
    ref = _mlp(params, x)
    out = CompiledRuntime(prog, p.assignment, devs, donate=False)(params, x)
    assert_matches(out, ref)


def test_aliased_devices_do_not_donate_shared_buffers():
    """On an aliased device_map, device_put is a no-copy alias; donating
    a multi-consumer 'transfer' slot would delete a buffer a later
    segment still reads (regression: RuntimeError: Array has been
    deleted)."""
    def f(x):
        a = x + 1.0
        b = a * 2.0
        c = b + a
        d = a + c
        return d

    x = jnp.arange(8.0)
    g, prog = trace_cost_graph(f, x, record=True)
    asn = np.zeros(g.n, dtype=np.int64)
    for i, nid in enumerate(sorted(prog.program)):
        asn[nid] = i % 2          # 'a' becomes a multi-consumer transfer
    dev0 = jax.devices()[0]
    rt = CompiledRuntime(prog, asn, [dev0, dev0])
    for _ in range(2):            # consts/env must survive across calls
        assert_matches(rt(x), f(x))
    # aliased cross-pe reads execute no real copies
    assert rt.stats.transfers == 0
    assert rt.stats.num_transfer_edges > 0


def test_runtime_frees_buffers_below_all_live_baseline():
    """The refcount scheduler must keep peak live bytes strictly below
    the interpreter's all-live total on a chain-structured program."""
    def chain(x):
        for i in range(24):
            x = jnp.tanh(x + float(i))
        return jnp.sum(x)

    x = jnp.ones((64, 64), jnp.float32)
    g, prog = trace_cost_graph(chain, x, record=True)
    # alternate devices down the chain to force many segment boundaries
    a = np.zeros(g.n, dtype=np.int64)
    ids = sorted(prog.program)
    for i, nid in enumerate(ids):
        a[nid] = i % 2
    devs = [jax.devices()[0]] * 2
    rt = CompiledRuntime(prog, a, devs)
    out = rt(x)
    assert_matches(out, chain(x))
    assert rt.stats.freed_buffers > 0
    # all-live: every intermediate held simultaneously (24 x 16 KiB);
    # the runtime holds input + a couple of chain links per device
    all_live = 24 * 64 * 64 * 4
    measured = sum(rt.stats.peak_live_bytes)
    assert measured < all_live, (measured, all_live)


def test_compiled_grad_of_scan_matches_interpreter_and_reference():
    """Regression companion to the tracer's reverse-scan fix: the
    backward pass of a scanned model is itself a reverse scan, and both
    engines must replay it identically to ``jax.grad`` (pre-fix, both
    engines agreed with each other and disagreed with the truth)."""
    params, x = _example()
    grad_fn = jax.grad(_mlp)
    ref = grad_fn(params, x)
    g, prog = trace_cost_graph(grad_fn, params, x, record=True)
    p = pardnn_partition(g, 3)
    devs = [jax.devices()[0]] * 3
    out_i = execute(prog, p.assignment, devs, params, x)
    out_c = CompiledRuntime(prog, p.assignment, devs)(params, x)
    for c, i, r in zip(jax.tree_util.tree_leaves(out_c),
                       jax.tree_util.tree_leaves(out_i),
                       jax.tree_util.tree_leaves(ref)):
        c, i = np.asarray(c), np.asarray(i)
        assert c.dtype == i.dtype and c.shape == i.shape
        # gradient leaves have near-zero elements where segment-fusion
        # rounding differences land above the scalar contract's 1e-8
        np.testing.assert_allclose(c, i, rtol=2e-6, atol=1e-7)
        np.testing.assert_allclose(c, np.asarray(r), rtol=1e-5, atol=1e-7)


# --------------------------------------------------------- dispatch modes
def test_runtime_mode_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_RUNTIME_SYNC", raising=False)
    assert resolve_runtime_mode(None) == "async"
    monkeypatch.setenv("REPRO_RUNTIME_SYNC", "1")
    assert resolve_runtime_mode(None) == "sync"
    # an explicit argument always wins over the env escape hatch
    assert resolve_runtime_mode("async") == "async"
    monkeypatch.setenv("REPRO_RUNTIME_SYNC", "0")
    assert resolve_runtime_mode(None) == "async"
    with pytest.raises(ValueError, match="async"):
        resolve_runtime_mode("eager")


def test_transfer_window_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_TRANSFER_WINDOW_MB", raising=False)
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    assert CompiledRuntime(prog, None, None).transfer_window_bytes \
        == DEFAULT_TRANSFER_WINDOW_BYTES
    monkeypatch.setenv("REPRO_TRANSFER_WINDOW_MB", "2")
    assert CompiledRuntime(prog, None, None).transfer_window_bytes \
        == 2 * 1024 * 1024
    # explicit ctor arg beats the env; 0 disables prefetching entirely
    rt = CompiledRuntime(prog, None, None, transfer_window_bytes=0.0)
    assert rt.transfer_window_bytes == 0.0


def test_sync_async_bit_equal_aliased_devices():
    """Both modes run the same compiled executables on the same values
    in the same order — outputs must be exactly equal, and the stats
    record which mode produced each call's numbers."""
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 3)
    devs = [jax.devices()[0]] * 3
    rt = CompiledRuntime(prog, p.assignment, devs, mode="async")
    out_a = np.asarray(rt(params, x))
    assert rt.stats.mode == "async"
    rt.mode = "sync"                     # mutable between calls
    out_s = np.asarray(rt(params, x))
    assert rt.stats.mode == "sync"
    np.testing.assert_array_equal(out_a, out_s)


def test_env_sync_escape_hatch_recorded(monkeypatch):
    monkeypatch.setenv("REPRO_RUNTIME_SYNC", "1")
    params, x = _example()
    g, prog = trace_cost_graph(_mlp, params, x, record=True)
    p = pardnn_partition(g, 2)
    rt = CompiledRuntime(prog, p.assignment, [jax.devices()[0]] * 2)
    assert rt.mode == "sync"
    assert_matches(rt(params, x), _mlp(params, x))
    assert rt.stats.mode == "sync"
    assert rt.stats.prefetched_transfers == 0
    assert rt.stats.transfer_window_bytes == 0.0


# --------------------------------------------------------- multi-device
def test_compiled_bit_equal_on_four_host_devices():
    run_py("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import pardnn_partition
        from repro.core.executor import execute
        from repro.core.runtime import CompiledRuntime
        from repro.core.tracing import trace_cost_graph
        assert len(jax.devices()) == 4

        def mlp(params, x):
            def layer(h, p):
                w1, w2 = p
                h = jnp.tanh(h @ w1) @ w2
                return h, jnp.sum(h)
            h, sums = jax.lax.scan(layer, x, params)
            return jnp.mean(h ** 2) + jnp.sum(sums)

        key = jax.random.PRNGKey(0)
        L, D, H = 6, 16, 32
        params = (jax.random.normal(key, (L, D, H)) * 0.1,
                  jax.random.normal(key, (L, H, D)) * 0.1)
        x = jax.random.normal(key, (3, D))
        g, prog = trace_cost_graph(mlp, params, x, record=True)
        ref = mlp(params, x)
        for k in (2, 3, 4):
            p = pardnn_partition(g, k)
            devs = jax.devices()[:k]
            out_i = execute(prog, p.assignment, devs, params, x)
            rt = CompiledRuntime(prog, p.assignment, devs)
            out_c = rt(params, x)
            np.testing.assert_allclose(np.asarray(out_c),
                                       np.asarray(out_i),
                                       rtol=2e-6, atol=1e-8, err_msg=str(k))
            np.testing.assert_allclose(np.asarray(out_c), np.asarray(ref),
                                       rtol=2e-6, atol=1e-8, err_msg=str(k))
            # repeated compiled calls are exactly deterministic
            out_c2 = rt(params, x)
            assert np.array_equal(np.asarray(out_c2), np.asarray(out_c)), k
        print('OK')
    """)


def test_facade_runtime_switch_on_four_host_devices():
    run_py("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        import repro

        def multi(params, x):
            def layer(h, p):
                w1, w2 = p
                h = jnp.tanh(h @ w1) @ w2
                return h, jnp.max(h)
            h, maxes = jax.lax.scan(layer, x, params)
            return {'loss': jnp.mean(h ** 2), 'h': h, 'maxes': maxes}

        key = jax.random.PRNGKey(1)
        params = (jax.random.normal(key, (4, 8, 16)) * 0.1,
                  jax.random.normal(key, (4, 16, 8)) * 0.1)
        x = jax.random.normal(key, (2, 8))
        traced = repro.trace(multi, params, x, record=True)
        plan = repro.partition(traced, devices=4)
        ref = multi(params, x)
        out_c = plan.execute(params, x, runtime='compiled')
        out_i = plan.execute(params, x, runtime='interpret')
        for k in ref:
            np.testing.assert_allclose(np.asarray(out_c[k]),
                                       np.asarray(out_i[k]),
                                       rtol=2e-6, atol=1e-8)
            np.testing.assert_allclose(np.asarray(out_c[k]),
                                       np.asarray(ref[k]),
                                       rtol=2e-6, atol=1e-8)
        r = plan.report.runtime
        assert r['num_segments'] >= 1 and r['calls'] == 1
        assert len(r['peak_live_bytes']) == 4
        print('OK segments', r['num_segments'], 'transfers', r['transfers'])
    """)


def test_async_sync_interp_equal_on_four_host_devices():
    """The overlap acceptance triangle on a real 4-device mesh:
    async == sync exactly (same executables, same values), both within
    ulp of the interpreter; prefetch counters move only under async;
    a one-byte window defers every prefetch yet changes nothing."""
    run_py("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import pardnn_partition
        from repro.core.executor import execute
        from repro.core.runtime import CompiledRuntime
        from repro.core.tracing import trace_cost_graph
        assert len(jax.devices()) == 4

        def mlp(params, x):
            def layer(h, p):
                w1, w2 = p
                h = jnp.tanh(h @ w1) @ w2
                return h, jnp.sum(h)
            h, sums = jax.lax.scan(layer, x, params)
            return jnp.mean(h ** 2) + jnp.sum(sums)

        key = jax.random.PRNGKey(0)
        L, D, H = 6, 16, 32
        params = (jax.random.normal(key, (L, D, H)) * 0.1,
                  jax.random.normal(key, (L, H, D)) * 0.1)
        x = jax.random.normal(key, (3, D))
        g, prog = trace_cost_graph(mlp, params, x, record=True)
        p = pardnn_partition(g, 4)
        devs = jax.devices()[:4]
        out_i = execute(prog, p.assignment, devs, params, x)

        rt = CompiledRuntime(prog, p.assignment, devs, mode='async')
        out_a = np.asarray(rt(params, x))
        assert rt.stats.mode == 'async'
        transfers = rt.stats.transfers
        prefetched = rt.stats.prefetched_transfers
        assert prefetched + rt.stats.deferred_transfers >= 0

        rt.mode = 'sync'
        out_s = np.asarray(rt(params, x))
        assert rt.stats.mode == 'sync'
        assert rt.stats.prefetched_transfers == 0     # sync never prefetches
        np.testing.assert_array_equal(out_a, out_s)
        np.testing.assert_allclose(out_a, np.asarray(out_i),
                                   rtol=2e-6, atol=1e-8)
        if transfers:
            assert prefetched > 0, (prefetched, transfers)

        # window too small for any copy: every prefetch deferred to the
        # lazy consumer-time path, outputs still bit-identical
        rt_w = CompiledRuntime(prog, p.assignment, devs, mode='async',
                               transfer_window_bytes=1.0)
        out_w = np.asarray(rt_w(params, x))
        np.testing.assert_array_equal(out_w, out_a)
        assert rt_w.stats.prefetched_transfers == 0
        if transfers:
            assert rt_w.stats.deferred_transfers > 0
            assert rt_w.stats.transfers == transfers  # lazy path covers all
        print('OK transfers', transfers, 'prefetched', prefetched)
    """)


def test_measured_timeline_on_four_host_devices():
    """measure_timeline: per-segment dispatch/ready/done envelope with
    the documented monotonicity, one entry per segment, makespan no
    earlier than the last observed completion; plain calls record
    dispatch stamps only (ready/done need output retention)."""
    run_py("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import pardnn_partition
        from repro.core.runtime import CompiledRuntime
        from repro.core.tracing import trace_cost_graph
        assert len(jax.devices()) == 4

        def mlp(params, x):
            def layer(h, p):
                w1, w2 = p
                h = jnp.tanh(h @ w1) @ w2
                return h, jnp.sum(h)
            h, sums = jax.lax.scan(layer, x, params)
            return jnp.mean(h ** 2) + jnp.sum(sums)

        key = jax.random.PRNGKey(0)
        params = (jax.random.normal(key, (6, 16, 32)) * 0.1,
                  jax.random.normal(key, (6, 32, 16)) * 0.1)
        x = jax.random.normal(key, (3, 16))
        g, prog = trace_cost_graph(mlp, params, x, record=True)
        p = pardnn_partition(g, 4)
        rt = CompiledRuntime(prog, p.assignment, jax.devices()[:4])
        out, tl = rt.measure_timeline(params, x)
        n = rt.stats.num_segments
        assert tl['mode'] == 'async'
        for key_ in ('dispatch_s', 'ready_s', 'done_s', 'transfer_wait_s'):
            assert len(tl[key_]) == n, key_
        d, r, dn, w = (tl['dispatch_s'], tl['ready_s'],
                       tl['done_s'], tl['transfer_wait_s'])
        assert all(b >= a for a, b in zip(d, d[1:]))    # dispatch order
        assert all(b >= a for a, b in zip(dn, dn[1:]))  # observed envelope
        assert all(x_ <= y for x_, y in zip(r, dn))     # ready before done
        assert all(x_ <= y for x_, y in zip(d, dn))     # no time travel
        assert all(x_ >= 0.0 for x_ in w)
        assert tl['makespan_s'] >= dn[-1]
        # the measured value is still the real result
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(mlp(params, x)),
                                   rtol=2e-6, atol=1e-8)
        # plain calls: dispatch stamps only
        rt(params, x)
        assert len(rt.stats.dispatch_seconds) == n
        assert rt.stats.ready_seconds == []
        assert rt.stats.done_seconds == []
        print('OK segments', n)
    """)


# ------------------------------------------------------------ view inputs
#: A stacked weight ``w`` [L, D] on pe 1, scanned twice over ``x``. The
#: first scan's layer-6 slice sits on pe 2 and both layer-7 slices on pe
#: 3, each with its multiply; every other node stays on pe 1. With
#: ``whole=True`` the output is scaled by ``max(w)``, a reader of the
#: whole stack placed on pe 3 beside the layer-7 slices. Multiplies and
#: a max round the same in any placement, so outputs are bit-equal.
VIEWS_SETUP = """
import jax, jax.numpy as jnp, numpy as np
from repro.core.tracing import trace_cost_graph

L, D = 8, 64

def scan_twice(w, x, whole):
    def body(h, wi):
        return h * wi, None
    h, _ = jax.lax.scan(body, x, w)
    h, _ = jax.lax.scan(body, h, w)
    return h * jnp.max(w) if whole else h

def placed(whole=False):
    w = jax.random.normal(jax.random.PRNGKey(0), (L, D))
    x = jax.random.normal(jax.random.PRNGKey(1), (D,))
    g, prog = trace_cost_graph(lambda w, x: scan_twice(w, x, whole), w, x,
                               record=True)
    consumers, _ = prog.liveness()
    w_node = prog.input_nodes[0]
    a = np.ones(g.n, dtype=np.int64)
    seen = set()
    for nid in sorted(prog.program):
        prim, params, ins = prog.program[nid]
        if prim == '__scan_slice__':
            i = params['index']
            pe = {6: None if i in seen else 2, 7: 3}.get(i)
            seen.add(i)
            if pe is not None:
                a[nid] = pe
                for c in consumers[nid]:
                    a[c] = pe
        elif any(inp[0] == 'slot' and inp[1] == w_node for inp in ins):
            a[nid] = 3              # the whole-stack reader
    if whole:
        a[max(prog.program)] = 3    # the final scaling
    # the slices placed off the stack's pe: (index, pe) per slice node
    off = [(prog.program[n][1]['index'], int(a[n])) for n in prog.program
           if prog.program[n][0] == '__scan_slice__' and a[n] != a[w_node]]
    return g, prog, a, w, x, off
"""


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_view_transfers_ship_only_the_slices_on_four_host_devices(mode):
    """A segment whose only readers of a remote stack are slices gets
    the slices, not the stack: outputs bit-equal to one device, bytes
    moved are the slices' (and the carries'), one copy per distinct
    (source, index, device), and a second call compiles nothing."""
    run_py(VIEWS_SETUP + f"""
from jax import monitoring
from repro.core.runtime import CompiledRuntime

g, prog, a, w, x, off = placed()
assert sorted(off) == [(6, 2), (7, 3), (7, 3)], off
one = CompiledRuntime(prog, None, jax.devices()[:1])(w, x)
rt = CompiledRuntime(prog, a, jax.devices(), mode={mode!r})
out = rt(w, x)
assert np.array_equal(np.asarray(out), np.asarray(one))
st = rt.stats
slice_nb = w.nbytes // L
assert st.view_transfers == len(set(off)) == 2, st.view_transfers
assert st.view_transfer_bytes == 2 * slice_nb
# the carry crosses pe 1 -> 2 -> 3 -> 1 -> 3; the stack never moves
assert st.transfer_bytes == 2 * slice_nb + 4 * x.nbytes, st.transfer_bytes
assert st.transfers == 2 + 4
assert st.to_dict()['view_transfers'] == 2

compiles = []
monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: compiles.append(event)
    if event == '/jax/core/compile/backend_compile_duration' else None)
compile_s = st.compile_seconds
out2 = rt(w, x)
assert compiles == [] and rt.stats.compile_seconds == compile_s
assert np.array_equal(np.asarray(out2), np.asarray(one))
assert rt.stats.view_transfers == 2
print('OK')
""")


def test_a_whole_reader_in_the_segment_ships_the_whole_stack():
    """Where another node of the segment reads the stack whole, the
    stack is copied whole, as before; the other device still gets its
    slice alone."""
    run_py(VIEWS_SETUP + """
from repro.core.runtime import CompiledRuntime

g, prog, a, w, x, off = placed(whole=True)
one = CompiledRuntime(prog, None, jax.devices()[:1])(w, x)
rt = CompiledRuntime(prog, a, jax.devices(), mode='sync')
out = rt(w, x)
assert np.array_equal(np.asarray(out), np.asarray(one))
st = rt.stats
assert st.view_transfers == 1                  # layer 6 to pe 2
assert st.transfer_bytes == w.nbytes + w.nbytes // L + 4 * x.nbytes
seg3 = [s for s in rt.schedule.segments if s.device == 3]
assert any((prog.input_nodes[0], 0) in s.inputs for s in seg3)
assert not any(s.views for s in seg3)
print('OK')
""")


def test_views_on_aliased_devices_cut_locally_and_ship_nothing():
    """On one physical device the slices are still cut (the segment
    takes them as arguments), but nothing crosses a device."""
    ns: dict = {}
    exec(VIEWS_SETUP, ns)
    g, prog, a, w, x, off = ns["placed"]()
    assert any(s.views for s in cut_segments(prog, a, 4).segments)
    rt = CompiledRuntime(prog, a, [jax.devices()[0]] * 4)
    out = rt(w, x)
    one = CompiledRuntime(prog, None, jax.devices()[:1])(w, x)
    assert np.array_equal(np.asarray(out), np.asarray(one))
    assert rt.stats.view_transfers == 0 and rt.stats.transfers == 0
