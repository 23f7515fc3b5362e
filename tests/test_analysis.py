"""Static plan verification (repro/analysis).

Covers the verifier's contract end to end: a clean plan over a real
trace verifies clean; every registered mutation class (use-after-free,
double-free, illegal donation, dropped transfer, transfer cycle,
cross-wired order, cap overflow, placement hole, refcount drift) is
caught with its expected RPxxx code; the facade refuses to save or
execute plans carrying error diagnostics (RP107); exceptions carry
stable codes; and the CLI exits with the documented status codes.
"""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.analysis import CODES, Diagnostic
from repro.analysis.__main__ import main as cli_main
from repro.analysis.mutate import MUTATIONS, apply_mutation, make_case
from repro.analysis.synth import random_assignment, random_program
from repro.core.errors import (RP100_PLAN_INVALID, RP105_PROFILE_INVALID,
                               RP107_VERIFICATION_FAILED,
                               PlanValidationError, ProfileValidationError)


def _mlp(params, x):
    def layer(h, p):
        w1, w2 = p
        h = jnp.tanh(h @ w1) @ w2
        return h, jnp.sum(h)
    h, sums = jax.lax.scan(layer, x, params)
    return jnp.mean(h ** 2) + jnp.sum(sums)


def _example():
    key = jax.random.PRNGKey(0)
    L, D, H = 3, 8, 16
    params = (jax.random.normal(key, (L, D, H)) * 0.1,
              jax.random.normal(key, (L, H, D)) * 0.1)
    x = jax.random.normal(key, (2, D))
    return params, x


@pytest.fixture(scope="module")
def traced():
    params, x = _example()
    return repro.trace(_mlp, params, x, record=True), params, x


@pytest.fixture(scope="module")
def plan2(traced):
    t, _, _ = traced
    return repro.partition(t, devices=2)


# ------------------------------------------------------------ clean path
def test_clean_plan_verifies_clean(plan2):
    rep = plan2.verify()
    assert not rep.has_errors(), rep.render()
    for name in ("placement", "structure", "deadlock", "liveness",
                 "memory", "overlap", "lint"):
        assert name in rep.passes_run, rep.passes_run
    # the report is cached per (trace, assignment, k)
    assert plan2.verify() is rep
    # and lands in the serializable plan report
    assert plan2.report.diagnostics["counts"]["error"] == 0


def test_verify_without_program_is_structural_only():
    params, x = _example()
    t = repro.trace(_mlp, params, x)            # record=False: no program
    plan = repro.partition(t, devices=2)
    rep = plan.verify()
    assert not rep.has_errors()
    assert rep.passes_run[-1] == "placement"
    assert "liveness" in rep.skipped


def test_random_clean_programs_verify_clean():
    # the property-test core, hypothesis-free (always runs in tier-1):
    # cut_segments of a random placed program agrees with the analyzer
    for seed in range(25):
        rng = np.random.default_rng(1000 + seed)
        prog = random_program(rng, n_ops=8 + seed % 12,
                              p_multi=0.3)
        k = 1 + seed % 4
        case = make_case(prog, random_assignment(rng, prog, k), k)
        rep = case.analyze()
        assert not rep.has_errors(), (seed, rep.render())


# ------------------------------------------------------ mutation harness
def test_every_mutation_code_is_registered():
    assert len(MUTATIONS) >= 5
    for m in MUTATIONS.values():
        assert m.expect_code in CODES, m.name


def test_required_corruption_classes_present():
    # the acceptance floor: these five classes must exist with exactly
    # these codes (docs/ARCHITECTURE.md "Static plan verification")
    required = {"use_after_free": "RP001", "double_donation": "RP003",
                "transfer_cycle": "RP011", "cap_overflow": "RP020",
                "placement_hole": "RP032"}
    for name, code in required.items():
        assert MUTATIONS[name].expect_code == code


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_mutation_caught_with_expected_code(name, traced, plan2):
    t, _, _ = traced
    mut = MUTATIONS[name]
    applied = False
    for seed in range(40):
        rng = np.random.default_rng(seed)
        if name in ("cap_overflow", "async_cap_overflow"):
            # needs byte annotations: use the real trace's cost graph
            case = make_case(t.program, plan2.assignment, plan2.k,
                             graph=t.graph)
        else:
            prog = random_program(rng, n_ops=16, p_multi=0.3)
            case = make_case(prog, random_assignment(rng, prog, 3), 3)
        pre = case.analyze()
        assert not pre.has_errors(), pre.render()
        if not apply_mutation(name, case, rng):
            continue
        applied = True
        rep = case.analyze()
        assert rep.has_errors(), (name, seed)
        assert mut.expect_code in rep.codes(), (name, seed, rep.render())
        break
    assert applied, f"mutation {name} never applied in 40 seeds"


# ------------------------------------------------------- facade wiring
def test_save_refuses_plan_with_error_diagnostics(tmp_path, traced):
    t, _, _ = traced
    plan = repro.partition(t, devices=2)
    plan.assignment[-1] = 99                     # placement hole
    path = str(tmp_path / "bad.plan.json")
    with pytest.raises(PlanValidationError) as ei:
        plan.save(path)
    assert ei.value.code == RP107_VERIFICATION_FAILED
    assert str(ei.value).startswith("[RP107]")
    assert "RP032" in str(ei.value)
    assert not os.path.exists(path)              # nothing was written


def test_execute_refuses_plan_with_error_diagnostics(traced):
    t, params, x = traced
    plan = repro.partition(t, devices=2)
    plan.assignment[-1] = -3
    with pytest.raises(PlanValidationError) as ei:
        plan.execute(params, x, device_map=[0, 0])
    assert ei.value.code == RP107_VERIFICATION_FAILED


def test_verify_cache_invalidated_by_assignment_change(traced):
    t, _, _ = traced
    plan = repro.partition(t, devices=2)
    clean = plan.verify()
    assert not clean.has_errors()
    plan.assignment[0] = 5
    dirty = plan.verify()
    assert dirty is not clean and dirty.has_errors()


def test_diagnostics_summary_roundtrips_with_plan(tmp_path, traced):
    t, _, _ = traced
    plan = repro.partition(t, devices=2)
    path = plan.save(str(tmp_path / "p.plan.json"))
    loaded = repro.PartitionPlan.load(path)
    diags = loaded.report.diagnostics
    assert diags["counts"]["error"] == 0
    assert "placement" in diags["passes_run"]
    json.dumps(diags)                            # JSON-clean end to end


# -------------------------------------------------------- error codes
def test_exceptions_carry_stable_codes():
    e = PlanValidationError("boom")
    assert e.code == RP100_PLAN_INVALID
    assert str(e).startswith("[RP100]")
    p = ProfileValidationError("boom")
    assert p.code == RP105_PROFILE_INVALID
    assert str(p).startswith("[RP105]")
    # explicit codes override the default and survive as attributes
    e2 = PlanValidationError("x", code=RP107_VERIFICATION_FAILED)
    assert e2.code == RP107_VERIFICATION_FAILED


def test_diagnostic_rejects_unknown_code_and_severity():
    with pytest.raises(ValueError):
        Diagnostic(code="RP999", severity="error", message="x")
    with pytest.raises(ValueError):
        Diagnostic(code="RP001", severity="fatal", message="x")


# --------------------------------------------------------------- CLI
def test_cli_exit_codes(tmp_path, traced):
    t, _, _ = traced
    plan = repro.partition(t, devices=2)
    path = plan.save(str(tmp_path / "p.plan.json"))

    # clean artifact, structural-only (no --arch): exit 0
    assert cli_main([path]) == 0

    # unloadable artifact: exit 2
    assert cli_main([str(tmp_path / "missing.plan.json")]) == 2

    # corrupt-but-consistent artifact (placement hole, sha re-stamped):
    # the verifier — not the loader — must catch it, exit 1
    npz = str(tmp_path / "p.plan.npz")
    with np.load(npz) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["assignment"] = arrays["assignment"].copy()
    arrays["assignment"][0] = -1
    with open(npz, "wb") as f:
        np.savez(f, **arrays)
    with open(path) as f:
        header = json.load(f)
    header["assignment_sha256"] = hashlib.sha256(
        np.ascontiguousarray(arrays["assignment"],
                             dtype=np.int64).tobytes()).hexdigest()
    with open(path, "w") as f:
        json.dump(header, f)
    out = str(tmp_path / "rep.json")
    assert cli_main([path, "--json", out]) == 1
    with open(out) as f:
        rep = json.load(f)
    assert any(d["code"] == "RP032" for d in rep["diagnostics"])


# ------------------------------------------------------------ view inputs
@pytest.fixture(scope="module")
def views():
    """The runtime tests' placed double scan: a stack on pe 1 whose
    layer-6 slice is read on pe 2 and layer-7 slices on pe 3."""
    from test_runtime import VIEWS_SETUP
    ns: dict = {}
    exec(VIEWS_SETUP, ns)
    return ns


def test_a_program_with_view_inputs_verifies_clean(views):
    from repro.analysis import analyze
    from repro.core.segments import cut_segments
    g, prog, a, w, x, off = views["placed"]()
    assert sum(len(s.views) for s in cut_segments(prog, a, 4).segments) == 2
    rep = analyze(prog, a, 4, graph=g)
    assert not rep.diagnostics, rep.render()
    for name in ("structure", "deadlock", "liveness", "memory", "overlap"):
        assert name in rep.passes_run


def test_the_certificate_charges_a_view_at_the_slice_bytes(views):
    """On the reader's pe the certificate holds the slice, the carry in
    and the product out: three slices' bytes, never the stack's eight.
    A whole reader beside the layer-7 slices brings the stack back."""
    from repro.analysis import certified_peaks
    g, prog, a, w, x, off = views["placed"]()
    slice_nb = w.nbytes // views["L"]
    cert = certified_peaks(prog, a, 4, g)
    assert cert[2] == 3 * slice_nb and cert[3] == 4 * slice_nb, cert
    g, prog, a, w, x, off = views["placed"](whole=True)
    assert certified_peaks(prog, a, 4, g)[3] > w.nbytes


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_the_lazy_certificate_bounds_the_runtime_with_views(mode):
    from repro.conformance import run_py
    from test_runtime import VIEWS_SETUP
    run_py(VIEWS_SETUP + f"""
from repro.analysis.passes import AnalysisContext, abstract_interpret
from repro.core.runtime import CompiledRuntime

for whole in (False, True):
    g, prog, a, w, x, off = placed(whole)
    rt = CompiledRuntime(prog, a, jax.devices(), mode={mode!r})
    rt(w, x)
    ctx = AnalysisContext(prog=prog, assignment=a, k=4,
                          schedule=rt.schedule, graph=g)
    cert = abstract_interpret(ctx).cert_peaks
    peaks = np.asarray(rt.stats.peak_live_bytes)
    assert np.all(cert >= peaks), (whole, cert, peaks)
print('OK')
""")
