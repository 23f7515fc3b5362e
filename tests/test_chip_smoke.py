"""``chip_smoke.py``'s phases at ``reduced()`` size on the CPU.

The smoke's ``main()`` refuses anything but a TPU, so these tests import
its phase functions and drive them on host devices: the control flow,
the checks and the reference comparisons run here at no chip cost. The
four-chip placement phase runs in a child process that sees 4 forced
host devices.
"""
import dataclasses

import jax
import pytest

import chip_smoke
from repro.conformance.subproc import repo_src_path, run_py
from repro.configs import get_config, reduced


def _cfg(dtype="float32"):
    return dataclasses.replace(reduced(get_config("granite-8b"), layers=2),
                               dtype=dtype)


def test_main_refuses_a_cpu(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_train_phase(dtype):
    res = chip_smoke.train_phase(_cfg(dtype), device=jax.devices()[0],
                                 batch=2, seq=32, memory=1e9)
    assert len(res["losses"]) == chip_smoke.TRAIN_STEPS
    assert res["loss_rel"] <= chip_smoke.LOSS_RTOL
    assert res["worst_grad_rel"] <= chip_smoke.GRAD_RTOL
    assert res["segments"] == 1
    # the runtime's count stays inside the Step-2 certificate
    assert 0 < res["runtime_peak_bytes"] <= res["certificate_bytes"]


def test_serve_phase_matches_reference_tokens():
    res = chip_smoke.serve_phase(_cfg(), device=jax.devices()[0],
                                 prompt_lens=(5, 17, 9, 12), new_tokens=4,
                                 memory=1e9)
    assert res["tokens"] == 16
    assert res["token_agreement"] == 1.0
    assert res["worst_gap"] == 0.0


def test_train_phase_fails_on_an_infeasible_cap():
    with pytest.raises(chip_smoke.SmokeFailure, match="no placement"):
        chip_smoke.train_phase(_cfg(), device=jax.devices()[0], batch=2,
                               seq=32, memory=1e3)


def test_placement_phase_on_four_host_devices():
    root = repo_src_path().rsplit("/src", 1)[0]
    out = run_py(f"""
        import sys
        sys.path.insert(0, {root!r})
        import jax
        import chip_smoke
        from repro.configs import get_config, reduced
        cfg = reduced(get_config("granite-8b"), layers=4)
        # at this size the runtime's in-flight transfer window outweighs
        # the model, so the verifier certifies no cap below the one-device
        # peak: the cap here leaves the placement to Step-1
        res = chip_smoke.placement_phase(cfg, devices=jax.devices()[:4],
                                         batch=2, seq=32, cap_fraction=1.5)
        assert res["async_equals_sync"]
        assert min(res["nodes_per_chip"]) > 0, res["nodes_per_chip"]
        print("PLACED", res["nodes_per_chip"])
    """, devices=4)
    assert "PLACED" in out
    assert "async == sync bit for bit: True" in out
