"""``chip_smoke.py``'s phases, run small on the CPU.

The smoke run itself needs a TPU; these tests drive the same phase
functions ``main()`` calls at a tiny teacher and a few requests, with the
kernels in interpret mode, so the control flow and the logit checks are
exercised on every change. They also pin that ``main()`` refuses the CPU
and that the script fails outside a checkout.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def ens(smoke):
    return smoke.build_ensemble(0, teacher_depth=10, teacher_widen=1,
                                zoo=["wrn-10-1"], n_devices=4,
                                teacher_steps=1, student_steps=1, batch=8)


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_phase_a_serves_under_chaos_and_matches_reference(smoke, ens,
                                                          quantize):
    out = smoke.serve_ensemble(ens, quantize=quantize, n_requests=24, seed=0)
    assert out["requests"] >= 1 and out["repairs"]
    assert out["degraded_compared"] > 0
    assert out["not_comparable"] == 0


def test_phase_b_runs_fused_and_coded_steps(smoke, ens):
    out = smoke.serve_coded(ens.ir, feat=ens.ir.M, hidden=ens.ir.M, rows=4,
                            n_requests=2, seed=0)
    assert set(out) == {"fused fp32", "fused int8", "output-coded",
                        "compute-coded"}
    assert any(m.startswith("coded(") for m in out["output-coded"]["modes"])
    assert any(m.startswith("coded_compute(")
               for m in out["compute-coded"]["modes"])
    for name in ("output-coded", "compute-coded"):
        assert all(out[name]["arrived_under_failure"])     # decoded


def test_ensemble_mask_follows_partitions_across_migrations(smoke):
    import numpy as np
    base = np.eye(3, dtype=bool)
    arrived = np.array([True, False, True])
    np.testing.assert_array_equal(
        smoke.ensemble_mask(base, base, frozenset(), arrived), arrived)
    # slots permuted by a replan, slot 1 zeroed (no stored weights)
    moved = base[[2, 0, 1]]
    np.testing.assert_array_equal(
        smoke.ensemble_mask(moved, moved, frozenset(), arrived), arrived)
    np.testing.assert_array_equal(         # slot 1 serves partition 0
        smoke.ensemble_mask(base, moved, frozenset({1}), [1, 1, 1]),
        [False, True, True])
    # an unknown partition contributes nothing; a doubled one has no mask
    other = np.array([[1, 1, 0], [0, 0, 1]], bool)
    np.testing.assert_array_equal(
        smoke.ensemble_mask(base, other, frozenset(), [1, 1]),
        [False, False, True])
    assert smoke.ensemble_mask(base, base[[0, 0]], frozenset(),
                               [1, 1]) is None


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
