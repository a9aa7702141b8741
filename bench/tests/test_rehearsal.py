"""A tiny cell driven on the CPU through the functions ``run.py`` calls.

Everything of a run but the look for the chip: a tiny configuration's
plan, weights from the seed, the program's engine on the real clock, the
metric readers and the comparison with the reference. Then the same with
the served path broken underneath, where ``correct`` has to come out false,
and the control and the planted fault of ``bench/check.py``, which have to
be judged not correct. ``main()`` itself refuses the CPU, and the script
alone, without the program beside it, fails.
"""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import deploy, kinds
from bench import traffic as T

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "tests" / "data"
DEV = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = _run_module()
LIMITS = kinds.load("cnn").LIMITS


def execute(mix_name: str, *, seconds: float = 1.5, trace: int = 0,
            control: int = 0, seed: int = 2 ** 33 + 11) -> dict:
    bench = RUN.load_benchmark()
    cell = {"name": "c10-chaos" if "drill" in mix_name else "c10-poisson",
            "config": "tiny", "traffic": mix_name, "chips": 1}
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace,
                                 sweep="", control=control)
    return RUN.execute(args, bench, cell, deploy.load_config(
        DATA / "tiny.json"), T.load_mix(DATA / f"{mix_name}.json"),
        dict(DEV))


@pytest.fixture(scope="module")
def clean():
    return execute("tiny-mix", control=1)


def test_tiny_cell_is_correct_and_reports_its_metrics(clean):
    assert clean["correct"], clean["compared"]
    assert clean["failed"] == 0 and clean["attempted"] > 0
    m = clean["metrics"]
    assert set(m) == {"served_rps", "p50_ms", "quorum_share", "setup_s"}
    assert all(np.isfinite(v["value"]) and v["value"] > 0
               for v in m.values())
    assert list(clean)[-1] == "compared"
    json.dumps(clean, allow_nan=False)


def test_control_fails_the_limit(clean):
    # the same run put the reference in bfloat16 in the program's place
    assert clean["control"]["correct"] is False
    assert clean["control"]["rel_gap_p90"] > LIMITS["rel_gap_p90"]
    assert clean["compared"]["rel_gap_p90"]["value"] < LIMITS["rel_gap_p90"]


def test_planted_fault_in_a_few_answers_fails_the_max(clean):
    # one answer in 16 merged as if one of its arrived slots had timed out
    assert clean["fault"]["correct"] is False
    assert clean["fault"]["max_rel_err"] > LIMITS["max_rel_err"]
    assert clean["fault"]["rel_gap_p90"] <= LIMITS["rel_gap_p90"]
    assert clean["compared"]["max_rel_err"]["value"] < LIMITS["max_rel_err"]


def _break(monkeypatch, how: str) -> None:
    from repro.kernels import ops

    real = ops.quorum_aggregate
    calls = [0]

    def broken(portions, weights, bias, mask, scales=None, **kw):
        if how == "altered":          # an answer altered where it is made
            return real(portions, weights, bias, mask, scales, **kw) + 0.5
        if how == "one_in_16":        # one batch in 16: its first row
            calls[0] += 1             # merged without one arrived slot
            live = np.flatnonzero(np.abs(np.asarray(portions[:, 0])).sum(1))
            if calls[0] % 16 == 0 and live.size:
                portions = portions.at[live[0], 0].set(0.0)
            return real(portions, weights, bias, mask, scales, **kw)
        half = portions.shape[1] // 2   # half the batch left out
        kept = portions.at[:, half:].set(0.0) if half else portions
        return real(kept, weights, bias, mask, scales, **kw)

    monkeypatch.setattr(ops, "quorum_aggregate", broken)


@pytest.mark.parametrize("how, caught_by", [
    ("altered", "rel_gap_p90"), ("half_batch", "rel_gap_p90"),
    ("one_in_16", "max_rel_err")])
def test_broken_path_is_not_correct(monkeypatch, how, caught_by):
    _break(monkeypatch, how)
    res = execute("tiny-mix", seed=5)
    assert not res["correct"]
    x = res["compared"][caught_by]
    assert x["value"] > x["limit"], res["compared"]


def test_drill_repairs_and_answers_after_a_migration_are_checked():
    res = execute("tiny-drill", seconds=2.0, trace=1)
    assert res["correct"], res["compared"]
    assert res["compared"]["migrated_compared"]["value"] >= 1
    assert "busy_s" in res["device"] and res["device"]["window_s"] > 0


def test_main_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "c10-poisson", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "refusing" in p.stderr
    assert not p.stdout.strip()


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "c10-poisson", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "repro" in p.stderr
    assert not p.stdout.strip()
