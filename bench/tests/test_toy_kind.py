"""A second kind of configuration is only files of its own: the toy kind
(``bench/tests/kinds/toy.py``, MLP students over vectors, served through
``Ensemble`` -> ``server_from_ensemble``) and its configuration, driven
through ``run.execute`` as a cell of the CNN kind is. Its answers are
correct against its own reference, its control and planted fault are not,
and with the merge broken underneath the run is not correct."""
import importlib.util
import pathlib
import sys
import types

import numpy as np
import pytest

from bench import deploy, kinds
from bench import traffic as T

TESTS = pathlib.Path(__file__).resolve().parent
ROOT = TESTS.parents[1]
DEV = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = _run_module()


@pytest.fixture
def toy(monkeypatch):
    """The toy kind, registered under the name ``kinds.load`` looks up."""
    spec = importlib.util.spec_from_file_location(
        "bench_kind_toy", TESTS / "kinds" / "toy.py")
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "bench_kind_toy", mod)
    spec.loader.exec_module(mod)
    assert kinds.load("toy") is mod
    return mod


def execute(*, control: int = 0, seed: int = 2 ** 33 + 5) -> dict:
    cell = {"name": "c10-poisson", "config": "toy", "traffic": "tiny-mix",
            "chips": 1}
    args = types.SimpleNamespace(seed=seed, seconds=1.5, trace=0, sweep="",
                                 control=control)
    return RUN.execute(args, RUN.load_benchmark(), cell,
                       deploy.load_config(TESTS / "data" / "toy.json"),
                       T.load_mix(TESTS / "data" / "tiny-mix.json"),
                       dict(DEV))


def test_toy_cell_is_correct_and_its_control_and_fault_are_not(toy):
    cfg = deploy.load_config(TESTS / "data" / "toy.json")
    assert deploy.kind_of(cfg) is toy
    res = execute(control=1)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    v = res["compared"]
    assert v["rel_gap_p90"]["limit"] == toy.LIMITS["rel_gap_p90"]
    assert v["requests_compared"]["value"] >= 50
    assert v["degraded_compared"]["value"] >= 1
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in res["metrics"].values())
    # the bfloat16 reference in the program's place, and one answer in 16
    # merged without one of its arrived slots
    assert res["control"]["correct"] is False
    assert res["control"]["rel_gap_p90"] > toy.LIMITS["rel_gap_p90"]
    assert res["fault"]["correct"] is False
    assert res["fault"]["max_rel_err"] > toy.LIMITS["max_rel_err"]


def test_toy_cell_with_its_merge_broken_is_not_correct(toy, monkeypatch):
    from repro.kernels import ops
    real = ops.quorum_aggregate

    def altered(portions, weights, bias, mask, scales=None, **kw):
        return real(portions, weights, bias, mask, scales, **kw) + 0.5

    monkeypatch.setattr(ops, "quorum_aggregate", altered)
    res = execute(seed=9)
    assert not res["correct"]
    x = res["compared"]["rel_gap_p90"]
    assert x["value"] > x["limit"], res["compared"]
