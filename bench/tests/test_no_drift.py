"""The CNN kind makes what the harness made before it moved behind the
kind seam: for each recorded seed, the same weights, image pool, arrivals
and kept sample, bit for bit, and the same limits."""
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import deploy, kinds, window
from bench import traffic as T

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = json.loads((DATA / "tiny-digests.json").read_text())
CNN = kinds.load("cnn")


def _digest(arrays) -> str:
    m = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        m.update(str(a.dtype).encode())
        m.update(str(a.shape).encode())
        m.update(a.tobytes())
    return m.hexdigest()


@pytest.mark.parametrize("seed", sorted(RECORDED["seeds"]))
def test_seed_makes_what_it_made_before(seed):
    want = RECORDED["seeds"][seed]
    cfg = deploy.load_config(DATA / "tiny.json")
    assert deploy.kind_of(cfg) is CNN
    times, sizes = T.arrivals(T.load_mix(DATA / "tiny-mix.json"), int(seed),
                              RECORDED["seconds"])
    got = {"weights": _digest(jax.tree.leaves(
               CNN.make_weights(cfg, int(seed)))),
           "pool": _digest([CNN.image_pool(cfg, int(seed))]),
           "arrivals": _digest([times, sizes]),
           "sample": _digest([np.asarray(
               sorted(window.sample(int(seed), len(times))), np.int64)]),
           "due": len(times)}
    assert got == want


def test_cnn_limits_are_the_ones_set_from_its_readings():
    assert CNN.LIMITS == {"rel_gap_p90": 4e-3, "max_rel_err": 5e-2}


def test_requests_take_their_own_pool_rows():
    pool = np.arange(10.0)[:, None]
    inp = kinds.PoolInputs(pool, [2, 1, 3, 2, 2, 4])
    got = [inp.request(rid, n)[:, 0].tolist()
           for rid, n in enumerate([2, 1, 3, 2, 2, 4])]
    # past the pool's end a request starts again from its first row
    assert got == [[0, 1], [2], [3, 4, 5], [6, 7], [8, 9], [0, 1, 2, 3]]
    assert inp.warm(3, 4)[:, 0].tolist() == [4, 5, 6]
