"""What one kind of configuration is, behind one seam.

A configuration file may name its ``"kind"``; without one it is ``"cnn"``.
The harness loads ``bench/kinds/<kind>.py`` by file name, the way
``measure.reader`` loads a metric's reader, and asks it only for what
differs from one kind to another:

- ``build(cfg, seed) -> deploy.Deployment``: the weights from the seed on
  the device, and the program's server built from them through the
  program's normal path;
- ``inputs(cfg, seed, sizes) -> Inputs``: one request's input array by its
  id and size (``request``), and arrays of any row count for warm-up and
  filler rows (``warm``);
- ``reference(dep, xs, masks, *, control=False)``: the plain reference's
  logits, one (rows, C) array per request input in ``xs``, each under its
  (K,) mask of arrived slots, at the precision the configuration states,
  or with ``control`` one step below it;
- ``LIMITS``: the limits of ``rel_gap_p90`` and ``max_rel_err``, set from
  this kind's own readings of the program and of its control;
- ``slot_cost(cfg, arch, width, rows)`` and ``merge_cost(cfg, arrived,
  rows, dk)``: (operations, least bytes) of one slot's forward over
  ``rows`` rows and of one quorum merge, from shapes alone.

The plan, the engine, the failure draws, the traffic and the comparison of
answers are the same for every kind and stay in the generic modules.
"""
from __future__ import annotations

import importlib.util
import pathlib
import sys
from typing import Sequence

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str):
    """The module ``bench/kinds/<name>.py``, loaded once as
    ``bench_kind_<name>``."""
    key = f"bench_kind_{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = HERE / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no kind {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


class PoolInputs:
    """Requests that are rows of one pool made from the seed. Request ``rid``
    takes the rows after those of request ``rid - 1``, from the pool's
    start again where they would run past its end, so every request has
    its own rows and the reference finds them by the request's id."""

    def __init__(self, pool: np.ndarray, sizes: Sequence[int]):
        self.pool = pool
        self.offsets = []
        end = 0
        for n in np.asarray(sizes).tolist():
            off = end if end + n <= len(pool) else 0
            self.offsets.append(off)
            end = off + n

    def request(self, rid: int, size: int) -> np.ndarray:
        off = self.offsets[rid]
        return self.pool[off:off + size]

    def warm(self, rows: int, at: int = 0) -> np.ndarray:
        return self.pool[at:at + rows]
