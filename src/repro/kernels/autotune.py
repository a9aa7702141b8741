"""Block-size autotuner for the serving Pallas kernels.

The serving kernels (`quorum_aggregate`, `coded_decode`, `dequant_matmul`)
take static block sizes that were picked once for a TPU-v5e-ish sweet spot.
The right block depends on the deployed shapes (portion width, batch
bucket, share count) and on the backend actually running the kernel — so
this module searches the block space with the same median-of-reps timing
the microbench harness uses and persists the winners in a shape-keyed
tuning table that ``repro.kernels.ops`` consults on every call.

Table contract
--------------
A table is a flat JSON object mapping ``"<kernel>|<d0>x<d1>x…|<dtype>"``
keys to block-parameter dicts, e.g.::

    {"dequant_matmul|256x64x512|int8": {"block_batch": 64, "block_n": 128},
     "quorum_aggregate|4x256x16x10|float32": {"block_batch": 256}}

The shape component is the kernel-specific *problem* shape (documented per
``key_*`` helper below), not any one operand's shape. Lookup is exact-match:
an unknown shape falls back to the kernel's built-in defaults, so a stale or
missing table can never change numerics — only speed.

The in-process table is loaded once from ``REPRO_TUNING_TABLE`` (env var)
when it is set, and is empty otherwise — no file is read that the caller
did not name; ``set_table`` / ``reset`` override it for tests and
benchmarks.

Search discipline
-----------------
The default block sizes are always in the candidate set, and a non-default
winner is recorded only when it beats the default by a hysteresis margin
(5%) — timing noise must not regress a shape below today's behaviour, which
is what the ``bench_roofline`` gate verifies.
"""
from __future__ import annotations

import json
import os
import pathlib
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

# today's built-in defaults — the fallbacks ops.py applies on table miss,
# and the baselines the hysteresis margin protects
DEFAULTS: Dict[str, Dict[str, int]] = {
    "quorum_aggregate": {"block_batch": 128},
    "coded_decode": {"block_batch": 128},
    "dequant_matmul": {"block_batch": 128, "block_n": 256},
}

# candidate grids (the default is always a member)
CANDIDATES: Dict[str, Dict[str, Tuple[int, ...]]] = {
    "quorum_aggregate": {"block_batch": (32, 64, 128, 256)},
    "coded_decode": {"block_batch": (32, 64, 128, 256)},
    "dequant_matmul": {"block_batch": (32, 64, 128, 256),
                       "block_n": (64, 128, 256, 512)},
}

# a non-default config must win by this factor to be recorded
HYSTERESIS = 1.05


def table_key(kernel: str, shape: Sequence[int], dtype) -> str:
    """The flat-JSON key: ``kernel|d0xd1x…|dtype``."""
    return f"{kernel}|{'x'.join(str(int(d)) for d in shape)}|{np.dtype(dtype).name}"


class TuningTable:
    """Shape-keyed block-size table with JSON persistence."""

    def __init__(self, entries: Optional[Dict[str, Dict[str, int]]] = None):
        self.entries: Dict[str, Dict[str, int]] = dict(entries or {})

    def get(self, kernel: str, shape: Sequence[int], dtype
            ) -> Optional[Dict[str, int]]:
        return self.entries.get(table_key(kernel, shape, dtype))

    def put(self, kernel: str, shape: Sequence[int], dtype,
            blocks: Dict[str, int]) -> None:
        self.entries[table_key(kernel, shape, dtype)] = \
            {k: int(v) for k, v in blocks.items()}

    def save(self, path) -> None:
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.entries, indent=1, sort_keys=True))

    @classmethod
    def load(cls, path) -> "TuningTable":
        return cls(json.loads(pathlib.Path(path).read_text()))

    def __len__(self) -> int:
        return len(self.entries)


_table: Optional[TuningTable] = None


def active_table() -> TuningTable:
    """The process-wide table ops.py consults: the table at
    ``REPRO_TUNING_TABLE`` when set, else empty (built-in defaults)."""
    global _table
    if _table is None:
        path = os.environ.get("REPRO_TUNING_TABLE")
        try:
            _table = TuningTable.load(path) if path else TuningTable()
        except (OSError, ValueError):
            _table = TuningTable()
    return _table


def set_table(table: Optional[TuningTable]) -> None:
    """Install (or with ``None`` drop back to lazy-load) the active table."""
    global _table
    _table = table


def reset() -> None:
    """Forget the cached table so the next lookup reloads from disk/env."""
    set_table(None)


def resolve(kernel: str, shape: Sequence[int], dtype,
            overrides: Optional[Dict[str, Optional[int]]] = None
            ) -> Dict[str, int]:
    """The block sizes a call should use: caller overrides (non-``None``
    values) beat the tuning table, which beats the built-in defaults."""
    blocks = dict(DEFAULTS[kernel])
    tuned = active_table().get(kernel, shape, dtype)
    if tuned:
        blocks.update({k: v for k, v in tuned.items() if k in blocks})
    if overrides:
        blocks.update({k: int(v) for k, v in overrides.items()
                       if v is not None and k in blocks})
    return blocks


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------

def _configs(kernel: str) -> Tuple[Dict[str, int], ...]:
    """Cartesian candidate grid, default config first."""
    grids = CANDIDATES[kernel]
    names = sorted(grids)
    out = [dict(DEFAULTS[kernel])]
    stack = [{}]
    for n in names:
        stack = [dict(c, **{n: v}) for c in stack for v in grids[n]]
    for c in stack:
        if c != out[0]:
            out.append(c)
    return tuple(out)


def tune_call(kernel: str, make_call: Callable[[Dict[str, int]], Callable],
              *, repeats: int = 5) -> Tuple[Dict[str, int], Dict[str, float]]:
    """Time ``make_call(blocks)()`` for every candidate config and pick the
    winner under the hysteresis rule: the default keeps its seat unless a
    challenger is >5% faster. Returns ``(blocks, {config_key: seconds})``."""
    from repro.launch.microbench import time_callable
    timings: Dict[str, float] = {}
    best_blocks, best_t, default_t = None, np.inf, np.inf
    for blocks in _configs(kernel):
        fn = make_call(blocks)
        t = time_callable(fn, repeats=repeats)
        key = ",".join(f"{k}={v}" for k, v in sorted(blocks.items()))
        timings[key] = t
        if blocks == DEFAULTS[kernel]:
            default_t = t
        if t < best_t:
            best_blocks, best_t = blocks, t
    if best_blocks != DEFAULTS[kernel] and best_t * HYSTERESIS > default_t:
        best_blocks = dict(DEFAULTS[kernel])
    return best_blocks, timings


# per-kernel problem-shape keys (what ops.py keys its lookups on)

def key_quorum_aggregate(portions, weights) -> Tuple[Tuple[int, ...], object]:
    """(K, B, Dk, C) + weights dtype."""
    K, B, Dk = portions.shape
    return (K, B, Dk, int(weights.shape[-1])), weights.dtype


def key_coded_decode(shares, dec) -> Tuple[Tuple[int, ...], object]:
    """(B, R, K, F) + shares dtype."""
    R, B, F = shares.shape
    return (B, R, int(dec.shape[0]), F), shares.dtype


def key_dequant_matmul(x, q) -> Tuple[Tuple[int, ...], object]:
    """(B, D, N) + weight dtype."""
    B, D = x.shape
    return (B, D, int(q.shape[-1])), q.dtype


def tune_quorum_aggregate(table: TuningTable, portions, weights, bias, mask,
                          scales=None, *, repeats: int = 5
                          ) -> Dict[str, float]:
    """Search block_batch for one quorum-aggregate shape; record the winner."""
    from repro.kernels import ops as K
    shape, dtype = key_quorum_aggregate(portions, weights)

    def make(blocks):
        return lambda: K.quorum_aggregate(
            portions, weights, bias, mask, scales,
            block_batch=blocks["block_batch"])
    blocks, timings = tune_call("quorum_aggregate", make, repeats=repeats)
    table.put("quorum_aggregate", shape, dtype, blocks)
    return timings


def tune_coded_decode(table: TuningTable, shares, dec, mask, scales=None, *,
                      repeats: int = 5) -> Dict[str, float]:
    """Search block_batch for one coded-decode shape; record the winner."""
    from repro.kernels import ops as K
    shape, dtype = key_coded_decode(shares, dec)

    def make(blocks):
        return lambda: K.coded_decode(shares, dec, mask, scales,
                                      block_batch=blocks["block_batch"])
    blocks, timings = tune_call("coded_decode", make, repeats=repeats)
    table.put("coded_decode", shape, dtype, blocks)
    return timings


def tune_dequant_matmul(table: TuningTable, x, q, scale, *,
                        repeats: int = 5) -> Dict[str, float]:
    """Search (block_batch, block_n) for one dequant-matmul shape."""
    from repro.kernels import ops as K
    shape, dtype = key_dequant_matmul(x, q)

    def make(blocks):
        return lambda: K.dequant_matmul(x, q, scale,
                                        block_batch=blocks["block_batch"],
                                        block_n=blocks["block_n"])
    blocks, timings = tune_call("dequant_matmul", make, repeats=repeats)
    table.put("dequant_matmul", shape, dtype, blocks)
    return timings
