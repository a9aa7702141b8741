"""Decides ``correct``: the window's own answers against the reference.

After the window has closed, every sampled request that was answered is
recomputed by ``bench/reference.py`` from the benchmark's copy of the
weights, its own images, and the slots that its answer says arrived, in
float32 at the matmul precisions the configuration states (``precision``:
``convolutions`` for the students, ``merge`` for the FC merge). Each
request's gap is the widest gap between a served logit and the
reference's, relative to the reference's largest logit of that request.
Two numbers are compared: the 90th percentile of those gaps over the
sample (``rel_gap_p90``), which the bfloat16 control fails, and their
maximum (``max_rel_err``), which a gross error in a few answers fails.

A migration can move knowledge partitions between slots: a served slot
counts as ensemble slot j when it serves j's partition (the program's
weight store is keyed by partition) and has not been zeroed.

The control (``control=True``) puts the reference computed one precision
step below the configuration's (``control_dtype``, bfloat16 for this
float32 path) in the program's place. The planted fault (``fault=True``)
puts, for one compared request in ``FAULT_EVERY`` (by request id), the
reference merged as if one of its arrived slots had timed out in the
program's place: a masking fault in a few answers.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from bench import reference as R

FAULT_EVERY = 16


@dataclasses.dataclass
class Comparison:
    max_rel_err: float
    requests: int          # sampled answers compared
    rows: int
    degraded: int          # compared answers served degraded
    migrated: int          # compared answers served on a migrated plan
    not_comparable: int    # one partition served by two slots
    per_request: np.ndarray = None   # each compared answer's relative gap

    @property
    def rel_gap_p90(self) -> float:
        """90th percentile over the compared requests of their gaps."""
        if self.per_request is None or not len(self.per_request):
            return float("inf")
        return float(np.quantile(self.per_request, 0.9))


def ensemble_mask(base: np.ndarray, partition: np.ndarray, zeroed,
                  arrived: np.ndarray) -> Optional[np.ndarray]:
    """The mask over the configuration's slots under which the reference
    gives what a server with ``partition`` (after migrations), ``zeroed``
    slots and ``arrived`` slots serves; None when one partition is served
    by two slots."""
    mask = np.zeros(base.shape[0], bool)
    seen = np.zeros(base.shape[0], np.int64)
    for k, row in enumerate(partition):
        hit = np.flatnonzero((base == row).all(axis=1))
        if hit.size:
            seen[hit[0]] += 1
            mask[hit[0]] |= bool(arrived[k]) and k not in zeroed
    return None if (seen > 1).any() else mask


def compare(cfg: Dict, slots: tuple, weights: Dict, base: np.ndarray,
            kept: Dict[int, tuple], offsets: Dict[int, int],
            sizes: np.ndarray, pool: np.ndarray, *,
            control: bool = False, fault: bool = False,
            precision: Optional[Dict] = None) -> Comparison:
    """Compare the kept answers with the reference (or, with ``control``,
    the lower-precision reference with the reference; with ``fault``, the
    answers with a planted fault). ``precision`` overrides the
    configuration's ``precision``."""
    prec = {**cfg["precision"], **(precision or {})}
    rows, masks, served, spans, rids = [], [], [], [], []
    degraded = migrated = skipped = 0
    for rid in sorted(kept):
        res, ir, zeroed = kept[rid]
        part = np.asarray(ir.partition)
        mask = ensemble_mask(base, part, zeroed, res.arrived)
        if mask is None:
            skipped += 1
            continue
        n = int(sizes[rid])
        off = offsets[rid]
        spans.append((len(rows), len(rows) + n))
        rids.append(rid)
        rows += list(range(off, off + n))
        masks += [mask] * n
        served.append(np.asarray(res.logits, np.float32))
        degraded += int(res.degraded)
        migrated += int(part.shape != base.shape or (part != base).any())
    if not served:
        return Comparison(float("inf"), 0, 0, 0, 0, skipped)
    x = pool[np.asarray(rows)]
    row_mask = np.asarray(masks)
    feats = R.slot_features(weights, cfg["archs"], slots, x,
                            precision=prec["convolutions"])
    want = R.merge(weights, slots, feats, row_mask, precision=prec["merge"])
    if control:
        lo = prec["control_dtype"]
        dt = {"bfloat16": jnp.bfloat16}[lo]
        f = R.slot_features(weights, cfg["archs"], slots, x, dtype=dt,
                            precision="default")
        ctl = R.merge(weights, slots, f, row_mask, dtype=dt,
                      precision="default")
        served = [ctl[a:b] for a, b in spans]
    if fault:
        bad_mask = row_mask.copy()
        hit = []
        for i, (a, b) in enumerate(spans):
            on = np.flatnonzero(row_mask[a])
            if rids[i] % FAULT_EVERY == 0 and on.size:
                bad_mask[a:b, on[0]] = False
                hit.append(i)
        bad = R.merge(weights, slots, feats, bad_mask,
                      precision=prec["merge"])
        for i in hit:
            a, b = spans[i]
            served[i] = bad[a:b]
    errs = np.zeros(len(served))
    for i, ((a, b), got) in enumerate(zip(spans, served)):
        ref = want[a:b]
        if got.shape != ref.shape or not np.isfinite(got).all():
            errs[i] = np.inf
            continue
        scale = max(float(np.abs(ref).max()), 1e-6)
        errs[i] = float(np.abs(got - ref).max()) / scale
    return Comparison(float(errs.max()), len(served), len(rows), degraded,
                      migrated, skipped, errs)
