"""Decides ``correct``: the window's own answers against the reference.

After the window has closed, every sampled request that was answered is
recomputed by the reference of the configuration's kind (``reference`` of
``bench/kinds/<kind>.py``) from the benchmark's copy of the weights, the
request's own input and the slots that its answer says arrived, at the
precision the configuration states. Each request's gap is the widest gap
between a served logit and the reference's, relative to the reference's
largest logit of that request. Two numbers are compared, each against the
kind's ``LIMITS``: the 90th percentile of those gaps over the sample
(``rel_gap_p90``), which the control fails, and their maximum
(``max_rel_err``), which a gross error in a few answers fails.

A migration can move knowledge partitions between slots: a served slot
counts as ensemble slot j when it serves j's partition (the program's
weight store is keyed by partition) and has not been zeroed.

The control (``control=True``) puts the kind's reference computed one
precision step below the configuration's in the program's place. The
planted fault (``fault=True``) puts, for one compared request in
``FAULT_EVERY`` (by request id), the reference merged as if the first of
its arrived slots had timed out in the program's place: a masking fault of
the quorum merge, which every kind has, in a few answers.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

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


def compare(dep, kept: Dict[int, tuple], inputs, sizes: np.ndarray, *,
            control: bool = False, fault: bool = False) -> Comparison:
    """Compare the kept answers of a window over the deployment ``dep``
    with the reference (or, with ``control``, the control with the
    reference; with ``fault``, the answers with a planted fault).
    ``inputs`` gives each request's input by id, ``sizes`` its rows."""
    base = np.asarray(dep.ir.partition)
    xs, masks, served, rids = [], [], [], []
    degraded = migrated = skipped = 0
    for rid in sorted(kept):
        res, ir, zeroed = kept[rid]
        part = np.asarray(ir.partition)
        mask = ensemble_mask(base, part, zeroed, res.arrived)
        if mask is None:
            skipped += 1
            continue
        rids.append(rid)
        xs.append(inputs.request(rid, int(sizes[rid])))
        masks.append(mask)
        served.append(np.asarray(res.logits, np.float32))
        degraded += int(res.degraded)
        migrated += int(part.shape != base.shape or (part != base).any())
    if not served:
        return Comparison(float("inf"), 0, 0, 0, 0, skipped)
    want = dep.kind.reference(dep, xs, masks)
    if control:
        served = dep.kind.reference(dep, xs, masks, control=True)
    if fault:
        hit = [i for i, rid in enumerate(rids)
               if rid % FAULT_EVERY == 0 and masks[i].any()]
        bad = []
        for i in hit:
            m = masks[i].copy()
            m[np.flatnonzero(m)[0]] = False
            bad.append(m)
        if hit:
            wrong = dep.kind.reference(dep, [xs[i] for i in hit], bad)
            for i, got in zip(hit, wrong):
                served[i] = got
    errs = np.zeros(len(served))
    for i, (ref, got) in enumerate(zip(want, served)):
        if got.shape != ref.shape or not np.isfinite(got).all():
            errs[i] = np.inf
            continue
        scale = max(float(np.abs(ref).max()), 1e-6)
        errs[i] = float(np.abs(got - ref).max()) / scale
    return Comparison(float(errs.max()), len(served), sum(len(x) for x in xs),
                      degraded, migrated, skipped, errs)
