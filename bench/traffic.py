"""The one traffic generator: a mix file in, arrivals and a drill out.

A mix is ``bench/traffic/<name>.json``:

    {"arrivals": {"kind": "poisson", "rate": 5200.0},
     "sizes": [1, 2], "size_probs": [0.5, 0.5],
     "drain_s": 2.0,           # answer requests due in the window for this long
     "drill": null}

Every mix is served under the fleet's own per-request outage channel and
the engine's default ``EngineConfig``. ``arrivals.kind`` is ``poisson``
(``rate``) or ``mmpp`` (``rates``, ``dwell``: a two-state Markov-modulated
Poisson process). A ``drill`` scripts device failures on a fixed tick,
from the file and not from the run's seed, so every run sees the same
repairs:

    {"tick_s": 0.5, "seed": 7, "flap": {"p_fail": 0.08, "p_recover": 0.4},
     "down": [{"group": 0, "from": 0.25, "to": 0.45}]}

``flap`` is a Gilbert up/down chain per device; each ``down`` entry holds
every member of the plan's device ``group`` down over that share of the
window.

Poisson arrivals are drawn given their count: ``rate × seconds`` requests
at uniform times, with the sizes in their exact shares, so that every seed
brings the same work in another order (a Poisson process conditioned on
its count). The MMPP generator is a copy of the program's
``core/scenarios.py`` ``MMPPArrivals``.
"""
from __future__ import annotations

import json
import math
import pathlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent


def load_mix(name_or_path) -> Dict:
    path = pathlib.Path(name_or_path)
    if path.suffix != ".json":
        path = BENCH / "traffic" / f"{name_or_path}.json"
    return json.loads(path.read_text())


def _sizes(rng: np.random.Generator, n: int, sizes: Sequence[int],
           probs) -> np.ndarray:
    """``n`` request sizes in their exact shares (largest remainders),
    shuffled."""
    arr = np.asarray(sizes, np.int64)
    p = np.full(len(arr), 1.0 / len(arr)) if probs is None \
        else np.asarray(probs, np.float64) / np.sum(probs)
    counts = np.floor(p * n).astype(np.int64)
    counts[np.argsort(-(p * n - counts))[:n - counts.sum()]] += 1
    return rng.permutation(np.repeat(arr, counts))


def poisson_times(rng: np.random.Generator, rate: float,
                  horizon: float) -> np.ndarray:
    """``round(rate × horizon)`` arrival times in [0, horizon): a Poisson
    process at ``rate``/s given its count."""
    return np.sort(rng.uniform(0.0, horizon, int(round(rate * horizon))))


def mmpp_times(rng: np.random.Generator, rates: Sequence[float],
               dwell: Sequence[float], horizon: float) -> np.ndarray:
    """Arrival times in [0, horizon) of a two-state MMPP that starts calm."""
    chunks: List[np.ndarray] = []
    t, state = 0.0, 0
    while t < horizon:
        end = min(t + float(rng.exponential(dwell[state])), horizon)
        n = int(rng.poisson(rates[state] * (end - t)))
        if n:
            chunks.append(np.sort(rng.uniform(t, end, n)))
        t, state = end, 1 - state
    return np.concatenate(chunks) if chunks else np.zeros(0)


def arrivals(mix: Dict, seed: int, seconds: float
             ) -> Tuple[np.ndarray, np.ndarray]:
    """(times, sizes) of every request due in a window of ``seconds``."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 1])
    a = mix["arrivals"]
    if a["kind"] == "poisson":
        times = poisson_times(rng, float(a["rate"]), seconds)
    elif a["kind"] == "mmpp":
        times = mmpp_times(rng, a["rates"], a["dwell"], seconds)
    else:
        raise ValueError(f"unknown arrival kind {a['kind']!r}")
    return times, _sizes(rng, len(times), mix["sizes"], mix.get("size_probs"))


def offered_rate(mix: Dict) -> float:
    a = mix["arrivals"]
    if a["kind"] == "poisson":
        return float(a["rate"])
    w, r = np.asarray(a["dwell"], float), np.asarray(a["rates"], float)
    return float((w * r).sum() / w.sum())


def with_rate(mix: Dict, rate: float) -> Dict:
    """The mix with its arrivals scaled to a mean of ``rate``/s."""
    a = dict(mix["arrivals"])
    if a["kind"] == "poisson":
        a["rate"] = rate
    else:
        f = rate / offered_rate(mix)
        a["rates"] = [r * f for r in a["rates"]]
    return {**mix, "arrivals": a}


def drill_down_sets(drill: Dict, names: Sequence[str],
                    groups: Dict[int, List[str]], seconds: float
                    ) -> List[set]:
    """The down-set at each drill tick (tick i fires at (i + 1) * tick_s)."""
    n_ticks = int(math.floor(seconds / drill["tick_s"] + 1e-9))
    rng = np.random.default_rng(drill["seed"])
    up = np.ones(len(names), bool)
    flap = drill.get("flap")
    out = []
    for i in range(n_ticks):
        if flap:
            u = rng.random(len(names))
            up = np.where(up, u >= flap["p_fail"], u < flap["p_recover"])
        down = {n for n, ok in zip(names, up) if not ok}
        t = (i + 1) * drill["tick_s"] / seconds
        for d in drill.get("down", ()):
            if d["from"] <= t < d["to"]:
                down |= set(groups[d["group"]])
        out.append(down)
    return out


def drill_events(down_sets: List[set]) -> List[Tuple[int, str, str]]:
    """(tick, device, crash|recover) transitions of the down-sets."""
    events, prev = [], set()
    for i, down in enumerate(down_sets):
        events += [(i, n, "crash") for n in sorted(down - prev)]
        events += [(i, n, "recover") for n in sorted(prev - down)]
        prev = down
    return events
