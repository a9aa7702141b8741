"""One benchmark run: a cell of ``BENCHMARK.json`` served on the chip.

    python3 bench/run.py --workload c10-poisson --seed 7 --seconds 10 --trace 0

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``). The configuration's kind
(``bench/kinds/<kind>.py``) makes the weights and the requests' inputs
from ``--seed`` and holds the plain reference; the run draws the traffic
from ``--seed``, serves the mix through the program's engine for
``--seconds`` on the real clock, and checks the window's answers against
the reference. With ``--trace 0`` it reports the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read by
``bench/metrics/<name>.py`` from the run's records and a profiler trace of
a few seconds of the window. The last line of standard output is the
result; the numbers compared for ``correct`` also end standard error.

It refuses to run without the chips the cell asks for. Two further modes
serve the benchmark's own set-up and are not used by its runs:
``--sweep R1,R2,...`` serves the mix at each offered rate in turn (the
knee sweep), and ``--control 1`` also reports whether the control and
the planted fault (``bench/check.py``) would have been judged correct.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.metadata  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional, Sequence  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

# Coverage of the comparison; the limits of the numbers compared are the
# configuration's kind's (``LIMITS`` of ``bench/kinds/<kind>.py``).
MIN_COMPARED = 50


def log(msg: str) -> None:
    print(msg, flush=True)


def load_benchmark(root: pathlib.Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones:
    those that list the cell, or list no cells and move a metric it
    reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def device_check(chips: int) -> Dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: needs {chips} TPU chip(s), JAX found {len(devs)} "
              f"{devs[0].platform!r} device(s) ({devs[0].device_kind}); "
              "refusing to run elsewhere", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _version(pkg: str) -> str:
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def describe_window(w, seconds: float) -> None:
    from bench.measure import percentile
    lat = w.latencies_ms()
    answered = np.isfinite(w.t_done)
    log(f"requests: due {w.due}, answered {int(answered.sum())} "
        f"({int((w.t_done <= seconds).sum())} inside the window), "
        f"queued at the end {int((~np.isfinite(w.t_dispatch)).sum())}, "
        f"failed {w.failed}")
    log(f"latency ms: p50 {percentile(lat, 50)!r} p95 {percentile(lat, 95)!r}"
        f" p99 {percentile(lat, 99)!r} max {float(np.max(lat))!r}")
    late = w.lateness * 1e3
    if late.size:
        log(f"dispatch lateness ms (real minus virtual due): p50 "
            f"{float(np.median(late))!r} p99 {percentile(late, 99)!r} "
            f"max {float(late.max())!r}")
    b = w.batches
    if b:
        log(f"batches: {len(b)}, mean requests {np.mean([x.n_requests for x in b])!r}"
            f", mean rows {np.mean([x.rows for x in b])!r}, mean service ms "
            f"{1e3 * np.mean([x.service_s for x in b])!r}")
    for kind in ("compile", "cache"):
        cs = [c for c in w.compiles if c[3] == kind]
        log(f"in the window, {kind}: {len(cs)} ({sum(c[2] for c in cs)!r} s"
            f") " + ", ".join(sorted({c[1] for c in cs}))[:300])
    if w.repairs:
        log("repairs: " + ", ".join(
            f"{r['kind']}@{r['t']:.2f}s({1e3 * r['wall_s']:.1f}ms,"
            f"rejit {r['rejitted']},zeroed {r['zeroed']})"
            for r in w.repairs))


def compare_window(dep, win, **how):
    from bench import check
    return check.compare(dep, win.kept, win.inputs, win.sizes, **how)


def verdict(cmp, mix: Dict, limits: Dict) -> Dict:
    """The numbers compared, each beside its limit (``limits``, the
    kind's, for the two gaps)."""
    out = {"rel_gap_p90": {"value": cmp.rel_gap_p90,
                           "limit": limits["rel_gap_p90"], "rule": "<="},
           "max_rel_err": {"value": cmp.max_rel_err,
                           "limit": limits["max_rel_err"], "rule": "<="},
           "requests_compared": {"value": cmp.requests,
                                 "limit": MIN_COMPARED, "rule": ">="},
           "degraded_compared": {"value": cmp.degraded, "limit": 1,
                                 "rule": ">="}}
    if mix.get("drill"):
        out["migrated_compared"] = {"value": cmp.migrated, "limit": 1,
                                    "rule": ">="}
    return out


def passes(v: Dict) -> bool:
    return all(x["value"] <= x["limit"] if x["rule"] == "<="
               else x["value"] >= x["limit"] for x in v.values())


def compile_cache() -> str:
    """The program's persistent compile cache, holding every program:
    the small eager ones compile in under JAX's default second too."""
    import jax
    from repro.launch.compile_cache import use_compile_cache  # the program
    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def run_cell(args, bench: Dict, cell: Dict) -> int:
    import jax
    import repro  # noqa: F401  the program under test, before the chip
    from bench import deploy
    from bench import traffic as T

    dev = device_check(int(cell["chips"]))
    cache = compile_cache()
    log(f"device: {dev['kind']} x{dev['count']} ({dev['platform']}); jax "
        f"{jax.__version__}, jaxlib {_version('jaxlib')}, libtpu "
        f"{_version('libtpu')}; compile cache {cache}")
    result = execute(args, bench, cell, deploy.load_config(cell["config"]),
                     T.load_mix(cell["traffic"]), dev)
    if result is not None:
        print(json.dumps(result), flush=True)
    return 0


def execute(args, bench: Dict, cell: Dict, cfg: Dict, mix: Dict,
            dev: Dict) -> Optional[Dict]:
    """Everything of a run after the look for the chip: returns the result
    line (None in sweep mode)."""
    import jax
    from bench import deploy, measure, trace_reduce, window
    from bench import traffic as T

    measure.peaks(dev["kind"])                # an unknown chip is an error
    counter = window.CompileCounter()
    t = time.perf_counter()
    dep = deploy.build(cfg, args.seed)
    log(f"cell {cell['name']}: {cfg['name']} under {cell['traffic']} "
        f"({T.offered_rate(mix)!r} req/s offered); {deploy.describe(dep)}")
    log(f"set-up: weights and server {time.perf_counter() - t:.1f} s, "
        f"{time.perf_counter() - T_START:.1f} s since start; "
        f"{counter.summary()}")
    if args.sweep:
        sweep(dep, mix, args, counter)
        return None
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        win = window.serve_window(dep, mix, args.seed, args.seconds,
                                  counter=counter, trace_dir=tdir)
        run = measure.Run(dep.kind, cfg, dep.slots, win,
                          win.setup_end - T_START, dev["kind"])
        describe_window(win, args.seconds)
        mem = jax.devices()[0].memory_stats() or {}
        dev["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
        if tdir is not None:
            run.trace = trace_reduce.load(trace_reduce.find_xplane(tdir))
            dev["busy_s"] = run.trace.busy_s()
            dev["window_s"] = run.trace.window_s
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    values = {}
    for m in metrics_for(bench, cell["name"], bool(args.trace)):
        v = measure.reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    for name, v in values.items():
        log(f"metric {name}: {v['value']!r} {v['unit']}")
    breakdown = None
    if run.trace is not None:
        breakdown = {"device_ops": run.trace.top_ops(),
                     "idle_gaps": run.trace.idle_gaps()}
        log(f"trace: busy {dev['busy_s']!r} s of {dev['window_s']!r} s; "
            f"{json.dumps(breakdown)}")
        log("trace: idle_gaps_by_phase "
            + json.dumps(run.trace.idle_by_phase()))
    # the reference runs once the program's state is freed
    dep.server = None
    gc.collect()
    t_check = time.perf_counter()
    limits = dep.kind.LIMITS
    cmp = compare_window(dep, win)
    v = verdict(cmp, mix, limits)
    others = {}
    if args.control:
        for who in ("control", "fault"):
            c = compare_window(dep, win, **{who: True})
            others[who] = {"correct": passes(verdict(c, mix, limits)),
                           "rel_gap_p90": c.rel_gap_p90,
                           "max_rel_err": c.max_rel_err}
    for who, c in [("program", {"correct": passes(v),
                                "rel_gap_p90": cmp.rel_gap_p90,
                                "max_rel_err": cmp.max_rel_err}),
                   *others.items()]:
        log(f"check numbers, {who}: rel_gap_p90 {c['rel_gap_p90']!r}, "
            f"max_rel_err {c['max_rel_err']!r}, correct {c['correct']}")
    log(f"check: {cmp.requests} requests / {cmp.rows} rows compared, "
        f"{cmp.degraded} degraded, {cmp.migrated} on a migrated plan, "
        f"{cmp.not_comparable} not comparable; "
        f"{time.perf_counter() - t_check:.1f} s")
    for name, x in v.items():
        print(f"{name}: {x['value']!r} (limit {x['rule']} {x['limit']!r})",
              file=sys.stderr, flush=True)
    result = {"correct": passes(v) and win.failed == 0,
              "attempted": int(win.due), "failed": int(win.failed),
              "metrics": values, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result.update(others)
    result["compared"] = v
    return result


def sweep(dep, mix: Dict, args, counter) -> None:
    """Serve ``mix`` at each rate of ``--sweep`` in turn. The knee is the
    highest rate that the window kept up with: 97 % of the offered rate
    answered inside it (requests due in its last ~25 ms are still in
    service when it closes) and a 95th percentile under 100 ms, which a
    backlog that grows through the window exceeds."""
    from bench import window
    from bench import traffic as T
    from bench.measure import percentile
    rows = []
    for rate in [float(r) for r in args.sweep.split(",")]:
        win = window.serve_window(dep, T.with_rate(mix, rate), args.seed,
                                  args.seconds, counter=counter)
        lat = win.latencies_ms()
        row = {"offered": rate,
               "served_rps": float((win.t_done <= args.seconds).sum())
               / args.seconds,
               "queued_share": float((~np.isfinite(win.t_dispatch)).mean()),
               "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
               "mean_batch": float(np.mean([b.n_requests
                                            for b in win.batches])),
               "service_ms": 1e3 * float(np.mean([b.service_s
                                                  for b in win.batches]))}
        log("sweep " + json.dumps(row))
        rows.append(row)
    kept = [r["offered"] for r in rows
            if r["served_rps"] >= 0.97 * r["offered"] and r["p95_ms"] < 100]
    log("knee " + json.dumps({"knee_rps": max(kept) if kept else None,
                              "rows": rows}))


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    return run_cell(args, bench, cell_of(bench, args.workload))


if __name__ == "__main__":
    sys.exit(main())
