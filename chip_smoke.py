"""On-chip smoke run of the RoCoIn serving path on one TPU chip.

Drives the main path once, through the entry points a user calls, at the
paper's CIFAR-10 widths: a WRN-16-4 teacher (256 final filters), synthetic
32x32x3 images with 10 classes, the default student zoo and an 8-device
fleet. Weights are random from ``--seed`` and take a few training steps:
the run checks the path and its numbers, not accuracy.

  (a) ``build_rocoin`` -> ``server_from_ensemble`` (fp32, then int8) ->
      ``ServingEngine`` in measured-wall mode, with a ``ClusterController``
      repairing and migrating the live server under Markov link flapping
      plus one scripted outage of a whole replica group;
  (b) the same plan served by ``build_demo_server`` at the teacher's final
      width: the fused megastep (fp32 and int8), the output-coded step and
      the compute-coded step, with forced failures that erase systematic
      shares so the ``coded_decode`` kernel runs.

Every served request's logits are compared with a plain ``jax.numpy``
float32 reference, computed on the chip at "highest" matmul precision,
under the same arrived mask. The compiled megasteps must contain
``tpu_custom_call``: no kernel may run interpreted.

The script refuses to run without a TPU. Early lines name the device and
the versions, then each phase's path, timings and measured errors beside
their tolerances. The last stdout line is the JSON verdict
``{"ok": true, "device": {...}}``; a failed phase or check exits non-zero
and prints no verdict.

Run from the repository root:  python chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import pathlib
import sys
import time
import traceback
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.coding.planner import select_redundancy  # noqa: E402
from repro.core.pipeline import Ensemble, build_rocoin  # noqa: E402
from repro.core.grouping import Device  # noqa: E402
from repro.core.plan_ir import PlanIR  # noqa: E402
from repro.core.scenarios import PoissonArrivals  # noqa: E402
from repro.core.simulator import FailureModel, make_fleet  # noqa: E402
from repro.data.images import ImageTaskConfig, SyntheticImages  # noqa: E402
from repro.kernels import autotune, ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.runtime.controller import ClusterController  # noqa: E402
from repro.runtime.engine import (EngineConfig, ServingEngine,  # noqa: E402
                                  build_demo_server)
from repro.runtime.failures import (FailureEvent, FailureInjector,  # noqa: E402
                                    markov_flap_schedule)
from repro.runtime.serving import QuorumServer, server_from_ensemble  # noqa: E402

# Served logits against the float32 reference, as max|served - ref| /
# max|ref| per request. The served path runs at the TPU's default f32
# matmul precision (one bf16 pass, unit roundoff 2^-9); the reference runs
# at "highest". 5e-2 leaves room for that rounding through a 22-layer
# student, while a wrong slot, mask, FC slice or decode weight is an O(1)
# error.
FP32_REL_TOL = 5e-2
# int8 weight-only deployment against the same fp32 reference: the share of
# rows whose top-1 class agrees.
INT8_TOP1_MIN = 0.9
N_CLASSES = 10


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# errors against the reference
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Errors:
    """Running comparison of served logits against reference logits."""
    requests: int = 0
    rows: int = 0
    max_rel: float = 0.0
    top1_agree: int = 0

    def add(self, served: np.ndarray, want: np.ndarray) -> None:
        check(served.shape == want.shape,
              f"logits shape {served.shape} != reference {want.shape}")
        check(bool(np.isfinite(served).all()), "non-finite served logits")
        scale = max(float(np.abs(want).max()), 1e-6)
        self.max_rel = max(self.max_rel,
                           float(np.abs(served - want).max()) / scale)
        self.top1_agree += int((served.argmax(-1) == want.argmax(-1)).sum())
        self.requests += 1
        self.rows += served.shape[0]

    @property
    def top1(self) -> float:
        return self.top1_agree / max(self.rows, 1)

    def verdict(self, quantize: str) -> str:
        """Check against the tolerance for ``quantize``; the printed line."""
        check(self.requests > 0, "no request was compared")
        if quantize == "int8":
            check(self.top1 >= INT8_TOP1_MIN,
                  f"int8 top-1 agreement {self.top1:.4f} < {INT8_TOP1_MIN}")
            return (f"top1_agreement={self.top1!r} (min {INT8_TOP1_MIN}) "
                    f"max_rel_err={self.max_rel!r} over {self.requests} "
                    f"requests / {self.rows} rows")
        check(self.max_rel <= FP32_REL_TOL,
              f"max relative logit error {self.max_rel:.3e} > {FP32_REL_TOL}")
        return (f"max_rel_err={self.max_rel!r} (tol {FP32_REL_TOL}) "
                f"top1_agreement={self.top1!r} over {self.requests} "
                f"requests / {self.rows} rows")


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return np.asarray(fn(*args))


# ---------------------------------------------------------------------------
# phase (a): distilled ensemble behind the continuous-batching engine
# ---------------------------------------------------------------------------

def fleet(n: int, seed: int, prefix: str = "d") -> List[Device]:
    """The paper's heterogeneous edge fleet (``make_fleet``), with device
    memory from 1 MB up so every device can hold the smallest student of
    the zoo (a device below that leaves its slot without a student, and
    such a plan cannot be coded)."""
    return [dataclasses.replace(d, name=f"{prefix}{i}") for i, d in
            enumerate(make_fleet(n, seed=seed, mem_range=(1.0e6, 4.0e6)))]


def build_ensemble(seed: int, *, teacher_depth: int = 16,
                   teacher_widen: int = 4, zoo: Optional[List[str]] = None,
                   n_devices: int = 8, teacher_steps: int = 4,
                   student_steps: int = 4, batch: int = 64
                   ) -> Ensemble:
    """The paper's CIFAR-10 offline phase (teacher, planner, distilled
    students, FC head) with a few steps per network."""
    data = SyntheticImages(ImageTaskConfig(n_classes=N_CLASSES, seed=seed))
    return build_rocoin(jax.random.key(seed), n_classes=N_CLASSES,
                        teacher_depth=teacher_depth,
                        teacher_widen=teacher_widen,
                        devices=fleet(n_devices, seed), p_th=0.25,
                        teacher_steps=teacher_steps,
                        student_steps=student_steps, zoo=zoo, data=data,
                        batch=batch)


def describe_students(ens: Ensemble) -> str:
    return ", ".join(f"{cfg.name}/f{dim}"
                     for (cfg, _, _), dim in zip(ens.students, ens.part_dims))


class _Recorder:
    """Keeps every batch a server answers, with the plan it answered under
    (the server snapshots its plan at the same point)."""

    def __init__(self, srv: QuorumServer):
        self.srv = srv
        self.inner = srv.serve_batch
        self.calls = []
        srv.serve_batch = self

    def __call__(self, xs, *, rng=None):
        partition = np.array(self.srv.ir.partition)
        zeroed = self.srv.zeroed_slots
        out = self.inner(xs, rng=rng)
        self.calls.append((xs, out, partition, zeroed))
        return out


def ensemble_mask(base: np.ndarray, partition: np.ndarray, zeroed,
                  arrived: np.ndarray) -> Optional[np.ndarray]:
    """The ensemble-slot arrived mask under which ``Ensemble.predict``
    gives what a server serves with ``partition`` (after migrations),
    ``zeroed`` slots and ``arrived`` slots. A served slot whose partition
    is ensemble slot j's runs student j with FC rows j (the weight store is
    keyed by partition); any other slot has a zeroed FC slice. None when an
    ensemble partition is served by two slots (no such mask exists)."""
    mask = np.zeros(base.shape[0], bool)
    seen = np.zeros(base.shape[0], np.int64)
    for k, row in enumerate(partition):
        hit = np.flatnonzero((base == row).all(axis=1))
        if hit.size:
            seen[hit[0]] += 1
            mask[hit[0]] |= bool(arrived[k]) and k not in zeroed
    return None if (seen > 1).any() else mask


def _chaos_injector(ir: PlanIR, ticks: int, seed: int) -> FailureInjector:
    """Markov link flapping on every device plus one scripted outage that
    takes down every replica of slot 0 (ticks 3-9), so the controller must
    repair and migrate at least once."""
    events = markov_flap_schedule(list(ir.device_names), 0.08, 0.4, ticks,
                                  np.random.default_rng(seed))
    group0 = [ir.device_names[j] for j in np.flatnonzero(ir.member[0])]
    events += [FailureEvent(3, n, "crash") for n in group0]
    events += [FailureEvent(10, n, "recover") for n in group0]
    return FailureInjector(events)


def serve_ensemble(ens: Ensemble, *, quantize: str, n_requests: int,
                   seed: int, rate: float = 400.0) -> Dict:
    """Serve ``n_requests`` image requests through the engine under chaos
    and compare every answer with ``Ensemble.predict`` under the same
    arrived mask. Returns the phase's numbers; raises on a failed check."""
    t0 = time.perf_counter()
    srv = server_from_ensemble(ens, seed=seed, quantize=quantize)
    path = ("fused megastep" if srv.fastpath_active else
            "per-slot loop (Ensemble.fused_export() is None: the students "
            "differ in config or width)")
    # only the fused megastep deploys int8 weights; the loop serves fp32
    weights = "int8" if srv.fastpath_active and quantize == "int8" else "fp32"
    rec = _Recorder(srv)
    data = SyntheticImages(ImageTaskConfig(n_classes=N_CLASSES, seed=seed))
    horizon = n_requests / rate
    times, sizes = PoissonArrivals(rate, sizes=(1, 2)).generate(
        np.random.default_rng(seed), horizon)
    cfg = EngineConfig(max_batch=4, max_wait=0.005, slo=1.0,
                       chaos_every=horizon / 24, seed=seed)
    ctl = ClusterController(srv.ir, server=srv,
                            injector=_chaos_injector(srv.ir, 32, seed),
                            seed=seed)

    def make_input(rng, rows):
        return data.batch(rows, int(rng.integers(1 << 30)))[0]

    engine = ServingEngine(srv, cfg, controller=ctl, make_input=make_input)
    report = engine.run(times, sizes)
    t_serve = time.perf_counter() - t0
    summary = report.summary()
    kinds = [out.kind for _, out in report.migrations]
    check(summary["n"] == len(times), f"served {summary['n']} of "
          f"{len(times)} requests")
    check(len(kinds) >= 1, "no repair was applied")
    check(srv.last_migration is not None, "the server never migrated")

    t1 = time.perf_counter()
    errs, skipped, degraded, migrated = Errors(), 0, 0, 0
    base = np.asarray(ens.ir.partition)
    ref_rows = max(int(x.shape[0]) for xs, *_ in rec.calls for x in xs)
    cache: Dict = {}
    for xs, results, partition, zeroed in rec.calls:
        moved = partition.shape != base.shape or bool((partition != base).any())
        for x, res in zip(xs, results):
            mask = ensemble_mask(base, partition, zeroed, res.arrived)
            if mask is None:
                skipped += 1
                continue
            key = (id(x), mask.tobytes())
            if key not in cache:
                # one padded reference shape keeps the eager ops compiled once
                xp = np.zeros((ref_rows,) + x.shape[1:], np.float32)
                xp[:x.shape[0]] = x
                cache[key] = _highest(ens.predict, jnp.asarray(xp),
                                      mask)[:x.shape[0]]
            errs.add(res.logits, cache[key])
            degraded += int(res.degraded)
            migrated += int(moved)
    check(degraded > 0, "no compared request was served degraded")
    line = errs.verdict(quantize)
    return {"path": path, "weights_served": weights,
            "requests": summary["n"], "batches": len(report.batches),
            "serve_s": t_serve, "check_s": time.perf_counter() - t1,
            "p50_s": summary["p50"], "p99_s": summary["p99"],
            "quorum_rate": summary["quorum_rate"], "repairs": kinds,
            "last_migration": srv.last_migration,
            "degraded_compared": degraded,
            "compared_on_a_migrated_plan": migrated,
            "not_comparable": skipped, "check": line}


# ---------------------------------------------------------------------------
# phase (b): fused megastep and the coded steps at the teacher's width
# ---------------------------------------------------------------------------

def _names(ir: PlanIR, cols) -> List[str]:
    return [ir.device_names[int(j)] for j in cols]


def coded_variants(ir: PlanIR, spares: Sequence[Device]):
    """(name, plan, quantize, devices forced down) for each served step.

    The coded plans see ``spares`` as the fleet's spare pool
    (``PlanIR.add_devices``) and take one parity share per group or slot:
    a plan of single-device groups with every device in use has nowhere
    to put a parity share, and adaptive coding never codes such groups
    (coding them costs more compute than they use). The forced failures
    erase a systematic share of a coded slot, so the decode kernel must
    run for every request."""
    pooled = ir.add_devices(spares)
    out_ir = select_redundancy(pooled, code_k=4, parity=1)
    cmp_ir = select_redundancy(pooled, code_k=2, parity=1, mode="compute")
    check(out_ir.coding is not None and out_ir.coding.n_groups > 0,
          "select_redundancy coded no group")
    check(cmp_ir.compute_coding is not None and cmp_ir.compute_coding.Q > 0,
          "select_redundancy(mode='compute') coded no slot")
    cs = out_ir.coding
    coded_slot = int(cs.group_slots(0)[0])
    cc = cmp_ir.compute_coding
    return [
        ("fused fp32", ir, "none", _names(ir, np.flatnonzero(ir.member[0]))),
        ("fused int8", ir, "int8", _names(ir, np.flatnonzero(ir.member[0]))),
        ("output-coded", out_ir, "none",
         _names(out_ir, np.flatnonzero(out_ir.member[coded_slot]))),
        ("compute-coded", cmp_ir, "none", _names(cmp_ir, cc.shard_member[0][:1])),
    ]


def demo_reference(srv: QuorumServer, x, arrived) -> jnp.ndarray:
    """Plain-jnp logits of the demo server's model: every slot's portion
    (the content-addressed forward, padded to the uniform width), masked
    by ``arrived``, merged by the ``quorum_aggregate`` oracle."""
    Dk = int(srv.fc_weights.shape[1])
    portions = []
    for fn in srv.portion_fns:
        p = fn(x)
        portions.append(jnp.pad(p, ((0, 0), (0, Dk - p.shape[1]))))
    return ref.quorum_aggregate_ref(jnp.stack(portions), srv.fc_weights,
                                    srv.fc_bias, jnp.asarray(arrived))


def megastep_hlo(srv: QuorumServer, x: np.ndarray, coded: bool) -> str:
    """Compiled HLO text of the server's megastep (or output-coded step)
    at the shapes of ``x``."""
    stacked, step = srv._ensure_fused()
    fc_w, fc_scales = ((srv._fc_q.q, srv._fc_q.scale)
                       if srv._fc_q is not None else (srv.fc_weights, None))
    B, K = x.shape[0], int(srv.fc_weights.shape[0])
    if not coded:
        lowered = step.lower(stacked, x, np.ones((B, K), bool),
                             np.ones(K, bool), fc_w, fc_scales, srv.fc_bias,
                             masked=True)
    else:
        rt = srv._coded_runtime(srv.ir)
        R = rt.n_shares
        lowered = srv._coded_step().lower(
            stacked, x, np.zeros((K, B, R), np.float32), np.ones((B, R), bool),
            np.ones(K, bool), rt.enc_device, fc_w, fc_scales, srv.fc_bias)
    return lowered.compile().as_text()


def serve_coded(ir: PlanIR, *, feat: int, hidden: int, rows: int,
                n_requests: int, seed: int, n_spares: int = 4) -> Dict:
    """Serve the fused, int8, output-coded and compute-coded steps with
    forced failures; compare each request with the plain-jnp reference."""
    out = {}
    rng = np.random.default_rng(seed)
    xs = [rng.standard_normal((rows, feat)).astype(np.float32)
          for _ in range(n_requests)]
    spares = fleet(n_spares, seed + 1, prefix="spare")
    for name, pir, quantize, down in coded_variants(ir, spares):
        t0 = time.perf_counter()
        srv = build_demo_server(pir, feat=feat, hidden=hidden,
                                n_classes=N_CLASSES, seed=seed,
                                quantize=quantize)
        check(srv.fastpath_active, f"{name}: the demo server is not fused")
        served = srv.serve_batch(xs)                  # all devices up
        srv.failure = FailureModel(forced_failures=down, outages=False)
        served += srv.serve_batch(xs)
        jax.block_until_ready([r._logits for r in served])
        t_serve = time.perf_counter() - t0
        if name == "output-coded":
            check(srv._fused_step_coded is not None,
                  f"{name}: the coded step never ran")
        if name == "compute-coded":
            check(srv._fused_step_compute is not None,
                  f"{name}: the compute-coded step never ran")
        check(bool(served[-1].arrived.all()) or name.startswith("fused"),
              f"{name}: the erased share was not recovered")
        errs = Errors()
        for x, res in zip(xs + xs, served):
            errs.add(res.logits, _highest(demo_reference, srv,
                                          jnp.asarray(x), res.arrived))
        entry = {"modes": pir.redundancy_modes(), "down": down,
                 "serve_s": t_serve,
                 "arrived_under_failure": served[-1].arrived.astype(int)
                 .tolist(), "check": errs.verdict(quantize)}
        if name in ("fused fp32", "output-coded"):
            hlo = megastep_hlo(srv, np.concatenate(xs[:1]),
                               coded=name == "output-coded")
            check("tpu_custom_call" in hlo or jax.default_backend() != "tpu",
                  f"{name}: no tpu_custom_call in the compiled megastep")
            entry["tpu_custom_call"] = "tpu_custom_call" in hlo
        out[name] = entry
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def device_line() -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _version(pkg: str) -> str:
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def _run_phase(name: str, fn, failures: List[str]):
    t0 = time.perf_counter()
    try:
        result = fn()
    except Exception as e:       # report every phase, then fail the run
        traceback.print_exc()
        log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s: "
            f"{type(e).__name__}: {e}")
        failures.append(name)
        return None
    log(f"[{name}] ok in {time.perf_counter() - t0:.1f}s")
    return result


def _report(name: str, result: Optional[Dict]) -> None:
    for key, val in (result or {}).items():
        log(f"[{name}] {key}: {val}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_line()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev['platform']!r} "
              f"({dev['kind']}); refusing to fall back", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    # block sizes come from the built-in defaults, never from a table file
    autotune.set_table(autotune.TuningTable())
    log(f"device: {dev['kind']} x{dev['count']} ({dev['platform']})")
    log(f"versions: jax {jax.__version__}, jaxlib {_version('jaxlib')}, "
        f"libtpu {_version('libtpu')}; compile cache: {cache}")

    failures: List[str] = []
    t0 = time.perf_counter()
    ens = _run_phase("build", lambda: build_ensemble(args.seed), failures)
    if ens is None:
        return 1
    log(f"[build] plan K={len(ens.students)} M={ens.ir.M} "
        f"modes={ens.ir.redundancy_modes()} students: "
        f"{describe_students(ens)}")
    for quantize in ("none", "int8"):
        name = f"a/{'fp32' if quantize == 'none' else 'int8'}"
        _report(name, _run_phase(name, lambda q=quantize: serve_ensemble(
            ens, quantize=q, n_requests=300, seed=args.seed), failures))
    res = _run_phase("b", lambda: serve_coded(
        ens.ir, feat=ens.ir.M, hidden=ens.ir.M, rows=32, n_requests=4,
        seed=args.seed), failures)
    for name, entry in (res or {}).items():
        _report(f"b/{name}", entry)
    log(f"total: {time.perf_counter() - t0:.1f}s")
    if failures:
        log(f"FAILED phases: {failures}")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
