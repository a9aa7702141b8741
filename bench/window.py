"""Drives the program's ``ServingEngine`` on the real clock for one window.

The engine keeps a virtual clock. ``RealClockEngine`` holds each dispatch
until its virtual time is due on the real clock (t0 + t) and takes the real
completion time as the batch's done time, so a request's latency is its
answer's real time minus its due time: the generator's lateness, the
engine's event loop and the controller's repairs are all inside it. It
overrides two private methods of the engine (``_dispatch``, ``_input``);
a real-clock mode in the engine would let it go.

``_input`` gives every request its own input, which the configuration's
kind (``bench/kinds/``) makes from the seed and the request's id (the
engine would reuse one array per row count); filler rows and warm-up take
the kind's ``warm`` arrays. The window closes ``seconds`` after the first
dispatch; requests due in it are answered for ``drain_s`` more, and
whatever is still queued then is left unanswered.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
import os
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from bench import deploy
from bench import traffic as T

SAMPLE = 400              # requests kept for the comparison with the reference


class WindowClosed(Exception):
    """The window and its drain are over; the backlog stays queued."""


def sample(seed: int, due: int) -> set:
    """Ids of the requests kept for the comparison with the reference."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 2])
    return set(rng.choice(due, min(SAMPLE, due), replace=False).tolist())


@dataclasses.dataclass
class BatchInfo:
    """One dispatched micro-batch, real seconds from the window's start."""
    t_dispatch: float
    t_done: float
    n_requests: int
    rows: int
    padded_rows: int
    service_s: float
    computed: Optional[np.ndarray]     # (K,) slots the batch ran (any row)
    slot_widths: tuple                 # (K,) widths of the served slots
    record: Any = None                 # the engine's own BatchRecord, whole


class Recorder:
    """Wraps ``server.serve_batch`` (an instance attribute, so the engine
    and its warm-up call through it): keeps the answers of the sampled
    requests with the plan they were served under, and what each batch
    computed."""

    def __init__(self, server, keep: set):
        self.server = server
        self.inner = server.serve_batch
        self.keep = keep
        self.current: Optional[List] = None      # requests of this dispatch
        self.kept: Dict[int, tuple] = {}
        self.last_any: Optional[np.ndarray] = None
        self.failed = 0
        server.serve_batch = self

    def __call__(self, xs, *, rng=None):
        srv = self.server
        ir, zeroed = srv.ir, srv.zeroed_slots
        self.last_any = None
        self.last_ok = False
        with jax.profiler.TraceAnnotation("serve_batch"):
            try:
                out = self.inner(xs, rng=rng)
            except Exception:          # an answer the program could not give
                traceback.print_exc()
                self.failed += len(self.current or ())
                return []
        self.last_ok = True
        if out:
            self.last_any = np.asarray([r.arrived for r in out]).any(axis=0)
        for req, res in zip(self.current or (), out):
            if req.rid in self.keep:
                self.kept[req.rid] = (res, ir, zeroed)
        return out


def make_engine_class():
    from repro.runtime.engine import ServingEngine

    class RealClockEngine(ServingEngine):
        """``ServingEngine`` whose dispatches run on the real clock."""

        def __init__(self, server, config, *, recorder: Recorder,
                     inputs, seconds: float, drain_s: float,
                     on_dispatch: Callable[[float], None], **kw):
            super().__init__(server, config, **kw)
            self.recorder = recorder
            self.inputs = inputs
            self.seconds = seconds
            self.drain_s = drain_s
            self.on_dispatch = on_dispatch
            self.t0: Optional[float] = None
            self.lateness: List[float] = []
            self.infos: List[BatchInfo] = []
            self.dispatched: List = []         # the engine's RequestRecords
            self._pending: List = []           # this dispatch's requests

        def _input(self, rows):
            # the engine asks for each request's rows in order, then for
            # the bucket's filler; its warm-up asks outside any dispatch
            if self._pending:
                r = self._pending.pop()
                return self.inputs.request(r.rid, r.size)
            return self.inputs.warm(rows)

        def _dispatch(self, now, reqs, bid):
            if self.t0 is None:
                self.t0 = time.perf_counter() - now
            wait = self.t0 + now - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("wait_due"):
                    time.sleep(wait)
            real = time.perf_counter() - self.t0
            if real > self.seconds + self.drain_s:
                raise WindowClosed
            self.lateness.append(real - now)
            self.on_dispatch(real)
            self._pending = reqs[::-1]
            self.recorder.current = reqs
            with jax.profiler.TraceAnnotation("dispatch"):
                _, batch, events = super()._dispatch(max(now, real), reqs,
                                                     bid)
            t_done = time.perf_counter() - self.t0
            self.recorder.current = None
            ok = self.recorder.last_ok
            for r in reqs:
                r.t_done = t_done if ok else np.inf
            self.dispatched += reqs
            batch.t_done = t_done
            rows = sum(r.size for r in reqs)
            padded = (1 << (rows - 1).bit_length()) - rows \
                if self.cfg.bucket_rows and rows else 0
            computed = self.recorder.last_any
            if self.server.fastpath_active and computed is not None:
                computed = np.ones_like(computed)   # the megastep runs all
            self.infos.append(BatchInfo(
                max(now, real), t_done, len(reqs), rows, padded,
                batch.service_s, computed, tuple(self.server.part_dims or ()),
                batch))
            return t_done, batch, events

    return RealClockEngine


@dataclasses.dataclass
class Window:
    """What one window measured; times are real seconds from its start."""
    seconds: float
    setup_end: float                     # perf_counter at the window's start
    t_arrival: np.ndarray
    sizes: np.ndarray
    t_dispatch: np.ndarray               # inf where never dispatched
    t_done: np.ndarray                   # inf where never answered
    quorum_ok: np.ndarray
    batches: List[BatchInfo]
    repairs: List[Dict]
    lateness: np.ndarray
    compiles: List[tuple]     # (real t, name, seconds, compile|cache) in window
    kept: Dict[int, tuple]               # rid -> (ServeResult, ir, zeroed)
    inputs: Any                          # the kind's inputs of the requests
    failed: int
    trace_span: Optional[tuple] = None   # (t_start, t_stop) real seconds

    @property
    def due(self) -> int:
        return len(self.t_arrival)

    def latencies_ms(self) -> np.ndarray:
        return (self.t_done - self.t_arrival) * 1e3


def buckets(max_rows: int) -> List[int]:
    """The engine's power-of-two row buckets up to ``max_rows``."""
    out = [1]
    while out[-1] < max_rows:
        out.append(out[-1] << 1)
    return out


def precompile(server, inputs, max_rows: int) -> int:
    """Compile every slot's forward at every row bucket, several at a time
    (XLA compiles outside the interpreter lock); the engine's own warm-up
    then finds them compiled. Returns the number of programs."""
    fns = server.jitted_portions
    jobs = [(k, b) for b in reversed(buckets(max_rows))
            for k in range(len(fns))]

    def one(job):
        k, b = job
        return jax.block_until_ready(fns[k](inputs.warm(b)))
    # the programs hold this seed's weights as constants, so no later run
    # can use them: keep them out of the persistent cache (disk writes)
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    try:
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
            list(ex.map(one, jobs))
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
    return len(jobs)


def rehearse_drill(server, ir, down_sets: List[set], seed: int,
                   inputs, max_rows: int) -> int:
    """Replay the drill's repairs on a shallow copy of ``server`` (it shares
    the compiled slot forwards; ``migrate`` replaces fields and mutates
    nothing shared) and serve every row bucket after each, so that the
    shapes the window's repairs bring are compiled in set-up. The drill is
    scripted, so the window's controller makes the same repairs. Returns
    the number of repairs replayed."""
    from repro.core.simulator import FailureModel
    from repro.runtime.controller import ClusterController
    shadow = copy.copy(server)
    ctl = ClusterController(ir, server=shadow, seed=seed)
    rng = np.random.default_rng(1)
    n = 0
    for down in down_sets:
        if ctl.observe(down) is None:
            continue
        n += 1
        shadow.failure = FailureModel(forced_failures=sorted(down))
        extra_warmup(shadow, inputs, max_rows, rng=rng)
    return n


def extra_warmup(server, inputs, max_rows: int, *,
                 rng: Optional[np.random.Generator] = None) -> None:
    """Compile the per-row masking of every row bucket under the server's
    own failure model (the engine's warm-up serves one request per bucket,
    which never masks part of a batch)."""
    rng = np.random.default_rng(0) if rng is None else rng
    for b in buckets(max_rows):
        for _ in range(3):
            out = server.serve_batch([inputs.warm(1, i) for i in range(b)],
                                     rng=rng)
            if out:
                out[0].block_until_ready()


def _options():
    """Profiler options: device ops and the benchmark's own host spans,
    without Python's calls or the programs' HLO (a small, cheap trace)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


class CompileCounter:
    """Counts the compiles JAX reports: those while ``active`` with their
    time in the window, and all of them with the persistent cache's hits."""

    def __init__(self):
        self.active: Optional[Callable[[], float]] = None
        self.events: List[tuple] = []
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, name: str, secs: float, **kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.compiles += 1
            self.compile_s += secs
        kind = ("compile" if name.endswith("backend_compile_duration")
                else "cache" if "cache_retrieval" in name else None)
        if self.active is not None and kind is not None:
            self.events.append((self.active(), kw.get("fun_name", name),
                                secs, kind))

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def summary(self) -> str:
        return (f"{self.compiles} compiles ({self.compile_s:.1f} s), "
                f"{self.cache_hits} persistent-cache hits")


def serve_window(dep: deploy.Deployment, mix: Dict, seed: int,
                 seconds: float, *, counter: CompileCounter,
                 trace_dir: Optional[str] = None) -> Window:
    """Serve ``mix`` for ``seconds`` through the program's engine."""
    from repro.core.simulator import FailureModel
    from repro.runtime.controller import ClusterController
    from repro.runtime.engine import EngineConfig
    from repro.runtime.failures import FailureEvent, FailureInjector

    srv = dep.server
    times, sizes = T.arrivals(mix, seed, seconds)
    keep = sample(seed, len(times))
    inputs = dep.kind.inputs(dep.cfg, seed, sizes)
    # the fleet's own per-request outage channel (each device's p_out)
    srv.failure = FailureModel()

    drill = mix.get("drill")
    ctl = None
    kw: Dict[str, Any] = {}
    econf: Dict[str, Any] = {}
    if drill:
        names = list(dep.ir.device_names)
        groups = {d["group"]: deploy.group_members(dep.cfg, d["group"])
                  for d in drill.get("down", ())}
        down_sets = T.drill_down_sets(drill, names, groups, seconds)
        events = [FailureEvent(t, n, k)
                  for t, n, k in T.drill_events(down_sets)]
        ctl = ClusterController(dep.ir, server=srv,
                                injector=FailureInjector(events),
                                seed=int(drill.get("controller_seed", 0)))
        repairs: List[Dict] = []
        poll = ctl.poll

        def annotated_poll():
            before = srv.fc_weights.shape[1]
            with jax.profiler.TraceAnnotation("poll_repair"):
                out = poll()
            if out is not None:
                mig = srv.last_migration or {}
                width_changed = srv.fc_weights.shape[1] != before
                repairs.append({
                    "kind": out.kind, "wall_s": out.wall_s,
                    "t": time.perf_counter(),
                    "rejitted": (len(srv.portion_fns) if width_changed
                                 else len(mig.get("rejitted_slots", ()))),
                    "zeroed": len(mig.get("zeroed_slots", ()))})
            return out
        ctl.poll = annotated_poll
        kw["controller"] = ctl
        kw["failure_for"] = lambda down: FailureModel(
            forced_failures=sorted(down))
        econf["chaos_every"] = drill["tick_s"]
    else:
        repairs = []
    config = EngineConfig(seed=int(seed) % (1 << 32), **econf)

    max_rows = int(max(mix["sizes"])) * config.max_batch
    t = time.perf_counter()
    n = precompile(srv, inputs, max_rows)
    t_pre = time.perf_counter() - t
    if drill:
        n_rep = rehearse_drill(srv, dep.ir, down_sets,
                               int(drill.get("controller_seed", 0)), inputs,
                               max_rows)
        print(f"set-up: {n_rep} drill repairs rehearsed", flush=True)
    recorder = Recorder(srv, keep)
    extra_warmup(srv, inputs, max_rows)
    print(f"set-up: {n} slot programs compiled in {t_pre:.1f} s, then "
          f"warm-up {time.perf_counter() - t - t_pre:.1f} s; "
          f"{counter.summary()}", flush=True)

    state = {"trace": None}
    span = None
    if trace_dir is not None:
        # the window's last few seconds: stopping the profiler holds the
        # host for ~18 s on a v5e, so it stops once the window has closed
        span = (seconds - min(3.0, 0.3 * seconds), seconds)

    def on_dispatch(real: float) -> None:
        if span is None:
            return
        if state["trace"] is None and real >= span[0]:
            jax.profiler.start_trace(trace_dir, profiler_options=_options())
            state["trace"] = [real, None]
        elif state["trace"] is not None and state["trace"][1] is None \
                and real >= span[1]:
            jax.profiler.stop_trace()
            state["trace"][1] = real

    Engine = make_engine_class()
    engine = Engine(srv, config, recorder=recorder, inputs=inputs,
                    seconds=seconds, drain_s=float(mix.get("drain_s", 0.0)),
                    on_dispatch=on_dispatch, **kw)
    counter.events = []
    counter.active = lambda: (time.perf_counter() - engine.t0
                              if engine.t0 is not None else -1.0)
    # set-up's garbage is never collected inside the window
    gc.collect()
    gc.freeze()
    try:
        engine.run(times, sizes)
    except WindowClosed:
        pass
    finally:
        counter.active = None
        if state["trace"] is not None and state["trace"][1] is None:
            jax.profiler.stop_trace()
            state["trace"][1] = time.perf_counter() - engine.t0
        srv.serve_batch = recorder.inner
    t0 = engine.t0
    n = len(times)
    t_dispatch = np.full(n, np.inf)
    t_done = np.full(n, np.inf)
    quorum_ok = np.zeros(n, bool)
    for rec in engine.dispatched:
        t_dispatch[rec.rid] = rec.t_dispatch
        t_done[rec.rid] = rec.t_done
        quorum_ok[rec.rid] = rec.quorum_ok
    for r in repairs:
        r["t"] -= t0
    compiles = [c for c in counter.events if c[0] >= 0]
    return Window(
        seconds=seconds, setup_end=t0, t_arrival=times, sizes=sizes,
        t_dispatch=t_dispatch, t_done=t_done, quorum_ok=quorum_ok,
        batches=engine.infos, repairs=repairs,
        lateness=np.asarray(engine.lateness), compiles=compiles,
        kept=recorder.kept, inputs=inputs, failed=recorder.failed,
        trace_span=tuple(state["trace"]) if state["trace"] else None)
