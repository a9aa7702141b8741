"""Observability plane (src/repro/obs): tracer span invariants, P² sketch
accuracy contract, registry scoping, export round-trips, the zero-overhead
(bit-identity) contract on engine and fleet runs, and the offline
critical-path/timeline analyzer. All seeded — part of the CI fast lane."""
import json

import numpy as np
import pytest

from repro.core.scenarios import MMPPArrivals, PoissonArrivals
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               P2Quantile)
from repro.obs.report import (critical_path, failure_timeline, load_trace,
                              render_report, request_paths)
from repro.obs.stats import latency_summary, percentile, throughput
from repro.obs.trace import Tracer, load_chrome, load_jsonl
from repro.runtime.controller import ClusterController
from repro.runtime.engine import (EngineConfig, ServingEngine,
                                  build_demo_server)
from repro.runtime.failures import FailureInjector, markov_flap_schedule
from repro.runtime.fleet import (FleetController, FleetEngine, FleetRouter,
                                 SLOClass, TenantSpec)
from tests.test_clock import _reports_identical
from tests.test_engine import _toy_ir


# -- stats: the one percentile convention -------------------------------------

def test_percentile_convention_and_edge_cases():
    xs = np.random.default_rng(0).exponential(size=257)
    # the repo-wide convention IS numpy linear interpolation
    assert percentile(xs, 99) == float(np.percentile(xs, 99))
    assert percentile([], 99) == float("inf")        # empty -> unservable
    assert percentile([0.25], 50) == 0.25            # single sample: itself
    assert percentile([0.25], 99) == 0.25


def test_throughput_and_latency_summary():
    assert throughput(0, 0.0, 1.0) == 0.0
    assert throughput(10, 0.0, 2.0) == 5.0
    assert throughput(1, 1.0, 1.0) > 0               # zero span guarded
    s = latency_summary([0.1, 0.2, 0.3, 0.4], slo=0.35)
    assert s["p50"] == pytest.approx(0.25)
    assert s["slo_attainment"] == pytest.approx(0.75)
    assert latency_summary([])["p99"] == float("inf")


# -- P² quantile sketch -------------------------------------------------------

def test_p2_exact_up_to_five_samples():
    sk = P2Quantile(0.5)
    assert np.isnan(sk.value())
    for xs in ([3.0], [3.0, 1.0], [3.0, 1.0, 2.0], [3.0, 1.0, 2.0, 9.0]):
        sk = P2Quantile(0.5)
        for x in xs:
            sk.observe(x)
        assert sk.value() == percentile(xs, 50)      # exact, same convention


def test_p2_rejects_degenerate_quantile():
    with pytest.raises(ValueError):
        P2Quantile(0.0)
    with pytest.raises(ValueError):
        P2Quantile(1.0)


@pytest.mark.parametrize("draw,p50_tol,p99_tol", [
    (lambda rng, n: rng.uniform(0.0, 1.0, n), 0.05, 0.15),
    (lambda rng, n: rng.exponential(1.0, n), 0.05, 0.15),
])
def test_p2_accuracy_contract(draw, p50_tol, p99_tol):
    """The documented bound: ≲5% on p50, ≲15% on p99 for smooth unimodal
    shapes at a few thousand samples."""
    rng = np.random.default_rng(42)
    xs = draw(rng, 4000)
    h = Histogram()
    for x in xs:
        h.observe(x)
    for q, tol in ((0.5, p50_tol), (0.99, p99_tol)):
        exact = percentile(xs, 100 * q)
        assert abs(h.quantile(q) - exact) / exact <= tol
    assert h.count == 4000
    assert h.min == xs.min() and h.max == xs.max()


def test_registry_scoping_and_type_guard():
    m = MetricsRegistry()
    m.counter("reqs", tenant="a").inc()
    m.counter("reqs", tenant="b").inc(2)
    assert m.counter("reqs", tenant="a").value == 1.0
    assert m.counter("reqs", tenant="b").value == 2.0
    m.gauge("depth").set(3.0)
    with pytest.raises(TypeError):
        m.histogram("reqs", tenant="a")              # name/type collision
    rows = m.collect()
    assert {r["type"] for r in rows} == {"counter", "gauge"}
    assert sorted(r["labels"].get("tenant", "") for r in rows
                  if r["name"] == "reqs") == ["a", "b"]
    assert isinstance(m.gauge("depth"), Gauge)
    assert isinstance(m.counter("reqs", tenant="a"), Counter)


# -- tracer unit invariants ---------------------------------------------------

def test_tracer_stack_discipline_enforced_at_record_time():
    tr = Tracer()
    outer = tr.begin("outer", "lane", t=0.0)
    inner = tr.begin("inner", "lane", t=0.1)
    with pytest.raises(RuntimeError, match="innermost"):
        tr.end(outer, t=0.2)                         # inner still open
    tr.end(inner, t=0.2)
    tr.end(outer, t=0.3)
    assert tr.open_spans() == []
    assert outer.contains(inner)
    assert not inner.contains(outer)


def test_tracer_seq_windows_certify_containment():
    tr = Tracer()
    sp = tr.begin("repair", "controller", t=0.0)
    bump = tr.instant("plan_epoch", "controller", t=0.0, epoch=1)
    tr.end(sp, t=0.1)
    outside = tr.instant("plan_epoch", "controller", t=0.05, epoch=2)
    assert sp.contains(bump)
    assert not sp.contains(outside)                  # time alone would lie


def test_chrome_and_jsonl_round_trips(tmp_path):
    tr = Tracer()
    a = tr.begin("request", "req/0", t=0.0, rid=0, bad=float("inf"))
    tr.instant("quorum_complete", "req/0", t=0.5, down={"b", "a"})
    tr.end(a, t=0.5)
    tr.complete("batch", "batches", 0.0, 0.5, bid=0)
    tr.begin("dangling", "batches", t=0.6)           # stays open on purpose
    for dump, load in ((tr.dump_chrome, load_chrome),
                       (tr.dump_jsonl, load_jsonl)):
        path = tmp_path / "t.trace.json"
        dump(str(path))
        back = load(str(path))
        assert [e.name for e in back] == [e.name for e in tr.events]
        assert all(abs(b.t - e.t) <= 1e-9
                   for b, e in zip(back, tr.events))
        by = {e.name: e for e in back}
        assert by["request"].attrs["bad"] == "inf"   # strict-JSON coercion
        assert by["quorum_complete"].attrs["down"] == ["a", "b"]
        assert by["dangling"].attrs.get("open") is True
        assert by["batch"].seq == by["batch"].end_seq
    # strict JSON throughout: no NaN/Infinity literals survive
    json.loads((tmp_path / "t.trace.json").read_text().splitlines()[0])


# -- instrumented runs: invariants + bit-identity -----------------------------

def _chaos_engine(tracer=None, metrics=None):
    ir = _toy_ir()
    srv = build_demo_server(ir, feat=8, hidden=16, n_classes=3, seed=0)
    events = markov_flap_schedule(list(ir.device_names), 0.2, 0.5, 60,
                                  np.random.default_rng(7))
    injector = FailureInjector(events)
    ctl = ClusterController(ir, server=srv, injector=injector, seed=0)
    cfg = EngineConfig(max_batch=8, max_wait=0.01, slo=0.2,
                       service_model=(2e-3, 1e-4), input_dim=8, seed=0,
                       chaos_every=0.02, pipeline_depth=2)
    return ServingEngine(srv, cfg, controller=ctl, tracer=tracer,
                         metrics=metrics)


def _chaos_trace():
    gen = MMPPArrivals(rates=(100.0, 1500.0), dwell=(0.05, 0.02),
                       sizes=(1, 2))
    return gen.generate(np.random.default_rng(3), 0.4)


def test_tracing_off_is_bit_identical_to_tracing_on():
    """The zero-overhead contract: attaching the obs plane changes no
    record, batch or migration — field for field."""
    times, sizes = _chaos_trace()
    plain = _chaos_engine().run(times, sizes)
    traced = _chaos_engine(tracer=Tracer(),
                           metrics=MetricsRegistry()).run(times, sizes)
    _reports_identical(plain, traced)


def test_chaos_run_span_invariants():
    tr, m = Tracer(), MetricsRegistry()
    times, sizes = _chaos_trace()
    eng = _chaos_engine(tracer=tr, metrics=m)
    rep = eng.run(times, sizes)

    # every admitted request: exactly one CLOSED root span, matching times
    assert tr.open_spans() == []
    done = [r for r in rep.records if np.isfinite(r.t_done)]
    roots = tr.spans("request")
    assert len(roots) == len(done) == len(rep.records)
    by_rid = {int(s.attrs["rid"]): s for s in roots}
    for r in done:
        s = by_rid[r.rid]
        assert s.t == r.t_arrival and s.t_end == pytest.approx(r.t_done)
        assert s.attrs["outcome"] in ("quorum_complete", "degraded")

    # batch_wait + service sum to the measured latency, per request
    for p in request_paths(tr.events):
        segs = dict(p.segments)
        assert set(segs) <= {"batch_wait", "service", "share_wait",
                             "merge_tail"}
        assert sum(segs.values()) == pytest.approx(p.latency, abs=1e-9)

    # per-track discipline holds globally: spans on one stack-disciplined
    # track nest or are disjoint — they never partially overlap. Batch
    # spans are exempt by design: under pipeline_depth > 1 consecutive
    # micro-batches legitimately run concurrently on the batches track,
    # bounded by the configured depth.
    by_track = {}
    for e in tr.events:
        if e.phase == "X":
            by_track.setdefault(e.track, []).append(e)
    for track, spans in by_track.items():
        if track.endswith("batches"):
            depth = eng.cfg.pipeline_depth
            for s in spans:
                live = sum(1 for o in spans
                           if o.t < s.t_end - 1e-12 and s.t < o.t_end - 1e-12)
                assert live <= depth
            continue
        spans = sorted(spans, key=lambda s: (s.t, -s.t_end))
        for a, b in zip(spans, spans[1:]):
            assert b.t >= a.t_end - 1e-12 or \
                (a.t <= b.t and b.t_end <= a.t_end + 1e-12)

    # controller repair spans bracket their plan-epoch bump (seq windows)
    repairs = [s for s in tr.spans(track="controller")
               if s.name in ("repair", "full_replan", "reencode", "noop")]
    bumps = tr.instants("plan_epoch", "controller")
    assert len(repairs) == len(rep.migrations) == len(bumps) > 0
    for sp, bump in zip(repairs, bumps):
        assert sp.contains(bump)
        assert sp.attrs["epoch"] == bump.attrs["epoch"]

    # chaos instants + one batch span per record + migrate instants landed
    assert len(tr.instants("chaos_tick", "chaos")) > 0
    spans = tr.spans("batch", "batches")
    assert len(spans) == len(rep.batches)
    for s, b in zip(sorted(spans, key=lambda s: s.attrs["bid"]),
                    rep.batches):
        assert s.attrs["bid"] == b.bid and s.attrs["rows"] == b.rows
        assert s.attrs["pad_rows"] == b.pad_rows
        assert s.t == b.t_dispatch and s.t_end == pytest.approx(b.t_done)
    assert any(b.pad_rows for b in rep.batches)
    assert all((b.rows + b.pad_rows) & (b.rows + b.pad_rows - 1) == 0
               for b in rep.batches)
    assert len(tr.instants("migrate", "server")) == len(rep.migrations)

    # metrics agree with the report within the documented sketch error
    s = rep.summary()
    assert m.counter("requests_served").value == s["n"]
    sketch = m.histogram("request_latency_s").quantile(0.99)
    assert abs(sketch - s["p99"]) / s["p99"] <= 0.15


def test_shed_requests_get_terminal_shed_span():
    """A same-instant burst behind pipeline_depth=1: the overflow is shed
    by admission control and must close with a zero-duration terminal
    ``shed`` span (still exactly one closed root per request)."""
    ir = _toy_ir()
    srv = build_demo_server(ir, feat=8, hidden=16, n_classes=3, seed=0)
    pred = float(ir.objective())
    cfg = EngineConfig(max_batch=8, max_wait=0.01, slo=pred + 1e-3,
                       service_model=(2e-3, 1e-4), input_dim=8, seed=0,
                       pipeline_depth=1, admission=True)
    tr = Tracer()
    eng = ServingEngine(srv, cfg, tracer=tr, metrics=MetricsRegistry())
    rep = eng.run(np.zeros(20), np.ones(20, np.int64))
    shed = [r for r in rep.records if r.rejected]
    assert len(shed) > 0 and len(shed) < 20
    assert tr.open_spans() == []
    assert len(tr.spans("request")) == 20            # one root each, closed
    terms = tr.spans("shed")
    assert len(terms) == len(shed)
    assert all(t.dur == 0.0 for t in terms)
    for r in shed:
        root = next(s for s in tr.spans("request")
                    if s.attrs["rid"] == r.rid)
        assert root.attrs["outcome"] == "shed"
    assert eng.metrics.counter("requests_shed").value == len(shed)
    # shed requests are excluded from critical paths unless asked for
    assert all(p.outcome != "shed" for p in request_paths(tr.events))
    got = request_paths(tr.events, include_shed=True)
    assert sum(1 for p in got if p.outcome == "shed") == len(shed)


# -- fleet: tracer threaded through lanes, router, broker ---------------------

def _tenant(name, ir, slo_cls, seed=0):
    srv = build_demo_server(ir, feat=8, hidden=16, n_classes=3, seed=0)
    ctl = ClusterController(ir, server=srv, seed=0, require_feasible=False)
    cfg = EngineConfig(max_batch=8, max_wait=0.01, slo=slo_cls.slo,
                       service_model=(2e-3, 1e-4), input_dim=8, seed=0,
                       pipeline_depth=2)
    return TenantSpec(name, srv, controller=ctl, slo=slo_cls, config=cfg)


def test_fleet_traced_run_and_bit_identity():
    from tests.test_fleet import _tenant_ir

    def build(tracer=None, metrics=None):
        tenants = [
            _tenant("gold", _tenant_ir("g"), SLOClass("gold", 0.2, 4.0)),
            _tenant("bulk", _tenant_ir("b"), SLOClass("bronze", 0.2, 1.0)),
        ]
        injector = FailureInjector(markov_flap_schedule(
            [d for t in ("g", "b") for d in
             (f"{t}-a", f"{t}-b", f"{t}-c", f"{t}-d")],
            0.2, 0.5, 30, np.random.default_rng(7)))
        fc = FleetController(tenants, [])
        return FleetEngine(tenants, router=FleetRouter("predicted"),
                           fleet_controller=fc, injector=injector,
                           chaos_every=0.02, seed=0,
                           tracer=tracer, metrics=metrics)

    traces = [PoissonArrivals(300.0).generate(np.random.default_rng(s), 0.3)
              for s in (2, 5)]
    plain = build().run([(t, s) for t, s in traces])
    tr, m = Tracer(), MetricsRegistry()
    traced = build(tracer=tr, metrics=m).run([(t, s) for t, s in traces])

    # bit-identity per tenant
    for a, b in zip(plain.reports, traced.reports):
        _reports_identical(a, b)

    # lane spans carry the tenant prefix; fleet tracks carry fleet events
    assert tr.open_spans() == []
    assert len(tr.spans("request")) == sum(len(r.records)
                                           for r in traced.reports)
    tenants_seen = {p.tenant for p in request_paths(tr.events)}
    assert tenants_seen == {"gold", "bulk"}
    routes = tr.instants("route", "fleet/router")
    assert len(routes) == sum(len(r.batches) for r in traced.reports)
    assert all(r.attrs["policy"] == "predicted" for r in routes)
    assert {r.attrs["picked"] for r in routes} == {"gold", "bulk"}
    assert len(tr.instants("chaos_tick", "fleet/chaos")) > 0
    # repairs landed on per-tenant controller tracks
    n_rep = sum(len(r.migrations) for r in traced.reports)
    assert sum(len(tr.spans(track=f"{t}/controller"))
               for t in ("gold", "bulk")) == n_rep
    # metrics scoped per tenant + slo class
    assert m.counter("requests_served", tenant="gold",
                     slo_class="gold").value > 0
    assert m.counter("requests_served", tenant="bulk",
                     slo_class="bronze").value > 0


def test_fleet_spare_claims_traced():
    """The cross-tenant contention scenario with the tracer attached: the
    broker's exclusive claim shows up as a ``spare_claim`` instant on the
    fleet/spares track, attributed to the winning tenant."""
    from tests.test_fleet import _cfg as fleet_cfg
    from tests.test_fleet import _spare, _tenant_ir
    from repro.runtime.failures import FailureEvent
    spare = _spare("spare-0")
    ir_a = _tenant_ir("ta", [spare], p_out=0.7)
    ir_b = _tenant_ir("tb", [spare, _spare("tb-priv")], p_out=0.7)
    srv_a = build_demo_server(ir_a, feat=8, hidden=16, n_classes=3, seed=0)
    srv_b = build_demo_server(ir_b, feat=8, hidden=16, n_classes=3, seed=0)
    ctl_a = ClusterController(ir_a, server=srv_a, seed=0)
    ctl_b = ClusterController(ir_b, server=srv_b, seed=0,
                              require_feasible=False)
    tenants = [TenantSpec("ta", srv_a, controller=ctl_a,
                          slo=SLOClass("gold", slo=0.2, weight=4.0),
                          config=fleet_cfg(admission=False)),
               TenantSpec("tb", srv_b, controller=ctl_b,
                          slo=SLOClass("bronze", slo=0.2, weight=1.0),
                          config=fleet_cfg(admission=False))]
    fc = FleetController(tenants, ["spare-0"])
    injector = FailureInjector([
        FailureEvent(0, d) for d in ("ta-a", "ta-b", "tb-a", "tb-b")])
    tr = Tracer()
    fleet = FleetEngine(tenants, fleet_controller=fc, injector=injector,
                        chaos_every=0.02, seed=0, tracer=tr)
    fleet.run([(np.arange(0.03, 0.3, 0.005), None),
               (np.arange(0.032, 0.3, 0.005), None)])
    claims = tr.instants("spare_claim", "fleet/spares")
    assert any(c.attrs["device"] == "spare-0" and c.attrs["tenant"] == "ta"
               for c in claims)
    # and the timeline analyzer surfaces the whole story in order
    rows = failure_timeline(tr.events)
    whats = [w for _, _, w, _ in rows]
    assert "chaos_tick" in whats and "failure_observed" in whats
    assert "spare_claim" in whats
    assert any(w in ("repair", "full_replan") for w in whats)
    ts = [t for t, _, _, _ in rows]
    assert ts == sorted(ts)


# -- offline analyzer ---------------------------------------------------------

def test_critical_path_segments_sum_to_measured_latency(tmp_path):
    tr = Tracer()
    times, sizes = _chaos_trace()
    rep = _chaos_engine(tracer=tr).run(times, sizes)
    s = rep.summary()
    cp = critical_path(tr.events, q=99.0)
    assert cp.n == s["n"]
    assert cp.target_latency == pytest.approx(s["p99"])  # same convention
    seg_sum = sum(d for _, d in cp.path.segments)
    assert seg_sum == pytest.approx(cp.path.latency, abs=1e-9)
    # the picked request is a real one with a real latency
    real = next(r for r in rep.records if r.rid == cp.path.rid)
    assert cp.path.latency == pytest.approx(real.latency)

    # render + round-trip the whole report through both file formats
    text = render_report(tr.events, q=99.0, timeline_limit=5)
    assert "critical path" in text and "timeline" in text
    for dump, name in ((tr.dump_chrome, "t.trace.json"),
                       (tr.dump_jsonl, "t.jsonl")):
        path = tmp_path / name
        dump(str(path))
        back = load_trace(str(path))
        cp2 = critical_path(back, q=99.0)
        assert cp2.path.rid == cp.path.rid
        assert cp2.path.latency == pytest.approx(cp.path.latency)


def test_trace_report_cli(tmp_path, capsys):
    import scripts.trace_report as cli
    tr = Tracer()
    times, sizes = _chaos_trace()
    _chaos_engine(tracer=tr).run(times, sizes)
    path = tmp_path / "run.trace.json"
    tr.dump_chrome(str(path))
    assert cli.main([str(path), "-q", "50", "--timeline-limit", "3"]) == 0
    out = capsys.readouterr().out
    assert "p50 critical path" in out
    assert "failure/repair timeline" in out


def test_engine_report_percentiles_route_through_stats():
    """The dedupe satellite: EngineReport.summary's p50/p99 are exactly
    the shared convention (no drift between report and analyzer)."""
    times, sizes = _chaos_trace()
    rep = _chaos_engine().run(times, sizes)
    s = rep.summary()
    lats = [r.latency for r in rep.records if np.isfinite(r.t_done)]
    assert s["p99"] == percentile(lats, 99)
    assert s["p50"] == percentile(lats, 50)


def test_tracer_state_does_not_leak_across_runs():
    """Per-run request-span bookkeeping is reset: a second run on the same
    engine appends a full second trace and still closes every span (the
    controller's plan state legitimately carries over, so the second run's
    event count may differ)."""
    tr = Tracer()
    times, sizes = _chaos_trace()
    eng = _chaos_engine(tracer=tr)
    rep1 = eng.run(times, sizes)
    n1 = len(tr.events)
    n_roots1 = len(tr.spans("request"))
    assert n_roots1 == len(rep1.records)
    rep2 = eng.run(times, sizes)
    assert tr.open_spans() == []
    assert len(tr.events) > n1
    assert len(tr.spans("request")) == n_roots1 + len(rep2.records)


# -- real-clock spans: the profiler's host plane ------------------------------

ENGINE_PHASES = ("engine.control", "engine.inputs", "engine.device_wait",
                 "engine.record")
LOOP_PHASES = ("server.stack", "server.draw", "server.slot_forward",
               "server.merge", "server.package")


def _profiled(log_dir, fn):
    """``fn()`` under the JAX profiler into ``log_dir``: its result and the
    program's host spans ``(name, start_ns, end_ns, stats)`` by start."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        out = fn()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("engine.", "server."))]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _lossy_server(fastpath):
    from repro.core.simulator import FailureModel
    return build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3,
                             seed=0, fastpath=fastpath,
                             failure=FailureModel(crash_prob=0.5,
                                                  outages=False))


@pytest.fixture(scope="module")
def loop_run(tmp_path_factory):
    """The per-slot loop behind the engine on the real clock (measured
    wall), once to compile, then again under the profiler with no
    warm-up, so that every server call is one of the engine's batches."""
    import dataclasses
    srv = _lossy_server(fastpath=False)
    rng = np.random.default_rng(4)
    times = np.sort(rng.uniform(0.0, 0.03, 40))
    sizes = rng.integers(1, 3, 40)
    cfg = EngineConfig(max_batch=4, max_wait=0.002, input_dim=8, seed=0)
    ServingEngine(srv, cfg).run(times, sizes)
    eng = ServingEngine(srv, dataclasses.replace(cfg, warmup=False))
    return _profiled(tmp_path_factory.mktemp("loop"),
                     lambda: eng.run(times, sizes))


def test_loop_phases_nest_in_engine_batch_with_their_stats(loop_run):
    rep, spans = loop_run
    batches = [s for s in spans if s[0] == "engine.batch"]
    assert [b[3] for b in batches] == [
        {"bid": b.bid, "n_requests": b.n_requests, "rows": b.rows,
         "pad_rows": b.pad_rows, "plan_epoch": b.plan_epoch}
        for b in rep.batches]
    names = {s[0] for s in spans}
    assert set(ENGINE_PHASES + LOOP_PHASES) <= names
    assert not {"server.fused_step", "server.slot_mask"} & names
    masked = []
    for name, t0, t1, stats in spans:
        if name == "engine.batch":
            continue
        outer = [b for b in batches if b[1] <= t0 and t1 <= b[2]]
        assert len(outer) == 1, name
        if name == "server.slot_forward":
            assert list(stats) == ["slot"] and 0 <= stats["slot"] < 2
        elif name == "server.merge":
            assert list(stats) == ["masked_slots"]
            masked.append(stats["masked_slots"])
        else:
            assert stats == {}
    # one merge a batch; the lossy server masks part of some batches
    assert len(masked) == len(batches)
    assert all(0 <= n <= 2 for n in masked) and max(masked) > 0
    # each batch runs each arrived slot's forward once, in slot order
    for b in batches:
        slots = [s[3]["slot"] for s in spans
                 if s[0] == "server.slot_forward" and b[1] <= s[1] < b[2]]
        assert slots == sorted(set(slots))


def test_loop_phases_cover_the_batch(loop_run):
    """The phases leave little of a batch unnamed: the engine's own
    phases and the server's top-level ones, which follow one another on
    one thread, cover >= 90 % of the ``engine.batch`` spans."""
    _, spans = loop_run
    top = set(ENGINE_PHASES + LOOP_PHASES)
    batch_ns = sum(s[2] - s[1] for s in spans if s[0] == "engine.batch")
    phase_ns = sum(s[2] - s[1] for s in spans if s[0] in top)
    assert batch_ns > 0 and 0.9 * batch_ns <= phase_ns <= batch_ns


@pytest.mark.parametrize("fastpath", [False, True])
def test_profiler_on_is_bit_identical_and_names_the_path(tmp_path,
                                                         fastpath):
    srv = _lossy_server(fastpath)
    rng = np.random.default_rng(9)
    xs = [rng.standard_normal((s, 8)).astype(np.float32)
          for s in (1, 2, 1, 3, 2, 1, 1, 2)]

    def serve():
        out = srv.serve_batch(xs, rng=np.random.default_rng(5))
        return ([r.logits for r in out], [r.arrived for r in out])
    off = serve()
    on, spans = _profiled(tmp_path, serve)
    for a, b in zip(off[0] + off[1], on[0] + on[1]):
        np.testing.assert_array_equal(a, b)
    assert not all(a.all() for a in on[1])         # some requests masked
    names = {s[0] for s in spans}
    assert ("server.fused_step" in names) == fastpath
    assert ("server.slot_forward" in names) != fastpath
    assert {"server.stack", "server.draw", "server.package"} <= names


def test_slot_programs_are_named_by_slot():
    import re
    srv = _lossy_server(fastpath=False)
    x = np.zeros((2, 8), np.float32)
    fns = srv.jitted_portions
    assert len(fns) == 2
    for k, fn in enumerate(fns):
        text = fn.lower(x).compile().as_text()
        assert re.match(rf"HloModule jit_padded_s{k}\b", text), text[:80]
