"""Fused serving fast path: fixed-seed bit-identity of the single-dispatch
megastep vs the legacy per-slot loop (including across live migrations), the
int8 weight-only deployment within asserted tolerance on the fig-3 fleet,
the lazy/deferred ServeResult semantics, and the new kernel paths (int8
quorum_aggregate, fused dequant-matmul). All seeded — CI fast lane."""
import numpy as np
import pytest

from repro.core.assignment import StudentArch
from repro.core.grouping import Device
from repro.core.plan_ir import (PlanIR, device_matrix, eq1a_latency,
                                student_matrix)
from repro.core.simulator import FailureModel
from repro.runtime.engine import build_demo_server


def _toy_ir(M=8):
    devs = [Device("a", 1e7, 2e6, 500, 0.3), Device("b", 2e7, 2e6, 500, 0.3),
            Device("c", 1e7, 2e6, 500, 0.3), Device("d", 3e7, 2e6, 500, 0.3)]
    names, dcaps = device_matrix(devs)
    snames, scaps = student_matrix(
        [StudentArch("s", 5e6, 0.6e6, 64, 0.15e6)])
    member = np.array([[1, 1, 0, 0], [0, 0, 1, 1]], bool)
    part = np.zeros((2, M), bool)
    part[0, :M // 2] = True
    part[1, M // 2:] = True
    return PlanIR(names, dcaps, snames, scaps, member, part,
                  np.zeros(2, np.int64), np.arange(2, dtype=np.int64),
                  eq1a_latency(scaps, dcaps), np.zeros((M, M)), 1.0, 0.5)


def _pair(ir=None, **kw):
    """(fused, legacy) demo servers over identical weights."""
    ir = ir if ir is not None else _toy_ir()
    build = dict(feat=8, hidden=16, n_classes=3, seed=0, **kw)
    return (build_demo_server(ir, **build),
            build_demo_server(ir, fastpath=False, **build))


def _x(rows=3, feat=8, seed=5):
    return np.random.default_rng(seed).normal(
        size=(rows, feat)).astype(np.float32)


# -- fp32 bit-identity vs the legacy oracle -----------------------------------

def test_fused_is_active_and_legacy_is_not():
    fused, legacy = _pair()
    assert fused.fastpath_active and not legacy.fastpath_active


def test_fastpath_true_without_export_raises():
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0)
    srv.fused = None
    srv.fastpath = True
    with pytest.raises(ValueError, match="no stacked student export"):
        srv.serve_batch([_x()])


def test_fused_bit_identical_to_legacy_clean_batch():
    fused, legacy = _pair()
    xs = [_x(3), _x(5, seed=9), _x(1, seed=11), _x(2, seed=13)]
    rf = fused.serve_batch(xs, rng=np.random.default_rng(7))
    rl = legacy.serve_batch(xs, rng=np.random.default_rng(7))
    for a, b in zip(rf, rl):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.latency == b.latency
        assert (a.arrived == b.arrived).all()
        assert a.degraded == b.degraded


@pytest.mark.parametrize("down", [["a"], ["a", "b"], ["a", "b", "c", "d"]])
def test_fused_bit_identical_under_failures(down):
    fused, legacy = _pair()
    for srv in (fused, legacy):
        srv.failure = FailureModel(forced_failures=down, outages=False)
    xs = [_x(3), _x(4, seed=9)]
    rf = fused.serve_batch(xs, rng=np.random.default_rng(3))
    rl = legacy.serve_batch(xs, rng=np.random.default_rng(3))
    for a, b in zip(rf, rl):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.degraded == b.degraded
        assert a.failed_devices == b.failed_devices


def test_fused_bit_identical_under_stochastic_outages():
    fused, legacy = _pair()
    for srv in (fused, legacy):
        srv.failure = FailureModel(outages=True)
    for trial in range(5):
        rng_f = np.random.default_rng(trial)
        rng_l = np.random.default_rng(trial)
        a = fused.serve_batch([_x()], rng=rng_f)[0]
        b = legacy.serve_batch([_x()], rng=rng_l)[0]
        np.testing.assert_array_equal(a.logits, b.logits)
        assert (a.arrived == b.arrived).all()


# -- the per-slot loop: one compiled mask-and-stack program -------------------

_LOOP_FAILURES = {
    "clean": FailureModel(outages=False),
    "stochastic": FailureModel(crash_prob=0.5, outages=False),
    "slot_unreached": FailureModel(forced_failures=["a", "b"], outages=False),
}


def _eager_loop_logits(srv, xs, arrived):
    """The loop as a sequence of eager ops: each arrived slot's forward,
    its missed rows zeroed by an eager multiply, zeros for a slot no row
    received, ``jnp.stack`` and the ``quorum_aggregate`` launch."""
    import jax.numpy as jnp

    from repro.kernels import ops
    sizes = [len(x) for x in xs]
    x = jnp.asarray(np.concatenate(xs))
    row_arrived = np.repeat(arrived, sizes, axis=0)
    any_arrived = arrived.any(axis=0)
    Dk = srv.fc_weights.shape[1]
    portions = []
    for k, fn in enumerate(srv.jitted_portions):
        if not any_arrived[k]:
            portions.append(jnp.zeros((len(x), Dk), jnp.float32))
            continue
        p = fn(x)
        if not row_arrived[:, k].all():
            p = p * jnp.asarray(row_arrived[:, k, None], p.dtype)
        portions.append(p)
    return np.asarray(ops.quorum_aggregate(
        jnp.stack(portions), srv.fc_weights, srv.fc_bias,
        jnp.asarray(any_arrived, jnp.int32)))


@pytest.mark.parametrize("sizes", [(1, 2, 1), (3, 1, 2, 4, 1, 2, 2, 1)])
@pytest.mark.parametrize("case", sorted(_LOOP_FAILURES))
def test_loop_bit_identical_to_eager_sequence(case, sizes):
    _, legacy = _pair()
    legacy.failure = _LOOP_FAILURES[case]
    xs = [_x(n, seed=20 + i) for i, n in enumerate(sizes)]
    res = legacy.serve_batch(xs, rng=np.random.default_rng(6))
    arrived = np.stack([r.arrived for r in res])
    partly = arrived.any(axis=0) & ~arrived.all(axis=0)
    assert {"clean": arrived.all(),
            "stochastic": partly.any(),
            "slot_unreached": not arrived[:, 0].any()}[case]
    got = np.concatenate([r.logits for r in res])
    np.testing.assert_array_equal(got,
                                  _eager_loop_logits(legacy, xs, arrived))


def test_loop_masks_clean_and_masked_batches_with_one_program():
    _, legacy = _pair()
    xs = [_x(2), _x(2, seed=9)]                    # 4 rows
    legacy.failure = FailureModel(outages=False)
    assert legacy.serve_batch(xs)[0].arrived.all()
    legacy.failure = FailureModel(forced_failures=["c", "d"], outages=False)
    assert not legacy.serve_batch(xs)[0].arrived.all()
    legacy.failure = FailureModel(crash_prob=0.5, outages=False)
    for seed in range(3):
        legacy.serve_batch(xs, rng=np.random.default_rng(seed))
    assert legacy._mask_stack._cache_size() == 1   # one per (B, K, Dk)
    legacy.failure = FailureModel(outages=False)
    legacy.serve_batch(xs + [_x(4)])               # 8 rows: a new shape
    assert legacy._mask_stack._cache_size() == 2
    # the unreached slot's zeros were made once for the 4-row shape
    assert list(legacy._zero_portions) == [(4, legacy.fc_weights.shape[1])]


# -- bit-identity across live migrations --------------------------------------

def test_fused_bit_identity_survives_remove_repair_migrate():
    """remove_device → controller repair → migrate on the FUSED server must
    serve logits bit-identical to a fresh fused server AND to the legacy
    loop on the repaired plan."""
    fused, _ = _pair()
    x = _x()
    fused.serve_batch([x], rng=np.random.default_rng(0))  # stacked built
    fused.remove_device("a")
    out = fused.remove_device("b")
    assert out is not None and out.kind == "repair"
    assert fused.fastpath_active
    fresh = build_demo_server(fused.ir, feat=8, hidden=16, n_classes=3, seed=0)
    oracle = build_demo_server(fused.ir, feat=8, hidden=16, n_classes=3,
                               seed=0, fastpath=False)
    r_mig = fused.serve_batch([x], rng=np.random.default_rng(7))[0]
    r_new = fresh.serve_batch([x], rng=np.random.default_rng(7))[0]
    r_ora = oracle.serve_batch([x], rng=np.random.default_rng(7))[0]
    assert r_mig.arrived.all()
    np.testing.assert_array_equal(r_mig.logits, r_new.logits)
    np.testing.assert_array_equal(r_mig.logits, r_ora.logits)
    assert r_mig.latency == r_new.latency


def test_partition_reshape_rebuilds_only_touched_fused_rows():
    """A reshape refit from the weight store must rewrite exactly the
    touched rows of the stacked pytree and stay bit-identical to a fresh
    server — both when the stack is already built and when it is lazy."""
    for prebuild in (True, False):
        srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3,
                                seed=0)
        x = _x()
        if prebuild:
            srv.serve_batch([x], rng=np.random.default_rng(0))
            assert srv._fused_stacked is not None
        new_part = np.zeros((2, srv.ir.M), bool)
        new_part[0, :5] = True
        new_part[1, 5:] = True
        new_ir = srv.ir.with_(partition=new_part)
        stats = srv.migrate(new_ir, {0: 0, 1: 1})
        assert stats["fused_rows_rebuilt"] == (0, 1)
        assert srv.fastpath_active
        fresh = build_demo_server(new_ir, feat=8, hidden=16, n_classes=3,
                                  seed=0)
        r = srv.serve_batch([x], rng=np.random.default_rng(7))[0]
        r_new = fresh.serve_batch([x], rng=np.random.default_rng(7))[0]
        np.testing.assert_array_equal(r.logits, r_new.logits)


def test_partial_reshape_keeps_untouched_row():
    """Only slot 0's mask changes: slot 1's stacked row must be carried (not
    rebuilt) and the merged logits still match a fresh server."""
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0)
    x = _x()
    srv.serve_batch([x], rng=np.random.default_rng(0))
    new_part = np.array(srv.ir.partition)
    new_part[0] = False
    new_part[0, :3] = True                 # slot 1 untouched
    new_ir = srv.ir.with_(partition=new_part)
    stats = srv.migrate(new_ir, {0: 0, 1: 1})
    assert stats["fused_rows_rebuilt"] == (0,)
    assert stats["reused_slots"] == 1
    fresh = build_demo_server(new_ir, feat=8, hidden=16, n_classes=3, seed=0)
    np.testing.assert_array_equal(
        srv.serve_batch([x], rng=np.random.default_rng(7))[0].logits,
        fresh.serve_batch([x], rng=np.random.default_rng(7))[0].logits)


def test_migration_without_store_params_falls_back_to_legacy():
    """A store that serves only (fn, fc_slice) 2-tuples cannot feed the
    stacked pytree — the server must drop to the per-slot loop, never serve
    a stale fused row."""
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0)
    old_store = srv.redeploy_fn
    srv.redeploy_fn = lambda ir, k: old_store(ir, k)[:2]
    new_part = np.array(srv.ir.partition)
    new_part[[0, 1]] = new_part[[1, 0]]
    new_ir = srv.ir.with_(partition=new_part)
    stats = srv.migrate(new_ir, {0: 0, 1: 1})
    assert stats["fused_rows_rebuilt"] == ()
    assert srv.fused is None and not srv.fastpath_active
    fresh = build_demo_server(new_ir, feat=8, hidden=16, n_classes=3, seed=0)
    np.testing.assert_array_equal(
        srv.serve_batch([_x()], rng=np.random.default_rng(7))[0].logits,
        fresh.serve_batch([_x()], rng=np.random.default_rng(7))[0].logits)


def test_deploy_slot_updates_fused_row():
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0)
    store = srv.redeploy_fn
    x = _x()
    srv.serve_batch([x], rng=np.random.default_rng(0))
    new_part = np.array(srv.ir.partition)
    new_part[[0, 1]] = new_part[[1, 0]]
    new_ir = srv.ir.with_(partition=new_part)
    srv.redeploy_fn = None
    srv.migrate(new_ir, {0: 0, 1: 1})          # both slots zeroed
    assert srv.zeroed_slots == {0, 1}
    for k in (0, 1):
        fn, fc, params = store(new_ir, k)
        srv.deploy_slot(k, fn, fc, params)
    assert srv.fastpath_active and srv.zeroed_slots == frozenset()
    fresh = build_demo_server(new_ir, feat=8, hidden=16, n_classes=3, seed=0)
    r = srv.serve_batch([x], rng=np.random.default_rng(7))[0]
    np.testing.assert_array_equal(
        r.logits, fresh.serve_batch([x], rng=np.random.default_rng(7))[0].logits)
    assert not r.degraded


def test_padless_export_width_growth_falls_back_to_legacy():
    """A pad-less fused export (uniform-width ensembles) cannot follow a
    uniform-width change — deploy_slot growing Dk must drop to the legacy
    loop instead of serving too-narrow stacked rows."""
    import jax.numpy as jnp
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0)
    store = srv.redeploy_fn
    fn, fc, params = store(srv.ir, 0)
    srv.fused = dataclasses_replace_pad_none(srv.fused)
    srv.serve_batch([_x()], rng=np.random.default_rng(0))
    Dk = int(srv.fc_weights.shape[1])
    wide = jnp.pad(fc, ((0, Dk + 2 - fc.shape[0]), (0, 0)))  # grows Dk
    srv.deploy_slot(0, fn, wide, params)
    assert srv.fused is None and not srv.fastpath_active
    r = srv.serve_batch([_x()], rng=np.random.default_rng(7))[0]
    assert np.isfinite(r.logits).all()


def dataclasses_replace_pad_none(fused):
    import dataclasses
    return dataclasses.replace(fused, pad=None)


def test_pinned_fastpath_unpins_instead_of_bricking():
    """A server pinned fastpath=True whose export is dropped mid-migration
    must fall back to the legacy loop, not raise at the next serve."""
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0,
                            fastpath=True)
    old_store = srv.redeploy_fn
    srv.redeploy_fn = lambda ir, k: old_store(ir, k)[:2]   # legacy 2-tuples
    new_part = np.array(srv.ir.partition)
    new_part[[0, 1]] = new_part[[1, 0]]
    srv.migrate(srv.ir.with_(partition=new_part), {0: 0, 1: 1})
    assert srv.fused is None and srv.fastpath is None
    r = srv.serve_batch([_x()], rng=np.random.default_rng(7))[0]
    assert np.isfinite(r.logits).all()


def test_dequantize_rejects_wrong_axis_scale():
    import jax.numpy as jnp

    from repro.optim.compression import dequantize_weight, quantize_weight
    w = jnp.asarray(np.random.default_rng(0).normal(size=(6, 11)),
                    jnp.float32)
    wq = quantize_weight(w, axis=1)
    with pytest.raises(ValueError, match="axis"):
        dequantize_weight(wq)                  # default axis 0: mismatch
    np.testing.assert_allclose(np.asarray(dequantize_weight(wq, axis=1)),
                               np.asarray(w), atol=0.02)


def test_deploy_slot_without_params_disables_fastpath():
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0)
    store = srv.redeploy_fn
    fn, fc, _ = store(srv.ir, 0)
    srv.deploy_slot(0, fn, fc)                 # no params
    assert srv.fused is None and not srv.fastpath_active
    fresh = build_demo_server(srv.ir, feat=8, hidden=16, n_classes=3, seed=0)
    np.testing.assert_array_equal(
        srv.serve_batch([_x()], rng=np.random.default_rng(7))[0].logits,
        fresh.serve_batch([_x()], rng=np.random.default_rng(7))[0].logits)


# -- int8 weight-only deployment ----------------------------------------------

def _fig3_fleet_ir():
    """The fig-3 fleet: 8 heterogeneous devices (seed 2) over a 64-filter
    affinity graph, planned by tune_d_th_ir."""
    from repro.core import planner as PL
    from repro.core.simulator import make_fleet
    rng = np.random.default_rng(0)
    a = np.abs(rng.normal(size=(128, 64)))
    A = (a.T @ a) * np.abs(a.mean(0)[:, None] - a.mean(0)[None, :])
    np.fill_diagonal(A, 0)
    A = 0.5 * (A + A.T)
    students = [StudentArch("small", 5e6, 0.6e6, 64, 0.15e6),
                StudentArch("mid", 2e7, 1.5e6, 64, 0.4e6)]
    fleet = make_fleet(8, seed=2, success_prob=0.8)
    return PL.tune_d_th_ir(fleet, A, students, p_th=0.25)


def test_int8_within_tolerance_on_fig3_fleet():
    ir = _fig3_fleet_ir()
    build = dict(feat=32, hidden=64, n_classes=10, seed=0)
    fp32 = build_demo_server(ir, **build)
    int8 = build_demo_server(ir, quantize="int8", **build)
    assert int8.fastpath_active
    x = np.random.default_rng(5).standard_normal((256, 32)).astype(np.float32)
    lf = fp32.serve_batch([x], rng=np.random.default_rng(0))[0].logits
    lq = int8.serve_batch([x], rng=np.random.default_rng(0))[0].logits
    rel = np.abs(lf - lq).max() / max(np.abs(lf).max(), 1e-12)
    assert rel < 0.05, f"int8 rel logits err {rel:.4f}"
    agree = (lf.argmax(-1) == lq.argmax(-1)).mean()
    assert agree >= 0.95, f"int8 top-1 agreement {agree:.3f}"


def test_int8_tolerance_survives_migration():
    ir = _fig3_fleet_ir()
    build = dict(feat=32, hidden=64, n_classes=10, seed=0)
    fp32 = build_demo_server(ir, **build)
    int8 = build_demo_server(ir, quantize="int8", **build)
    x = np.random.default_rng(5).standard_normal((64, 32)).astype(np.float32)
    int8.serve_batch([x], rng=np.random.default_rng(0))    # stack built
    name = ir.device_names[int(np.flatnonzero(ir.member.any(0))[0])]
    for srv in (fp32, int8):
        srv.remove_device(name)
    assert int8.fastpath_active
    lf = fp32.serve_batch([x], rng=np.random.default_rng(1))[0].logits
    lq = int8.serve_batch([x], rng=np.random.default_rng(1))[0].logits
    rel = np.abs(lf - lq).max() / max(np.abs(lf).max(), 1e-12)
    assert rel < 0.05, f"post-migration int8 rel err {rel:.4f}"


def test_int8_masks_failures_like_fp32():
    fused, _ = _pair()
    int8 = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3,
                             seed=0, quantize="int8")
    down = ["a", "b"]
    for srv in (fused, int8):
        srv.failure = FailureModel(forced_failures=down, outages=False)
    a = fused.serve_batch([_x()], rng=np.random.default_rng(3))[0]
    b = int8.serve_batch([_x()], rng=np.random.default_rng(3))[0]
    assert (a.arrived == b.arrived).all() and a.degraded == b.degraded
    # the dead slot contributes nothing in both deployments
    np.testing.assert_allclose(b.logits, a.logits, rtol=0.1, atol=0.05)


# -- lazy / deferred ServeResult ----------------------------------------------

def test_serve_result_defers_host_sync():
    import jax
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0)
    r = srv.serve_batch([_x()], rng=np.random.default_rng(0))[0]
    assert isinstance(r._logits, jax.Array)        # still device-backed
    assert r.block_until_ready() is r
    out = r.logits
    assert isinstance(out, np.ndarray) and out.shape == (3, 3)


def test_failed_devices_lazy_and_correct():
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0)
    srv.failure = FailureModel(forced_failures=["b", "d"], outages=False)
    r = srv.serve_batch([_x()], rng=np.random.default_rng(0))[0]
    assert r.failed_devices == ["b", "d"]
    assert ServeResultHasNoEagerList(r)


def ServeResultHasNoEagerList(r):
    """failed_devices must be derived, not stored."""
    return "failed_devices" not in r.__dict__


def test_deterministic_outcome_cache_matches_generic_path():
    """The memoized failure-free outcome must be bit-identical to the
    generic sample+reduce path (forced through a FailureModel subclass,
    which the cache deliberately does not match)."""
    import dataclasses

    @dataclasses.dataclass
    class PlainModel(FailureModel):
        pass

    cached = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3,
                               seed=0)
    generic = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3,
                                seed=0)
    cached.failure = FailureModel(outages=False)
    generic.failure = PlainModel(outages=False)
    xs = [_x(2), _x(3, seed=9)]
    for srv in (cached, generic):       # twice: second serve hits the cache
        srv.serve_batch(xs, rng=np.random.default_rng(1))
    ra = cached.serve_batch(xs, rng=np.random.default_rng(1))
    rb = generic.serve_batch(xs, rng=np.random.default_rng(1))
    for a, b in zip(ra, rb):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.latency == b.latency
        assert (a.arrived == b.arrived).all()
        assert a.failed_devices == b.failed_devices
    # the cache is keyed by the plan-arrays object: a migration must miss
    cached.remove_device("a")
    generic.remove_device("a")
    ra = cached.serve_batch(xs, rng=np.random.default_rng(2))
    rb = generic.serve_batch(xs, rng=np.random.default_rng(2))
    for a, b in zip(ra, rb):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.latency == b.latency


def test_serve_empty_batch():
    srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3, seed=0)
    assert srv.serve_batch([]) == []


# -- ensemble stacked export --------------------------------------------------

def _uniform_ensemble(n_classes=4, dim=4):
    import jax

    from repro.core import distill as DS
    from repro.core import planner as PL
    from repro.core.pipeline import Ensemble
    from repro.models import cnn
    st = StudentArch("s", 5e6, 0.6e6, 64, 0.15e6)
    groups = [
        PL.GroupPlan(0, [Device("a", 1e7, 2e6, 500, 0.3),
                         Device("b", 2e7, 2e6, 500, 0.3)], 0,
                     np.arange(dim), st),
        PL.GroupPlan(1, [Device("c", 1e7, 2e6, 500, 0.3),
                         Device("d", 3e7, 2e6, 500, 0.3)], 1,
                     np.arange(dim, 2 * dim), st),
    ]
    plan = PL.Plan(groups, np.zeros((2 * dim, 2 * dim)), 1.0, 0.5)
    students = [cnn.make_student(jax.random.key(i), "wrn-10-1", n_classes, dim)
                for i in range(2)]
    fc = DS.fc_head_init(jax.random.key(9), 2 * dim, n_classes)
    return Ensemble(plan, students, fc, [dim, dim], teacher_acc=0.0)


def test_uniform_arch_ensemble_gets_fused_export():
    from repro.runtime.serving import server_from_ensemble
    ens = _uniform_ensemble()
    assert ens.fused_export() is not None
    fused = server_from_ensemble(ens, failure=FailureModel(outages=False))
    legacy = server_from_ensemble(ens, failure=FailureModel(outages=False),
                                  fastpath=False)
    assert fused.fastpath_active and not legacy.fastpath_active
    x = np.random.default_rng(0).standard_normal(
        (4, 32, 32, 3)).astype(np.float32)
    a = fused.serve_batch([x], rng=np.random.default_rng(7))[0]
    b = legacy.serve_batch([x], rng=np.random.default_rng(7))[0]
    np.testing.assert_array_equal(a.logits, b.logits)


def test_heterogeneous_arch_ensemble_has_no_export():
    import jax

    from repro.models import cnn
    ens = _uniform_ensemble()
    # swap one student to a different arch: no longer stackable
    ens.students[1] = cnn.make_student(jax.random.key(5), "wrn-16-1", 4, 4)
    assert ens.fused_export() is None


# -- kernel paths -------------------------------------------------------------

def test_quorum_aggregate_scales_ones_bit_identical():
    import jax.numpy as jnp

    from repro.kernels.quorum_aggregate import quorum_aggregate
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.normal(size=(3, 5, 4)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 4, 6)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=6).astype(np.float32))
    m = jnp.asarray([1, 1, 0], jnp.int32)
    o1 = quorum_aggregate(p, w, b, m, interpret=True)
    o2 = quorum_aggregate(p, w, b, m, jnp.ones(3, jnp.float32),
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_quorum_aggregate_int8_matches_ref():
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.quorum_aggregate import quorum_aggregate
    from repro.optim.compression import quantize_weight
    rng = np.random.default_rng(1)
    p = jnp.asarray(rng.normal(size=(4, 9, 6)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 6, 5)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=5).astype(np.float32))
    m = jnp.asarray([1, 0, 1, 1], jnp.int32)
    wq = quantize_weight(w, axis=0)
    assert wq.q.dtype == jnp.int8 and wq.scale.shape == (4,)
    out = quorum_aggregate(p, wq.q, b, m, wq.scale, interpret=True)
    exp = ref.quorum_aggregate_ref(p, wq.q, b, m, wq.scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)
    dense = ref.quorum_aggregate_ref(p, w, b, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=0.1, atol=0.1)


def test_quorum_aggregate_int8_without_scales_raises():
    import jax.numpy as jnp

    from repro.kernels.quorum_aggregate import quorum_aggregate
    p = jnp.zeros((2, 3, 4))
    w = jnp.zeros((2, 4, 5), jnp.int8)
    with pytest.raises(ValueError, match="scales"):
        quorum_aggregate(p, w, jnp.zeros(5), jnp.ones(2, jnp.int32),
                         interpret=True)


@pytest.mark.parametrize("per_channel", [False, True])
def test_dequant_matmul_matches_ref(per_channel):
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.dequant_matmul import dequant_matmul
    from repro.optim.compression import quantize_weight
    rng = np.random.default_rng(2)
    for B, D, N in ((1, 8, 5), (7, 16, 11), (130, 8, 300)):
        x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(D, N)).astype(np.float32))
        wq = quantize_weight(w, axis=1 if per_channel else None)
        out = dequant_matmul(x, wq.q, wq.scale, interpret=True)
        exp = ref.dequant_matmul_ref(x, wq.q, wq.scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-4, atol=1e-4)


def test_dequant_matmul_empty_batch():
    import jax.numpy as jnp

    from repro.kernels.dequant_matmul import dequant_matmul
    out = dequant_matmul(jnp.zeros((0, 4)), jnp.zeros((4, 3), jnp.int8),
                         jnp.float32(0.1), interpret=True)
    assert out.shape == (0, 3)


@pytest.mark.parametrize("B,D,N,bb,bn", [
    (7, 16, 13, 4, 8),       # both dims ragged vs the block
    (33, 8, 257, 32, 64),    # one full tile + a 1-wide remainder each way
    (1, 8, 1, 128, 256),     # blocks far larger than the problem
    (250, 32, 100, 128, 256),  # defaults against a non-multiple shape
])
def test_dequant_matmul_ragged_grid_vs_ref(B, D, N, bb, bn):
    """Explicit block sizes that don't divide (B, N): the grid pads the
    last tile and the result must still match the reference exactly."""
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.dequant_matmul import dequant_matmul
    rng = np.random.default_rng(B * 1000 + N)
    x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32))
    q = jnp.asarray(rng.integers(-127, 128, (D, N)), jnp.int8)
    sc = jnp.asarray(rng.uniform(0.01, 0.1, (N,)).astype(np.float32))
    out = dequant_matmul(x, q, sc, block_batch=bb, block_n=bn,
                         interpret=True)
    exp = ref.dequant_matmul_ref(x, q, sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_dequant_matmul_degenerate_blocks_degrade_to_legal_grid():
    """Nonsensical block sizes (0, negative, larger than the problem) —
    e.g. a stale tuning-table entry for a shape that shrank — are clamped
    to a legal grid rather than crashing."""
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.dequant_matmul import dequant_matmul
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(5, 8)).astype(np.float32))
    q = jnp.asarray(rng.integers(-127, 128, (8, 6)), jnp.int8)
    sc = jnp.float32(0.05)
    exp = np.asarray(ref.dequant_matmul_ref(x, q, sc))
    for bb, bn in ((0, 0), (-5, 4), (4096, 4096)):
        out = dequant_matmul(x, q, sc, block_batch=bb, block_n=bn,
                             interpret=True)
        np.testing.assert_allclose(np.asarray(out), exp,
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kernel", ["quorum_aggregate", "coded_decode"])
def test_serving_kernels_ragged_block_batch(kernel):
    """block_batch not dividing B on the other two tuned kernels."""
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    rng = np.random.default_rng(4)
    B = 37
    if kernel == "quorum_aggregate":
        p = jnp.asarray(rng.normal(size=(3, B, 8)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(3, 8, 5)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=5).astype(np.float32))
        m = np.ones(3, np.int32)
        out = ops.quorum_aggregate(p, w, b, m, block_batch=16)
        exp = ref.quorum_aggregate_ref(p, w, b, m)
    else:
        sh = jnp.asarray(rng.normal(size=(5, B, 8)).astype(np.float32))
        dec = jnp.asarray(rng.normal(size=(3, B, 5)).astype(np.float32))
        m = jnp.ones((B, 5), jnp.float32)
        out = ops.coded_decode(sh, dec, m, block_batch=16)
        exp = ref.coded_decode_ref(sh, dec, m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


# -- engine integration -------------------------------------------------------

def test_engine_serves_fused_and_int8_servers():
    from repro.runtime.engine import EngineConfig, ServingEngine
    for quantize in ("none", "int8"):
        srv = build_demo_server(_toy_ir(), feat=8, hidden=16, n_classes=3,
                                seed=0, quantize=quantize)
        cfg = EngineConfig(max_batch=4, max_wait=0.01, slo=10.0, input_dim=8,
                           service_model=(1e-3, 1e-4), warmup=False, seed=0)
        rep = ServingEngine(srv, cfg).run(np.linspace(0, 0.05, 12))
        s = rep.summary()
        assert s["n"] == 12 and s["quorum_rate"] == 1.0
