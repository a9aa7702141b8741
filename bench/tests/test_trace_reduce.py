"""The trace reduction: busy/idle union, kernel time and idle attribution,
on made-up intervals and on a trace recorded on a TPU v5e."""
import pathlib

import numpy as np
import pytest

from bench import trace_reduce as TR

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = DATA / "c10.xplane.pb"


def test_union_merges_overlaps_and_clips():
    u = TR.union([(0.5, 2.0), (1.0, 3.0), (4.0, 5.0), (-1.0, 0.2),
                  (5.5, 9.0)], (0.0, 6.0))
    assert u.tolist() == [[0.0, 0.2], [0.5, 3.0], [4.0, 5.0], [5.5, 6.0]]


def _made_up():
    ops = {0: [("fusion.1", 1.0, 1.0), ("fusion.2", 1.5, 1.0),
               ("agg_kernel", 6.0, 0.5)]}
    modules = {0: [("jit_padded(3)", 1.0, 1.5), ("jit_padded(4)", 2.0, 0.5),
                   ("_quorum_aggregate_jit(7)", 6.0, 0.5)]}
    host = [("dispatch", 0.0, 7.0), ("serve_batch", 0.5, 6.5),
            ("wait_due", 3.0, 2.0), ("poll_repair", 5.2, 0.5)]
    return TR.Trace((0.0, 10.0), ops, modules, host)


def test_busy_kernel_time_and_idle_attribution():
    t = _made_up()
    assert t.busy(0).tolist() == [[1.0, 2.5], [6.0, 6.5]]
    assert t.busy_s() == pytest.approx(2.0)
    assert t.op_time("agg_kernel") == (0.5, 1)
    assert t.module_time(r"^jit_padded") == (2.0, 2)
    assert t.top_ops()[0] == ["jit_padded", 2.0]
    gaps = dict(t.idle_gaps())
    # idle: [0,1) mid 0.5 -> serve_batch; [2.5,6) mid 4.25 -> wait_due;
    # [6.5,10) mid 8.25 -> no span: the engine's loop
    assert gaps == pytest.approx({"serve_batch": 1.0, "wait_due": 3.5,
                                  "engine_loop": 3.5})
    assert sum(gaps.values()) + t.busy_s() == pytest.approx(t.window_s)


def test_program_spans_attribute_idle_gaps():
    t = _made_up()
    assert t.program == []
    # program spans nest: each gap goes to the innermost one over its
    # midpoint, else to the benchmark span idle_gaps chooses
    t.program = [TR.Span("engine.batch", 0.2, 6.6, {"rows": 24}),
                 TR.Span("server.stack", 0.45, 0.1),
                 TR.Span("server.merge", 5.8, 0.2)]
    got = dict(t.idle_by_phase())
    # [0,1) mid 0.5 -> server.stack; [2.5,6) mid 4.25 -> engine.batch;
    # [6.5,10) mid 8.25 -> no span: the engine's loop
    assert got == pytest.approx({"server.stack": 1.0, "engine.batch": 3.5,
                                 "engine_loop": 3.5})
    assert sum(got.values()) + t.busy_s() == pytest.approx(t.window_s)
    assert t.idle_by_phase([]) == t.idle_gaps(20)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    t = TR.load(str(RECORDED))
    assert list(t.ops) == [0] and t.window_s > 0
    busy = t.busy(0)
    # the union against a fine grid over the window
    grid = np.linspace(t.window[0], t.window[1], 200_001)
    covered = np.zeros(grid.shape, bool)
    for _, s, d in t.ops[0]:
        covered |= (grid >= s) & (grid < s + d)
    assert t.busy_s() / t.window_s == pytest.approx(covered.mean(),
                                                    abs=2e-3)
    assert (np.diff(busy.ravel()) >= 0).all()
    secs, n = t.module_time(r"^jit_padded")
    assert n > 0 and 0 < secs < t.window_s
    k_secs, k_n = t.op_time(r"agg_kernel|quorum_aggregate")
    assert k_n > 0 and 0 < k_secs < secs
    gaps = dict(t.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s(),
                                               rel=1e-6)
    assert "serve_batch" in gaps
    # the recorded trace predates the program's spans: by phase is by gap
    assert t.program == []
    assert dict(t.idle_by_phase()) == pytest.approx(gaps, rel=1e-12)
