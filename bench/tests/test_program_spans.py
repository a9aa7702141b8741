"""The program's host spans as the benchmark reads them: per-batch phase
times and idle attribution on made-up intervals, the recorded v5e trace
(which predates the spans), and a tiny cell traced on the CPU, whose
engine counts the same filler rows as the benchmark and whose spans keep
their stats."""
import pathlib

import numpy as np
import pytest

from bench import deploy, program_spans as PS, trace_reduce as TR
from bench import traffic as T
from bench import window

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = DATA / "c10.xplane.pb"


def _made_up():
    ops = {0: [("fusion.1", 1.0, 1.0), ("fusion.2", 1.5, 1.0),
               ("agg_kernel", 6.0, 0.5)]}
    modules = {0: [("jit_padded_s0(3)", 1.0, 1.5),
                   ("jit_padded_s1(4)", 2.0, 0.5),
                   ("_quorum_aggregate_jit(7)", 6.0, 0.5)]}
    host = [("dispatch", 0.0, 7.0), ("serve_batch", 0.5, 6.5),
            ("wait_due", 3.0, 2.0), ("poll_repair", 5.2, 0.5)]
    spans = [("engine.batch", 0.2, 6.6), ("engine.control", 0.2, 0.1),
             ("server.draw", 0.6, 0.3), ("server.slot_forward", 0.9, 0.2),
             ("server.slot_mask", 2.6, 0.2), ("server.merge", 5.8, 0.2),
             ("engine.device_wait", 6.0, 0.7), ("engine.batch", 9.5, 1.0),
             ("server.slot_forward", 9.6, 0.2)]
    t = TR.Trace((0.0, 10.0), ops, modules, host)
    t.program = [TR.Span(*s) for s in spans]
    return t, spans


def test_idle_by_phase_and_per_batch_ms_on_made_up_intervals():
    t, spans = _made_up()
    # idle: [0,1) mid 0.5 -> engine.batch (innermost program span);
    # [2.5,6) mid 4.25 -> engine.batch; [6.5,10) mid 8.25 -> no program
    # span, no benchmark span: the engine's loop
    got = dict(t.idle_by_phase())
    assert got == pytest.approx({"engine.batch": 4.5, "engine_loop": 3.5})
    assert sum(got.values()) == pytest.approx(t.window_s - t.busy_s())
    # the same gaps with a program span over each midpoint
    inner = spans + [("server.stack", 0.45, 0.1), ("server.package", 4.0, 0.5)]
    got = dict(t.idle_by_phase(sorted(inner, key=lambda s: s[1])))
    assert got == pytest.approx({"server.stack": 1.0, "server.package": 3.5,
                                 "engine_loop": 3.5})
    # without program spans it is idle_gaps, gap for gap
    assert dict(t.idle_by_phase([])) == pytest.approx(
        dict(t.idle_gaps()))
    # two batches start in the window [0, 10)
    assert len(PS.batches(t)) == 2
    # the loop is the slot forwards and the merge: no server.slot_mask,
    # which the program no longer emits; 0.4 s in the first batch, 0.2 s
    # in the second
    assert "server.slot_mask" not in PS.SLOT_LOOP
    assert PS.per_batch_s(t, PS.SLOT_LOOP) == pytest.approx([0.4, 0.2])
    assert PS.per_batch_ms(t, PS.SLOT_LOOP) == pytest.approx(300)
    assert PS.per_batch_ms(t, ("server.draw",)) == pytest.approx(150)
    # a span is clipped to the window: the second batch ends at 10.5
    assert PS.per_batch_s(t, ("engine.batch",)) == pytest.approx([6.6, 0.5])
    # the chip is busy [1, 2.5) and [6, 6.5): 2 s in the first batch, none
    # in the second
    assert PS.device_per_batch_ms(t) == pytest.approx(1e3)
    t.program = []
    assert PS.per_batch_ms(t, PS.SLOT_LOOP) is None
    assert PS.device_per_batch_ms(t) is None
    t, _ = _made_up()
    split = PS.phase_split(t)
    assert list(split)[0] == "engine.batch"
    assert split["engine.device_wait"] == pytest.approx(350)


def test_per_batch_readings_are_medians_over_the_batches():
    """One slow batch moves neither median; an op that straddles two
    batches counts in each for its part."""
    ops = {0: [("f", 0.1, 0.2), ("f", 0.8, 0.4), ("f", 2.0, 0.8)],
           1: [("f", 0.1, 0.2), ("f", 0.8, 0.4), ("f", 2.0, 0.8)]}
    t = TR.Trace((0.0, 3.0), ops, {}, [("dispatch", 0.0, 3.0)])
    t.program = [TR.Span(*s) for s in [
        ("engine.batch", 0.0, 0.9), ("server.slot_forward", 0.1, 0.1),
        ("engine.batch", 1.0, 0.9), ("server.slot_forward", 1.1, 0.1),
        ("engine.batch", 2.0, 0.9), ("server.slot_forward", 2.1, 0.7)]]
    assert PS.per_batch_ms(t, PS.SLOT_LOOP) == pytest.approx(100)
    assert PS.phase_split(t)["server.slot_forward"] == pytest.approx(300)
    # busy per batch: 0.2 + 0.1, 0.2, 0.8 (each chip alike)
    assert PS.device_per_batch_ms(t) == pytest.approx(300)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace_without_program_spans():
    t = TR.load(str(RECORDED))
    assert t.program == []
    assert PS.per_batch_ms(t, PS.SLOT_LOOP) is None
    assert PS.device_per_batch_ms(t) is None
    assert PS.phase_split(t) == {}
    assert dict(t.idle_by_phase()) == pytest.approx(
        dict(t.idle_gaps()), rel=1e-12)
    rep = PS.report(str(RECORDED))
    assert rep["slot_loop_ms"] is None and rep["batches"] == 0


@pytest.fixture(scope="module")
def tiny_traced(tmp_path_factory):
    """A tiny cell's window served on the CPU with the profiler over its
    last part, keeping each batch's engine record beside the benchmark's."""
    cfg = deploy.load_config(DATA / "tiny.json")
    seed = 2 ** 33 + 11
    dep = deploy.build(cfg, seed)
    pairs = []
    make = window.make_engine_class

    def recording_class():
        base = make()

        class Recording(base):
            def _dispatch(self, now, reqs, bid):
                out = super()._dispatch(now, reqs, bid)
                pairs.append((out[1], self.infos[-1]))
                return out
        return Recording

    tdir = tmp_path_factory.mktemp("trace")
    mp = pytest.MonkeyPatch()
    mp.setattr(window, "make_engine_class", recording_class)
    try:
        win = window.serve_window(dep, T.load_mix(DATA / "tiny-mix.json"),
                                  seed, 1.5,
                                  counter=window.CompileCounter(),
                                  trace_dir=str(tdir))
    finally:
        mp.undo()
    return win, pairs, TR.find_xplane(str(tdir))


def test_engine_pad_rows_equal_the_benchmarks_padded_rows(tiny_traced):
    _, pairs, _ = tiny_traced
    assert pairs
    assert [rec.pad_rows for rec, _ in pairs] == \
        [info.padded_rows for _, info in pairs]
    assert any(rec.pad_rows for rec, _ in pairs)


def test_cpu_trace_holds_the_program_spans(tiny_traced):
    win, pairs, path = tiny_traced
    t = TR.load(path)
    spans = t.program
    names = {s[0] for s in spans}
    assert {"engine.batch", "engine.inputs", "engine.device_wait",
            "server.draw", "server.stack", "server.slot_forward",
            "server.merge", "server.package"} <= names
    traced = [b for b in win.batches
              if win.trace_span[0] <= b.t_dispatch < win.trace_span[1]]
    assert abs(len(PS.batches(t)) - len(traced)) <= 1
    loop = PS.per_batch_ms(t, PS.SLOT_LOOP)
    assert 0 < loop < PS.per_batch_ms(t, ("engine.batch",))
    # each span keeps its stats: every traced batch's rows and filler rows
    # as the engine recorded them
    by_bid = {int(s.stats["bid"]): s.stats for s in spans
              if s.name == "engine.batch"}
    recs = {b.record.bid: b.record for b in win.batches}
    assert by_bid and set(by_bid) <= set(recs)
    for bid, st in by_bid.items():
        assert (int(st["rows"]), int(st["pad_rows"])) == \
            (recs[bid].rows, recs[bid].pad_rows)
    assert all("slot" in s.stats for s in spans
               if s.name == "server.slot_forward")
    # no device plane on the CPU: nothing is idle, nothing is attributed
    assert t.idle_by_phase() == []
    assert PS.device_per_batch_ms(t) is None
