"""The reduction by the program's names (``benchlib.spans``) and the
per-layer metrics that read it, on synthetic events, on the trace recorded
before the program had names (every new reading must come out empty
there, not fail) and on a scoped graph500 solve recorded on a TPU v5e
chip (``bench/scopes.py --save``)."""
from __future__ import annotations

import dataclasses
import os

import helpers
import pytest

from benchlib import harness, spans
from benchlib import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
UNNAMED = os.path.join(DATA, "trace_graph500_s18.json.gz")
DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW = ("dispatch_idle_ms", "sync_idle_ms", "host_syncs")


def op(start, dur, scope="", plane=DEV):
    return spans.ScopedEvent(plane, "XLA Ops", "%fusion = op", float(start),
                             float(dur), scope)


def host(name, start, dur):
    return spans.ScopedEvent(HOST, "python3", name, float(start),
                             float(dur))


@dataclasses.dataclass
class FakeReading:
    trace: object
    solves: list


def read(metric, summary, iterations):
    reader = harness.Bench(helpers.ROOT).metric_reader(metric)
    return reader(FakeReading(summary, [harness.Solve(None, iterations,
                                                      [])]))


def test_scope_sums():
    ops = [op(0, 10, "jit(lpa_move)/lpa.gather/gather"),
           op(10, 20, "jit(lpa_move)/lpa.fold.r0/lpa_fold/pallas_call"),
           op(30, 5, "jit(lpa_move)/reshape;jit(lpa_move)/lpa.fold.r1/x"),
           op(35, 40, "jit(lpa_move)/lpa.relayout/gather"),
           op(75, 7, "jit(lpa_move)/lpa.apply/scatter"),
           op(82, 3, ""), op(85, 1, "jit(mark_frontier)/scatter")]
    assert spans.scope_s(ops, "lpa.gather") == pytest.approx(10e-9)
    assert spans.scope_s(ops, "lpa.fold") == pytest.approx(25e-9)
    assert spans.scope_s(ops, "lpa.fold.r1") == pytest.approx(5e-9)
    assert spans.scope_s(ops, "lpa.relayout") == pytest.approx(40e-9)
    assert spans.scope_s(ops, "lpa.apply") == pytest.approx(7e-9)
    assert spans.scope_s(ops, "lpa.gath") == 0
    # plain events of the unchanged reduction carry no scope
    assert spans.scope_s([tr.Event(DEV, "XLA Ops", "x", 0., 5.)],
                         "lpa.gather") == 0
    assert spans.instruction("%fusion.2 = f32[3] fusion(%a)") == "fusion.2"


def test_scopes_come_from_the_module_that_ran_the_op():
    text = """ENTRY %main.7 (labels.1: s32[8]) -> s32[8] {
  %fusion.2 = s32[9]{0} fusion(%labels.1), kind=kLoop, calls=%f, metadata={op_name="jit(lpa_move)/lpa.gather/concatenate" stack_frame_id=3}
  ROOT %lpa_select.1 = s32[8]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(lpa_move)/lpa.fold.r0/lpa_select/pallas_call"}
}"""
    names = spans.op_names_of(text)
    assert names == {
        "fusion.2": "jit(lpa_move)/lpa.gather/concatenate",
        "lpa_select.1": "jit(lpa_move)/lpa.fold.r0/lpa_select/pallas_call"}
    events = [tr.Event(DEV, "XLA Modules", "jit_lpa_move(7)", 0., 100.),
              tr.Event(DEV, "XLA Ops", "%fusion.2 = s32[9]{0} fusion()",
                       0., 10.),
              tr.Event(DEV, "XLA Ops", '%lpa_select.1 = s32[8]{0} '
                       'custom-call(), custom_call_target="tpu_custom_call"',
                       10., 80.),
              tr.Event(DEV, "XLA Modules", "jit_mark_frontier(3)", 100.,
                       50.),
              # the same instruction name in another module: no scope
              tr.Event(DEV, "XLA Ops", "%fusion.2 = pred[8]{0} fusion()",
                       100., 50.),
              tr.Event(HOST, "python3", "lpa.solve", 0., 150.)]
    got = spans.with_scopes(events, {"jit_lpa_move": names})
    assert [e.scope for e in got] == ["", names["fusion.2"],
                                      names["lpa_select.1"], "", "", ""]
    assert [dataclasses.astuple(e)[:5] for e in got] == [
        dataclasses.astuple(e) for e in events]
    assert spans.scope_s(got, "lpa.fold") == pytest.approx(80e-9)


@pytest.mark.parametrize("gaps,want", [
    # idle [300, 400) inside lpa.dispatch.move, which nests in iteration
    ([(300, 100)], {"lpa.dispatch.move": 100e-9}),
    # idle [650, 700) in lpa.sync.delta, [700, 750) in the iteration's code
    ([(650, 100)], {"lpa.sync.delta": 50e-9, "lpa.iteration": 50e-9}),
    # idle [950, 1000) after lpa.solve ends: no lpa.* span, uncharged
    ([(950, 50)], {"": 50e-9}),
], ids=["innermost", "split", "outside"])
def test_idle_charged_to_the_innermost_span(gaps, want):
    window = (0, 1000)
    cuts = sorted(g for gap in gaps for g in (gap[0], gap[0] + gap[1]))
    edges = [window[0]] + cuts + [window[1]]
    ops = [op(a, b - a) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    events = ops + [host("bench/solve", 0, 1000),
                    host("lpa.solve", 10, 930),
                    host("lpa.iteration", 100, 800),
                    host("lpa.dispatch.move", 150, 300),
                    host("lpa.sync.delta", 600, 100),
                    host("np.asarray(jax.Array)", 600, 100)]
    s = tr.Summary(events, *window)
    assert s.window_s - s.busy_s == pytest.approx(sum(want.values()))
    got = spans.idle_by_span(s)
    assert got.keys() == want.keys()
    for k, v in want.items():
        assert got[k] == pytest.approx(v)


def test_readers_on_synthetic_solve():
    # two iterations; each: a move dispatch (idle 20 ns of it), the
    # loop's own code after it (idle 5 ns), one sync (idle 30 ns)
    events = [host("bench/solve", 0, 1000), host("lpa.solve", 0, 1000)]
    for t in (0, 500):
        events += [host("lpa.iteration", t, 500),
                   host("lpa.dispatch.move", t, 100),
                   host("lpa.sync.delta", t + 300, 100),
                   op(t + 20, 100), op(t + 125, 175), op(t + 330, 170)]
    s = tr.Summary(events, 0, 1000)
    assert read("dispatch_idle_ms", s, 2) == pytest.approx(2e-5)
    assert read("sync_idle_ms", s, 2) == pytest.approx(3.5e-5)
    assert read("host_syncs", s, 2) == 1.0
    assert spans.sync_count(s) == 2


def test_new_readers_are_silent_on_a_program_without_names():
    events = tr.load(UNNAMED)
    span = [e for e in events if e.name == "bench/solve"][0]
    s = tr.Summary(events, span.start_ns, span.end_ns)
    assert not spans.lpa_spans(s.host)
    for metric in NEW:
        assert read(metric, s, 5) is None
    assert read("move_ms", s, 5) > 0  # the old readers still read it


SCOPED = os.path.join(DATA, "trace_graph500_s19_scoped.json.gz")


@pytest.fixture(scope="module")
def scoped():
    events = spans.load(SCOPED)
    span = [e for e in events if e.name == "bench/solve"]
    assert len(span) == 1
    return events, tr.Summary(events, span[0].start_ns, span[0].end_ns)


def mover_ops(s):
    mods = [m for m in s.modules if tr.module_name(m.name) == "jit_lpa_move"]
    return [o for o in s.ops if any(m.start_ns <= o.start_ns < m.end_ns
                                    for m in mods)]


def test_recorded_scopes_cover_the_mover(scoped):
    _, s = scoped
    ops = mover_ops(s)
    total = sum(o.dur_ns for o in ops) * 1e-9
    parts = {k: spans.scope_s(ops, k) for k in
             ("lpa.gather", "lpa.relayout", "lpa.fold", "lpa.apply")}
    assert all(v > 0 for v in parts.values()), parts
    assert sum(parts.values()) == pytest.approx(
        sum(spans.scope_s(ops, c) for c in {
            c for o in ops for c in spans.scopes_of(o.scope)[:1]}))
    assert sum(parts.values()) >= 0.95 * total
    # graph500's hub rows: three rounds, the re-layout between them the
    # largest part, the kernels named by pallas_call(name=...)
    assert {f"lpa.fold.r{i}" for i in range(3)} <= {
        c for o in ops for c in spans.scopes_of(o.scope)}
    assert parts["lpa.relayout"] == max(parts.values())
    kernels = {spans.instruction(o.name).split(".")[0] for o in ops
               if "tpu_custom_call" in o.name}
    assert kernels == {"lpa_fold", "lpa_select"}
    # the unchanged readers find the named mover and kernels as before
    assert s.module_s_with("tpu_custom_call") == pytest.approx(
        s.module_s("jit_lpa_move"))
    assert s.op_s("tpu_custom_call") == pytest.approx(
        spans.scope_s([o for o in ops if "tpu_custom_call" in o.name],
                      "lpa.fold"))


def test_recorded_idle_is_charged_to_the_loop_spans(scoped):
    events, s = scoped
    solve = [h for h in s.host if h.name == "lpa.solve"][0]
    inner = spans.idle_by_span(tr.Summary(events, solve.start_ns,
                                          solve.end_ns))
    named = sum(v for k, v in inner.items() if k)
    assert named >= 0.95 * sum(inner.values())
    iters = len([h for h in s.host if h.name == "lpa.iteration"])
    assert read("host_syncs", s, iters) == 2.0
    dispatch = read("dispatch_idle_ms", s, iters)
    sync = read("sync_idle_ms", s, iters)
    idle_ms = 1e3 * (s.window_s - s.busy_s) / iters
    assert 0 < dispatch + sync <= idle_ms
