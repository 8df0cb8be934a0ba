"""The trace reduction, on synthetic events and on a trace recorded on a
TPU v5e chip: one graph500 scale-18 solve of 5 iterations (unweighted),
the events of a ``--trace 1`` run's window saved with
``benchlib.trace.save``."""
from __future__ import annotations

import os

import helpers  # noqa: F401  (puts bench/ on the path)
import pytest

from benchlib import trace as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "trace_graph500_s18.json.gz")
DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return tr.Event(plane, line, name, float(start), float(dur))


@pytest.mark.parametrize("intervals,want", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 15)], 15),
    ([(0, 10), (10, 20)], 20), ([(20, 30), (0, 10)], 20),
    ([(0, 100), (10, 20), (30, 40)], 100), ([(0, 5), (7, 9), (8, 12)], 10)])
def test_union_of_busy_intervals(intervals, want):
    assert tr.union_ns(intervals) == want


def test_summary_on_synthetic_events():
    events = [
        ev("XLA Modules", "jit__unknown(7)", 100, 400),
        ev("XLA Ops", "%fusion = gather", 100, 150),
        ev("XLA Ops", '%k = custom-call(), custom_call_target="tpu_custom_call"',
           300, 200),
        ev("XLA Modules", "jit_mark_frontier(3)", 600, 100),
        ev("XLA Ops", "%fusion = segment max", 600, 100),
        ev("python3", "bench/solve", 0, 1000, plane="/host:CPU"),
        ev("python3", "np.asarray(jax.Array)", 700, 300, plane="/host:CPU"),
    ]
    s = tr.Summary(events, 0, 1000)
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(450e-9)
    assert s.module_s("jit_mark_frontier") == pytest.approx(100e-9)
    assert s.module_s_with("tpu_custom_call") == pytest.approx(400e-9)
    assert s.op_s("tpu_custom_call") == pytest.approx(200e-9)
    gaps = s.idle_gaps()
    assert [g[0] for g in gaps] == ["np.asarray(jax.Array)", "bench/solve",
                                    "bench/solve", "bench/solve"]
    assert [g[1] for g in gaps] == pytest.approx([300e-9, 100e-9, 100e-9,
                                                  50e-9])


@pytest.fixture(scope="module")
def recorded():
    events = tr.load(RECORDED)
    span = [e for e in events if e.name == "bench/solve"]
    assert len(span) == 1
    return events, tr.Summary(events, span[0].start_ns, span[0].end_ns)


def test_recorded_busy_is_union_of_device_ops(recorded):
    events, s = recorded
    ops = [(max(e.start_ns, s.t0), min(e.end_ns, s.t1)) for e in events
           if e.plane == DEV and e.line == "XLA Ops"]
    ops = [(a, b) for a, b in ops if b > a]
    # an independent union, clipped to the solve: sweep over the sorted
    # start and end points
    points = sorted([(a, 1) for a, _ in ops] + [(b, -1) for _, b in ops])
    busy, depth, since = 0.0, 0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0 < s.busy_s < s.window_s


def test_recorded_module_and_kernel_sums(recorded):
    events, s = recorded
    mods = [e for e in events if e.plane == DEV and e.line == "XLA Modules"]
    mover = sum(e.dur_ns for e in mods if e.name.startswith("jit__unknown("))
    frontier = sum(e.dur_ns for e in mods
                   if e.name.startswith("jit_mark_frontier("))
    kernels = sum(e.dur_ns for e in events if e.plane == DEV
                  and e.line == "XLA Ops" and "tpu_custom_call" in e.name)
    assert s.module_s_with("tpu_custom_call") == pytest.approx(mover * 1e-9)
    assert s.module_s("jit_mark_frontier") == pytest.approx(frontier * 1e-9)
    assert s.op_s("tpu_custom_call") == pytest.approx(kernels * 1e-9)
    # 5 iterations: one mover and one mark_frontier module each
    assert sum(e.name.startswith("jit__unknown(") for e in mods) == 5
    assert sum(e.name.startswith("jit_mark_frontier(") for e in mods) == 5
    assert 0 < kernels < mover


def test_recorded_breakdown_is_bounded(recorded):
    _, s = recorded
    top = s.top_ops()
    assert len(top) == 10
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
    gaps = s.idle_gaps()
    assert len(gaps) == 10
    assert sum(g for _, g in gaps) <= s.window_s - s.busy_s + 1e-12


def test_saved_events_load_unchanged(recorded, tmp_path):
    events, _ = recorded
    path = str(tmp_path / "events.json.gz")
    tr.save(events[:1000], path)
    assert tr.load(path) == events[:1000]
