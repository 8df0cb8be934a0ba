"""The solve's names (repro.core.obs): host spans on the profiler's host
plane, the host-sync counter, and the mover's device scopes in its ops'
``op_name`` metadata. The parity suites (test_stream_engine,
test_fused_engine, test_sparse_frontier) hold the labels to the reference
fold with the names in place."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import obs
from repro.core.fold_engine import get_engine
from repro.core.lpa import LPAConfig, build_workspace, lpa, mover
from repro.graphs.generators import powerlaw_communities

STREAM = dict(method="mg", rho=2, chunk=16, fold_backend="pallas_stream",
              stream_window=1024)


@pytest.fixture(scope="module")
def graph():
    return powerlaw_communities(384, p_in=0.5, mix=0.02, seed=3)[0]


def host_events(log_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return [ev for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("backend", ["jnp", "pallas_stream"])
def test_solve_spans_nest_on_the_host_plane(graph, backend, tmp_path):
    cfg = LPAConfig(**{**STREAM, "fold_backend": backend})
    ws = build_workspace(graph, cfg)
    lpa(graph, cfg, ws=ws)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        res = lpa(graph, cfg, ws=ws)
    events = host_events(str(tmp_path))
    spans = [(e.name, e.start_ns, e.end_ns) for e in events
             if e.name.startswith("lpa.")]
    by = {n: [s for s in spans if s[0] == n] for n, _, _ in spans}
    modules = {v for e in events for k, v in e.stats if k == "hlo_module"}
    assert {"jit_lpa_move", "jit_mark_frontier"} <= modules
    assert len(by["lpa.solve"]) == 1
    solve = by["lpa.solve"][0]
    iters = by["lpa.iteration"]
    assert len(iters) == res.iterations
    assert len(by["lpa.dispatch.move"]) == res.iterations
    assert len(by["lpa.sync.delta"]) == res.iterations
    for it in iters:
        assert inside(it, solve)
    for name in ("lpa.dispatch.move", "lpa.dispatch.frontier",
                 "lpa.sync.delta", "lpa.sync.frontier_mean"):
        for s in by[name]:
            assert any(inside(s, it) for it in iters), name
    # each program the loop dispatches sits in the span of its purpose
    for program, span in (("lpa_move", "lpa.dispatch.move"),
                          ("mark_frontier", "lpa.dispatch.frontier"),
                          ("bitwise_or", "lpa.dispatch.frontier"),
                          ("_mean", "lpa.sync.frontier_mean"),
                          ("_reduce_sum", "lpa.sync.delta"),
                          ("iota", "lpa.solve")):
        calls = [(e.name, e.start_ns, e.end_ns) for e in events
                 if e.name == f"PjitFunction({program})"]
        assert calls, program
        for c in calls:
            cover = [s for s in spans if inside(c, s)]
            assert max(cover, key=lambda s: s[1])[0] == span, program


@pytest.mark.parametrize("cfg,per_iter", [
    ({}, 2),                                             # delta + frontier
    ({"track_frontier": False}, 1),                      # delta only
    ({"frontier_gate": True, "track_frontier": False}, 1),
    ({"frontier_gate": True, "frontier_sparse": True}, 3),  # + sparse fit
], ids=["default", "untracked", "gated-untracked", "gated-sparse"])
def test_host_syncs_count_reads_per_iteration(graph, cfg, per_iter):
    res = lpa(graph, LPAConfig(**{**STREAM, **cfg}))
    assert res.iterations > 1
    assert res.host_syncs == per_iter * res.iterations


def test_sync_counts_and_spans_without_a_profiler():
    count = obs.SyncCount()
    for _ in range(3):
        with obs.sync("delta", count):
            pass
    with obs.span("lpa.iteration", it=0):
        pass
    assert count.host_syncs == 3


def mover_hlo(graph, cfg):
    ws = build_workspace(graph, cfg)
    move = mover(cfg, ws.bundle.cap_rows())
    return jax.jit(move, static_argnames=("sparse",)).lower(
        ws, jnp.arange(graph.n_nodes, dtype=jnp.int32), jnp.asarray(True),
        jnp.int32(1), frontier=None, sparse=False).compile().as_text()


@pytest.mark.parametrize("aligned,backend", [
    (True, "pallas_stream"), (False, "pallas_stream"),
    (False, "pallas_fused")], ids=["stream-aligned", "stream-regathered",
                                   "fused"])
def test_mover_ops_carry_the_scopes(graph, aligned, backend):
    cfg = LPAConfig(**{**STREAM, "fold_backend": backend,
                       "aligned_layout": aligned})
    ws = build_workspace(graph, cfg)
    rounds = len(ws.bundle.aux_for(get_engine(backend)).rounds)
    assert rounds >= 2  # a relayout between rounds
    op_names = set(re.findall(r'op_name="([^"]*)"', mover_hlo(graph, cfg)))
    scopes = {c for n in op_names for part in n.split(";")
              for c in part.split("/") if c.startswith("lpa.")}
    assert scopes >= {"lpa.gather", "lpa.relayout", "lpa.apply"} | {
        f"lpa.fold.r{i}" for i in range(rounds)}
    assert all(n.startswith("jit(lpa_move)/") for n in op_names
               if "/" in n)
    # siblings: no scope nests in another
    assert not any(n.count("lpa.") > 1 for n in op_names if ";" not in n)
