"""Sparse frontier-gated fold execution — parity, wrinkles, accounting.

Contracts under test (DESIGN.md §8.5):
  * **parity** — ``frontier_sparse=True`` is bit-identical to the dense
    ``frontier_gate=True`` reference on every engine ("jnp" | "pallas" |
    "pallas_fused" | "pallas_stream"), both sketches (mg | bm) and the
    rescan ablation, for any row capacity: inactive vertices carry their
    label through unchanged and active vertices fold from real inputs.
  * **overflow fallback** — when a round's active unit count exceeds
    ``frontier_cap_rows`` the host falls back to the dense gated mover;
    results at cap = frontier size - 1 / size / size + 1 all agree.
  * **Pick-Less wrinkle** — a PL-deferred vertex in a quiet neighborhood
    (no changed neighbor) must stay queued, not frozen (§8.5 union rule).
  * **accounting** — ``work_rows_history`` matches the frontier fractions
    in ``frontier_history`` on one-row-per-vertex plans, and the engines'
    request-keyed ``dispatches_per_iter(plan, aux, request)`` matches the
    plan helpers for every routable request, with ``mode="sparse"`` never
    changing a count (kernelcheck R3 verifies the same statically).
  * **decoupling** — with ``frontier_gate`` and ``track_frontier`` both
    off, ``mark_frontier`` (the O(|E|) segment_max) is never called.
  * **marks from the mover** — on the dense MG fold of an aligned
    streamed plan the mover marks the frontier from its round-0 gather
    (``lpa_move(marks=)``): labels, moved counts and the frontier history
    are identical to ``mark_frontier``'s, ``mark_frontier`` is never
    called there, and every other path still calls it. ``lpa_move`` keeps
    its (new_labels, changed) contract there, so a wrapper of it runs.
"""
import importlib

import numpy as np
import jax.numpy as jnp
import pytest
from _propcheck import given, settings, st

# `repro.core.lpa` the attribute is shadowed by the function of the same
# name once the package re-exports it — resolve the module explicitly
lpa_mod = importlib.import_module("repro.core.lpa")
from repro.core.fold_engine import get_engine
from repro.core.fold_program import FoldRequest
from repro.core.lpa import (LPAConfig, build_workspace, lpa, lpa_move,
                            mark_frontier)
from repro.graphs.csr import (CSRGraph, build_csr, compact_active_rows,
                              fused_active_rows, fused_dispatches,
                              plan_dispatches, plan_round0_dispatches,
                              streamed_dispatches)
from repro.graphs.generators import chain_kmer, rmat, sbm

BACKENDS = ("jnp", "pallas", "pallas_fused", "pallas_stream")
SPARSE_BACKENDS = ("pallas_fused", "pallas_stream")  # the ones that skip rows


def _graph(seed=3):
    g, _ = sbm(4, 16, 0.5, 0.02, seed=seed)
    return g


def _config(backend, method="mg", rescan=False, **kw):
    base = dict(method=method, rescan=rescan, fold_backend=backend,
                chunk=16, max_iters=8, frontier_gate=True)
    if backend == "pallas_stream":
        base["stream_window"] = 128
    base.update(kw)
    return LPAConfig(**base)


def _assert_parity(g, backend, method, rescan, cap):
    dense = lpa(g, _config(backend, method, rescan))
    sparse = lpa(g, _config(backend, method, rescan, frontier_sparse=True,
                            frontier_cap_rows=cap))
    assert jnp.array_equal(dense.labels, sparse.labels), (
        backend, method, rescan, cap)
    assert dense.changed_history == sparse.changed_history
    assert dense.iterations == sparse.iterations


# ---------------------------------------------------------------------------
# property parity: every engine x sketch x rescan, random caps and graphs
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6),
       backend=st.sampled_from(BACKENDS),
       combo=st.sampled_from([("mg", False), ("mg", True), ("bm", False)]),
       cap=st.integers(min_value=1, max_value=256))
def test_sparse_gated_matches_dense_gated(seed, backend, combo, cap):
    method, rescan = combo
    _assert_parity(_graph(seed % 5), backend, method, rescan, cap)


def test_sparse_parity_all_engine_sketch_combos():
    """The exhaustive (engine, sketch, rescan) sweep at an always-fitting
    cap — the slice the analyze CI job replays under REPRO_CHECKED=1."""
    g = _graph()
    for backend in BACKENDS:
        for method, rescan in (("mg", False), ("mg", True), ("bm", False)):
            _assert_parity(g, backend, method, rescan, cap=10**9)


def test_sparse_parity_aligned_layout():
    """The window-aligned CSR layout (DESIGN.md §13) composes with the
    frontier-gated paths: dense gated, sparse gated, and the unaligned
    runs all agree bit-for-bit — including the folded-row accounting, so
    alignment changes WHERE round-0 entries come from, never which rows
    the sparse path folds."""
    g = _graph()
    for method, rescan in (("mg", False), ("mg", True), ("bm", False)):
        dense_u = lpa(g, _config("pallas_stream", method, rescan))
        dense_a = lpa(g, _config("pallas_stream", method, rescan,
                                 aligned_layout=True))
        sp = dict(frontier_sparse=True, frontier_cap_rows=10**9)
        sparse_u = lpa(g, _config("pallas_stream", method, rescan, **sp))
        sparse_a = lpa(g, _config("pallas_stream", method, rescan,
                                  aligned_layout=True, **sp))
        for got in (dense_a, sparse_u, sparse_a):
            assert jnp.array_equal(dense_u.labels, got.labels), (
                method, rescan)
            assert dense_u.iterations == got.iterations
        assert sparse_u.work_rows_history == sparse_a.work_rows_history


def test_overflow_fallback_at_cap_boundaries():
    """cap = frontier size - 1 / size / size + 1: the host fit decision
    flips between the sparse and dense movers, results never move."""
    g = _graph()
    for backend in SPARSE_BACKENDS:
        cfg = _config(backend)
        ws = build_workspace(g, cfg)
        probe = lpa(g, cfg, ws=ws)
        # the largest mid-run frontier count (iteration 0 is all-ones)
        counts = [int(round(f * g.n_nodes))
                  for f in probe.frontier_history[1:]]
        pivot = max(counts) if counts else 1
        for cap in (max(pivot - 1, 1), pivot, pivot + 1):
            _assert_parity(g, backend, "mg", False, cap)


def test_sparse_requires_gate_and_fold_plan():
    g = _graph()
    with pytest.raises(ValueError, match="frontier_gate"):
        lpa(g, LPAConfig(frontier_sparse=True))
    with pytest.raises(ValueError, match="exact"):
        lpa(g, LPAConfig(method="exact", frontier_gate=True,
                         frontier_sparse=True))
    cfg = _config("pallas_fused", frontier_sparse=True)
    ws = build_workspace(g, cfg)
    with pytest.raises(ValueError, match="needs a frontier"):
        lpa_move(ws, jnp.arange(g.n_nodes, dtype=jnp.int32),
                 jnp.asarray(False), jnp.int32(1), cfg, frontier=None,
                 sparse=True, cap_rows=8)


def test_sparse_folds_fewer_rows_than_dense():
    """The point of the tentpole: once the frontier thins (iteration >= 2),
    the compacted engines grid over strictly fewer rows. Disconnected
    cliques converge fast, collapsing the frontier hard; tau=0 keeps the
    loop running so the thin-frontier iterations are actually recorded."""
    g, _ = sbm(8, 8, 0.9, 0.0, seed=1)
    for backend in SPARSE_BACKENDS:
        extra = {"stream_window": 32} if backend == "pallas_stream" else {}
        base = dict(method="mg", fold_backend=backend, chunk=16,
                    max_iters=8, tau=0.0, frontier_gate=True, **extra)
        dense = lpa(g, LPAConfig(**base))
        sparse = lpa(g, LPAConfig(frontier_sparse=True,
                                  frontier_cap_rows=10**9, **base))
        assert jnp.array_equal(dense.labels, sparse.labels)
        tail_d = dense.work_rows_history[2:]
        tail_s = sparse.work_rows_history[2:]
        assert sum(tail_s) < sum(tail_d), backend
        assert all(s <= d for s, d in zip(tail_s, tail_d))


# ---------------------------------------------------------------------------
# compaction unit + Pick-Less wrinkle + mark_frontier edge cases
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(rows=st.integers(min_value=0, max_value=50),
       cap=st.integers(min_value=1, max_value=60),
       seed=st.integers(min_value=0, max_value=10**6))
def test_compact_active_rows_properties(rows, cap, seed):
    rng = np.random.default_rng(seed)
    active = rng.random(rows) < 0.4
    idx = np.asarray(compact_active_rows(jnp.asarray(active), cap))
    assert idx.shape == (cap,)
    want = np.nonzero(active)[0][:cap]
    assert (idx[:len(want)] == want).all()       # active rows, in order
    assert (idx[len(want):] == rows).all()       # sentinel padding


def test_compact_active_rows_all_empty_frontier():
    """Zero active rows: the compaction is pure sentinel padding, so a
    sparse round with an all-quiet frontier folds nothing (every slot
    gathers the neutral pad entries)."""
    idx = np.asarray(compact_active_rows(jnp.zeros(7, jnp.bool_), 4))
    assert idx.shape == (4,)
    assert (idx == 7).all()


def test_compact_active_rows_exactly_full_cap():
    """cap == active count: every active row lands, in order, with no
    sentinel slot left over and no overflow truncation."""
    idx = np.asarray(compact_active_rows(jnp.ones(5, jnp.bool_), 5))
    assert idx.tolist() == [0, 1, 2, 3, 4]


def test_pick_less_deferred_vertex_is_not_frozen():
    """§8.5 wrinkle: vertex 0 wants a *larger* label in the PL iteration
    (blocked) while its only neighbor is quiet — no changed neighbor, so
    the marks alone would freeze it with the wrong label. The PL union
    must keep it queued."""
    # clique {1,2,3} collapses to label 1 in iteration 0 while vertex 1
    # itself is PL-blocked (its majority is 2/3-tied, both larger), so
    # vertex 0's neighborhood {1} sees no change.
    edges = np.asarray([[0, 1], [1, 2], [1, 3], [2, 3]])
    weights = np.asarray([5.0, 20.0, 20.0, 1.0], np.float32)
    g = build_csr(edges, 4, weights=weights)
    for sparse in (False, True):
        got = lpa(g, LPAConfig(method="mg", chunk=16, rho=8, max_iters=8,
                               frontier_gate=True, frontier_sparse=sparse,
                               frontier_cap_rows=10**9 if sparse else None))
        ref = lpa(g, LPAConfig(method="mg", chunk=16, rho=8, max_iters=8))
        assert jnp.array_equal(got.labels, ref.labels)
        assert np.asarray(got.labels).tolist() == [1, 1, 1, 1]
        # the union kept everything queued out of the quiet PL iteration
        assert got.frontier_history[1] == 1.0


def test_mark_frontier_isolated_and_self_loops():
    # manual CSR: vertex 0 has a self-loop, 1-2 are connected, 3 isolated
    g = CSRGraph(offsets=jnp.asarray([0, 1, 2, 3, 3], jnp.int32),
                 indices=jnp.asarray([0, 2, 1], jnp.int32),
                 weights=jnp.ones((3,), jnp.float32),
                 n_nodes=4, n_edges=3)
    ws = build_workspace(g, LPAConfig(chunk=16))
    marked = mark_frontier(ws, jnp.asarray([True, False, False, False]))
    # the self-loop marks its own vertex; nobody else changed
    assert np.asarray(marked).tolist() == [True, False, False, False]
    marked = mark_frontier(ws, jnp.asarray([False, True, False, True]))
    # isolated vertex 3 'changing' marks nobody; 1 marks its neighbor 2
    assert np.asarray(marked).tolist() == [False, False, True, False]
    # isolated vertices are never marked (no incoming edges)
    marked = mark_frontier(ws, jnp.ones((4,), jnp.bool_))
    assert not bool(marked[3])


# ---------------------------------------------------------------------------
# accounting: work rows vs frontier history, dispatch declarations
# ---------------------------------------------------------------------------

def test_work_rows_match_frontier_history():
    """One row per vertex (degrees <= chunk, single round): the fused
    sparse path's folded rows ARE the frontier counts."""
    g = _graph()
    assert int(np.asarray(g.degrees).max()) <= 64
    res = lpa(g, _config("pallas_fused", chunk=64, frontier_sparse=True,
                         frontier_cap_rows=10**9))
    n = g.n_nodes
    assert len(res.work_rows_history) == res.iterations
    for frac, rows in zip(res.frontier_history, res.work_rows_history):
        assert rows == int(round(frac * n))


def test_bucketed_backends_fold_densely():
    """jnp/pallas have no compacted path: sparse delegates to the dense
    fold, so every iteration records the full plan rows."""
    g = _graph()
    for backend in ("jnp", "pallas"):
        res = lpa(g, _config(backend, frontier_sparse=True,
                             frontier_cap_rows=10**9))
        assert len(set(res.work_rows_history)) == 1


def test_request_dispatch_table_is_golden():
    """The full request-keyed dispatch table (DESIGN.md §14): one
    ``dispatches_per_iter(plan, aux, request)`` per engine, checked for
    every (backend, family, rescan) cell against the plan helpers — and
    for both modes, because sparse compaction shrinks grids *inside* the
    same dispatches and must never change a count."""
    g = _graph()
    ws_f = build_workspace(g, _config("pallas_fused"))
    ws_s = build_workspace(g, _config("pallas_stream"))
    frontier = jnp.ones(g.n_nodes, jnp.bool_)
    plans = {"jnp": (ws_f.plan, None), "pallas": (ws_f.plan, None),
             "pallas_fused": (ws_f.plan, ws_f.fused_plan),
             "pallas_stream": (ws_s.plan, ws_s.stream_plan)}
    r_fused = fused_dispatches(ws_f.fused_plan)
    r_stream = streamed_dispatches(ws_s.stream_plan)
    golden = {
        ("jnp", "mg", False): 0,
        ("jnp", "bm", False): 0,
        ("jnp", "mg", True): 0,
        ("pallas", "mg", False): plan_dispatches(ws_f.plan),
        ("pallas", "bm", False): plan_round0_dispatches(ws_f.plan),
        ("pallas", "mg", True): plan_dispatches(ws_f.plan),
        ("pallas_fused", "mg", False): r_fused,
        ("pallas_fused", "bm", False): 1,
        ("pallas_fused", "mg", True): r_fused + 1,
        ("pallas_stream", "mg", False): r_stream,
        ("pallas_stream", "bm", False): 1,
        ("pallas_stream", "mg", True): r_stream + 1,
    }
    for (backend, family, rescan), want in golden.items():
        eng = get_engine(backend)
        plan, aux = plans[backend]
        dense = FoldRequest(family=family, rescan=rescan)
        sparse = FoldRequest(family=family, rescan=rescan, mode="sparse",
                             frontier=frontier, cap_rows=8)
        for req in (dense, sparse):
            got = eng.dispatches_per_iter(plan, aux, req)
            assert got == want, (backend, family, rescan, req.mode)


def test_fused_active_rows_tracks_the_frontier():
    g = _graph()
    ws = build_workspace(g, _config("pallas_fused"))
    all_on = np.ones(g.n_nodes, bool)
    none_on = np.zeros(g.n_nodes, bool)
    full = fused_active_rows(ws.fused_plan, all_on)
    empty = fused_active_rows(ws.fused_plan, none_on)
    assert all(e == 0 for e in empty)
    assert full[0] > 0
    one_on = none_on.copy()
    one_on[0] = True
    assert fused_active_rows(ws.fused_plan, one_on)[0] >= 1


# ---------------------------------------------------------------------------
# track_frontier decoupling: segment_max only when needed
# ---------------------------------------------------------------------------

def test_mark_frontier_only_called_when_needed(monkeypatch):
    g = _graph()
    calls = []
    real = mark_frontier

    def counting(ws, changed):
        calls.append(1)
        return real(ws, changed)

    monkeypatch.setattr(lpa_mod, "mark_frontier", counting)

    # both off: the O(|E|) segment_max is never paid
    res = lpa(g, LPAConfig(chunk=16, max_iters=4, frontier_gate=False,
                           track_frontier=False), jit=False)
    assert calls == []
    assert res.frontier_history == []

    # gate on, tracking off: marks are computed (the gate needs them)
    # but no history is recorded — track_frontier does not re-enable
    res = lpa(g, LPAConfig(chunk=16, max_iters=4, frontier_gate=True,
                           track_frontier=False), jit=False)
    assert len(calls) > 0
    assert res.frontier_history == []

    # tracking alone also computes marks, and records the history
    calls.clear()
    res = lpa(g, LPAConfig(chunk=16, max_iters=4, frontier_gate=False,
                           track_frontier=True), jit=False)
    assert len(calls) > 0
    assert len(res.frontier_history) == res.iterations


# ---------------------------------------------------------------------------
# frontier marks from the mover's round-0 pass (DESIGN.md §8.5)
# ---------------------------------------------------------------------------

def _weighted_rmat():
    """A weighted R-MAT whose hub rows span several 16-entry chunks: hub
    vertices own several round-0 rows, and the plan has merge rounds."""
    g0 = rmat(8, edge_factor=8, seed=5)
    src, dst = np.asarray(g0.sources()), np.asarray(g0.indices)
    e = np.stack([src[src < dst], dst[src < dst]], 1)
    w = np.random.default_rng(5).random(len(e)).astype(np.float32)
    return build_csr(e, g0.n_nodes, weights=w)


MARK_GRAPHS = {"rmat_hubs": _weighted_rmat,
               "kmer_chain": lambda: chain_kmer(600, branch_prob=0.05,
                                                seed=3)}
# rho=3 and tau=0: every run crosses the Pick-Less iterations 3 and 6
MARK_BASE = dict(method="mg", k=4, chunk=16, rho=3, tau=0.0, max_iters=8,
                 fold_backend="pallas_stream", stream_window=256,
                 aligned_layout=True)


def _counting_mark_frontier(monkeypatch):
    calls = []

    def counting(ws, changed):
        calls.append(1)
        return mark_frontier(ws, changed)

    monkeypatch.setattr(lpa_mod, "mark_frontier", counting)
    return calls


@pytest.mark.parametrize("mode", [
    {}, {"frontier_gate": True},
    {"frontier_gate": True, "track_frontier": False}],
    ids=["tracked", "gated", "gated-untracked"])
@pytest.mark.parametrize("name", sorted(MARK_GRAPHS))
def test_mover_marks_match_mark_frontier(monkeypatch, name, mode):
    """The same aligned workspace solved twice: marks from the mover's
    round-0 pass, then (``marks_in_mover`` patched off) from
    ``mark_frontier``. Labels, moved counts, the frontier entering every
    iteration and, under the gate, every gated move agree exactly."""
    g = MARK_GRAPHS[name]()
    cfg = LPAConfig(**MARK_BASE, **mode)
    ws = build_workspace(g, cfg)
    if name == "rmat_hubs":
        rv0 = np.asarray(ws.stream_plan.row_to_vertex0)
        assert np.bincount(rv0[rv0 >= 0]).max() > 1  # hub rows split
        assert ws.stream_plan.n_rounds > 1
    else:
        assert ws.stream_plan.n_rounds == 1  # the fused selection marks
    assert lpa_mod.marks_in_mover(ws, cfg)
    calls = _counting_mark_frontier(monkeypatch)
    got = lpa(g, cfg, ws=ws)
    assert calls == []
    monkeypatch.setattr(lpa_mod, "marks_in_mover", lambda ws, config: False)
    ref = lpa(g, cfg, ws=ws)
    assert calls
    assert ref.iterations == got.iterations > 2 * cfg.rho
    np.testing.assert_array_equal(np.asarray(got.labels),
                                  np.asarray(ref.labels))
    assert got.changed_history == ref.changed_history
    assert got.frontier_history == ref.frontier_history
    assert len(got.frontier_history) == (
        got.iterations if cfg.track_frontier else 0)
    if cfg.track_frontier:  # the marks thin the frontier, not all ones
        assert min(got.frontier_history) < 1.0
    assert got.mover_marks == got.iterations - 1
    assert ref.mover_marks == 0
    assert got.host_syncs == ref.host_syncs


@pytest.mark.parametrize("change", [
    {"fold_backend": "jnp"}, {"fold_backend": "pallas_fused"},
    {"aligned_layout": False}, {"method": "bm"}, {"rescan": True},
    {"frontier_gate": True, "frontier_sparse": True,
     "frontier_cap_rows": 10**9}],
    ids=["jnp", "pallas_fused", "unaligned", "bm", "rescan", "sparse"])
def test_mover_marks_fallbacks_keep_mark_frontier(monkeypatch, change):
    """Every path but the dense MG fold of an aligned streamed plan keeps
    ``mark_frontier``, and counts no mover marks."""
    g = _graph()
    cfg = LPAConfig(**{**MARK_BASE, "max_iters": 4, **change})
    calls = _counting_mark_frontier(monkeypatch)
    res = lpa(g, cfg)
    assert calls
    assert res.mover_marks == 0
    assert len(res.frontier_history) == res.iterations


def _moves_nothing(orig):
    def move(ws, labels, *a, **k):
        return labels, jnp.zeros(labels.shape, bool)
    return move


def _passes_through(orig):
    def move(ws, labels, *a, **k):
        new, changed = orig(ws, labels, *a, **k)
        return new, changed
    return move


@pytest.mark.parametrize("wrap", [_passes_through, _moves_nothing],
                         ids=["passes-through", "moves-nothing"])
def test_marking_mover_keeps_its_two_output_contract(monkeypatch, wrap):
    """On the marking path ``lpa()`` still calls ``lpa_move`` for
    (new_labels, changed), so a wrapper of it runs there: one that passes
    the call on leaves the solve and its marks as they were; one that
    marks nothing gets ``mark_frontier``'s marks in the same program."""
    g = _graph()
    cfg = LPAConfig(**{**MARK_BASE, "max_iters": 4})
    ws = build_workspace(g, cfg)
    assert lpa_mod.marks_in_mover(ws, cfg)
    want = lpa(g, cfg, ws=ws)
    calls = _counting_mark_frontier(monkeypatch)
    monkeypatch.setattr(lpa_mod, "lpa_move", wrap(lpa_mod.lpa_move))
    got = lpa(g, cfg, ws=ws)
    if wrap is _passes_through:
        assert calls == []
        np.testing.assert_array_equal(np.asarray(got.labels),
                                      np.asarray(want.labels))
        assert got.changed_history == want.changed_history
        assert got.frontier_history == want.frontier_history
    else:
        assert calls
        np.testing.assert_array_equal(np.asarray(got.labels),
                                      np.arange(g.n_nodes))
        # nothing moves: the Pick-Less iteration 0 keeps the first two
        # frontiers whole, then nothing is marked
        assert got.changed_history == [0, 0, 0, 0]
        assert got.frontier_history == [1.0, 1.0, 0.0, 0.0]


def test_mover_marks_nothing_when_nothing_needs_marks(monkeypatch):
    """Neither the gate nor the history: the aligned mover packs no bits
    and nothing marks the frontier."""
    g = _graph()
    cfg = LPAConfig(**{**MARK_BASE, "max_iters": 4, "track_frontier": False})
    calls = _counting_mark_frontier(monkeypatch)
    res = lpa(g, cfg)
    assert calls == []
    assert res.mover_marks == 0 and res.frontier_history == []
