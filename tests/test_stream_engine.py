"""Streaming fold engine vs the repro.core.sketch reference — bit-identical.

The HBM-streaming engine (one kernel dispatch per round, entries windowed
through double-buffered VMEM blocks, final round fused with move
selection) must reproduce the reference ``run_mg_plan`` + ``select_best``
results bit-for-bit in interpret mode, on every fixture the fused engine
is validated on, plus window-boundary fixtures where rows end exactly on /
would straddle a window edge.

Also covers the ``auto`` engine policy (round-0 entry volume vs the VMEM
budget) and — slow-marked — the |E| >= 4M end-to-end run the ROADMAP's
VMEM-cap item demanded.
"""
import zlib

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.fold_engine import (DEFAULT_VMEM_BUDGET_BYTES, get_engine,
                                    resolve_auto)
from repro.core.lpa import LPAConfig, build_workspace, lpa
from repro.core.sketch import run_mg_plan, scatter_rows, select_best
from repro.graphs.csr import (_min_stream_stride, _pack_stream_windows,
                              build_csr, build_fold_plan,
                              build_streamed_fold_plan, fused_hbm_entries,
                              build_fused_fold_plan, stream_round_cost,
                              streamed_dispatches,
                              streamed_gather_slots, streamed_hbm_entries,
                              streamed_peak_window_bytes,
                              streamed_window_slots)
from repro.graphs.generators import chain_kmer, powerlaw_communities
from repro.kernels.mg_sketch.streaming import (run_mg_plan_stream,
                                               select_best_stream,
                                               windowed_entries)


def _star_graph(n_leaves=300):
    """One hub + leaves: the hub's 300 entries chunk into multiple rows,
    exercising the multi-round merge through the windowed layout."""
    edges = np.stack([np.zeros(n_leaves, np.int64),
                      np.arange(1, n_leaves + 1)], axis=1)
    return build_csr(edges, n_leaves + 1)


FIXTURES = {
    "powerlaw": lambda: powerlaw_communities(1024, p_in=0.4, mix=0.05,
                                             seed=7)[0],
    "road_deg2": lambda: chain_kmer(600, branch_prob=0.05, seed=3),
    "star_hub": lambda: _star_graph(300),
    "zero_degree": lambda: build_csr(
        np.asarray([[0, 1], [1, 2], [2, 0]]), 7),  # vertices 3..6 isolated
    "empty": lambda: build_csr(np.zeros((0, 2), np.int64), 5),
}


def _entries(g, rng):
    labels = jnp.asarray(rng.integers(0, max(g.n_nodes, 2),
                                      g.n_edges).astype(np.int32))
    weights = jnp.asarray((rng.random(g.n_edges) * 3 + 0.25)
                          .astype(np.float32))
    return labels, weights


def _stream_candidates(g, splan, el, ew, k):
    """Run the streamed fold and scatter padded rows to [N, k] arrays."""
    fs_k, fs_v = run_mg_plan_stream(splan, el, ew)
    n = g.n_nodes
    rtv = np.asarray(splan.row_to_vertex)
    safe = np.where(rtv >= 0, rtv, n)
    fcc = np.full((n + 1, k), -1, np.int32)
    fcw = np.zeros((n + 1, k), np.float32)
    fcc[safe] = np.asarray(fs_k)
    fcw[safe] = np.asarray(fs_v)
    return fcc[:n], fcw[:n]


@pytest.mark.parametrize("name", sorted(FIXTURES))
@pytest.mark.parametrize("k,chunk,tile_r,window",
                         [(8, 128, 128, 8192),  # production shape
                          (4, 16, 8, 64)])      # tiny windows, many rounds
def test_stream_fold_parity(name, k, chunk, tile_r, window):
    """Per-vertex candidate sketches are bit-identical to the reference."""
    g = FIXTURES[name]()
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    el, ew = _entries(g, rng)
    degrees = np.asarray(g.degrees)
    plan = build_fold_plan(degrees, k=k, chunk=chunk)
    splan = build_streamed_fold_plan(degrees, k=k, chunk=chunk,
                                     tile_r=tile_r, window_entries=window)
    s_k, s_v = run_mg_plan(plan, el, ew)
    cand_c, cand_w = scatter_rows(plan, s_k, s_v)
    fcc, fcw = _stream_candidates(g, splan, el, ew, k)
    np.testing.assert_array_equal(fcc, np.asarray(cand_c))
    np.testing.assert_array_equal(fcw, np.asarray(cand_w))


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_stream_select_parity(name):
    """Full streamed iteration (fold + in-kernel selection) matches
    run_mg_plan + select_best bit-for-bit across tie-break seeds."""
    g = FIXTURES[name]()
    rng = np.random.default_rng(zlib.crc32(name.encode()) + 1)
    el, ew = _entries(g, rng)
    degrees = np.asarray(g.degrees)
    plan = build_fold_plan(degrees, k=8, chunk=128)
    splan = build_streamed_fold_plan(degrees, k=8, chunk=128, tile_r=32,
                                     window_entries=512)
    labels = jnp.asarray(rng.integers(0, max(g.n_nodes, 2),
                                      g.n_nodes).astype(np.int32))
    s_k, s_v = run_mg_plan(plan, el, ew)
    for seed in (1, 2, 5, 11):
        ref = select_best(plan, s_k, s_v, labels, jnp.int32(seed))
        got = select_best_stream(splan, el, ew, labels, jnp.int32(seed))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_window_boundary_rows():
    """Rows that end exactly on a window edge stay put; rows that would
    straddle it are bumped whole into the next window (the plan's
    slice-safety invariant), and the re-layout still covers every entry
    exactly once."""
    # chunk=8, window cap 16: after the builder's ascending-count sort the
    # row widths are [5, 8, 8, 8]; row 0 leaves offset 5, so the next row's
    # full-chunk slice (5 + 8 <= 16) fits, but the one after (13 + 8 > 16)
    # would straddle the cap and is bumped whole into window 1 — where the
    # final row then ends exactly on the boundary (8 + 8 = 16).
    cap = 16
    degrees = np.asarray([8, 8, 5, 8])
    g_entries = int(degrees.sum())
    splan = build_streamed_fold_plan(degrees, k=4, chunk=8, tile_r=4,
                                     window_entries=cap)
    rnd = splan.rounds[0]
    rs = np.asarray(rnd.row_start)
    rc = np.asarray(rnd.row_count)
    # invariant: no row's full-chunk slice crosses the packing cap (the
    # materialized stride is lane-aligned >= cap, so a fortiori safe)
    assert ((rs + splan.chunk) * (rc > 0) <= cap).all()
    assert rnd.window_entries >= cap
    assert rnd.n_windows == 2
    np.testing.assert_array_equal(rc[0][rc[0] > 0], [5, 8])   # 13+8 > cap
    np.testing.assert_array_equal(rc[1][rc[1] > 0], [8, 8])   # exact fill
    # the windowed re-layout covers each source entry exactly once
    gather = np.asarray(rnd.entry_gather)
    covered = np.sort(gather[gather >= 0])
    np.testing.assert_array_equal(covered, np.arange(g_entries))
    # and the fold through it is still bit-identical to the reference
    rng = np.random.default_rng(0)
    el = jnp.asarray(rng.integers(0, 9, g_entries).astype(np.int32))
    ew = jnp.asarray((rng.random(g_entries) + 0.25).astype(np.float32))
    plan = build_fold_plan(degrees, k=4, chunk=8)
    s_k, s_v = run_mg_plan(plan, el, ew)
    cand_c, cand_w = scatter_rows(plan, s_k, s_v)
    fs_k, fs_v = run_mg_plan_stream(splan, el, ew)
    rtv = np.asarray(splan.row_to_vertex)
    for slot, v in enumerate(rtv):
        if v >= 0:
            np.testing.assert_array_equal(np.asarray(fs_k)[slot],
                                          np.asarray(cand_c)[v])
            np.testing.assert_array_equal(np.asarray(fs_v)[slot],
                                          np.asarray(cand_w)[v])


def test_exact_window_fill_keeps_single_window():
    """Rows that exactly fill the window (8 + 8 = 16 = cap) share it: the
    boundary itself is safe, only a *crossing* slice forces a bump."""
    splan = build_streamed_fold_plan(np.asarray([8, 8]), k=4, chunk=8,
                                     tile_r=4, window_entries=16)
    assert splan.rounds[0].n_windows == 1
    rc = np.asarray(splan.rounds[0].row_count)
    np.testing.assert_array_equal(rc[rc > 0], [8, 8])


def _round_rows(rnd):
    """A round's rows in packing order: the real row slots, window by
    window (every row holds at least one entry)."""
    rc = np.asarray(rnd.row_count).reshape(-1)
    return rc[rc > 0]


# 1,000 rows of 8 entries and two hubs of 64 full rows each: at a fixed cap
# the hub windows set an 8,192-slot stride for every window of the round
HUB_AND_SHORT_ROWS = np.asarray([8] * 1000 + [8192] * 2)


@pytest.mark.parametrize("aligned", [False, True],
                         ids=["regathered", "aligned"])
@pytest.mark.parametrize("name", ["powerlaw", "star_hub", "road_deg2"])
@pytest.mark.parametrize("chunk,tile_r,cap", [(128, 128, 8192),
                                              (16, 8, 1024),
                                              (16, 8, 64)])
def test_round_stride_chosen_from_its_rows(name, aligned, chunk, tile_r, cap):
    """Each round's stride lies between the kernels' narrowest stride and
    the cap (or is the cap's own stride where the cap is narrower), and its
    cost is no more than packing the same rows at the cap."""
    g = FIXTURES[name]()
    splan = build_streamed_fold_plan(
        np.asarray(g.degrees), k=4, chunk=chunk, tile_r=tile_r,
        window_entries=cap, indices=np.asarray(g.indices),
        weights=np.asarray(g.weights), aligned=aligned)
    assert splan.aligned == aligned
    floor = _min_stream_stride(chunk)
    for rnd in splan.rounds:
        rows = _round_rows(rnd)
        at_cap = _pack_stream_windows(rows, chunk, tile_r, cap)
        if cap >= floor:
            assert floor <= rnd.window_entries <= cap
        else:
            assert rnd.window_entries == at_cap["window_entries"]
            assert rnd.n_windows == at_cap["n_windows"]
        gathers = 1 if rnd.aligned else 2
        assert (stream_round_cost(rnd.n_windows, rnd.window_entries, gathers)
                <= stream_round_cost(at_cap["n_windows"],
                                     at_cap["window_entries"], gathers))
        # slice safety holds at the chosen stride
        rs, rc = np.asarray(rnd.row_start), np.asarray(rnd.row_count)
        assert ((rs + chunk) * (rc > 0) <= rnd.window_entries).all()


def test_hub_rows_no_longer_set_every_windows_stride():
    """On hub rows plus short rows the chosen strides gather at least 2x
    fewer window slots than packing every round at the 8,192-entry cap,
    and the fold and the selection through them stay bit-identical to the
    reference."""
    degrees = HUB_AND_SHORT_ROWS
    splan = build_streamed_fold_plan(degrees, k=8, chunk=128, tile_r=128,
                                     window_entries=8192)
    assert splan.n_rounds > 1
    at_cap = sum(p["n_windows"] * p["window_entries"] for p in (
        _pack_stream_windows(_round_rows(r), 128, 128, 8192)
        for r in splan.rounds))
    assert 2 * streamed_window_slots(splan) <= at_cap
    assert splan.rounds[0].window_entries < 8192
    rng = np.random.default_rng(3)
    n, m = len(degrees), int(degrees.sum())
    el = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
    ew = jnp.asarray((rng.random(m) * 3 + 0.25).astype(np.float32))
    plan = build_fold_plan(degrees, k=8, chunk=128)
    s_k, s_v = run_mg_plan(plan, el, ew)
    cand_c, cand_w = scatter_rows(plan, s_k, s_v)
    fs_k, fs_v = run_mg_plan_stream(splan, el, ew)
    rtv = np.asarray(splan.row_to_vertex)
    real = rtv >= 0
    np.testing.assert_array_equal(np.asarray(fs_k)[real],
                                  np.asarray(cand_c)[rtv[real]])
    np.testing.assert_array_equal(np.asarray(fs_v)[real],
                                  np.asarray(cand_w)[rtv[real]])
    labels = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    for seed in (1, 7):
        ref = select_best(plan, s_k, s_v, labels, jnp.int32(seed))
        got = select_best_stream(splan, el, ew, labels, jnp.int32(seed))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_auto_policy_resolution():
    """get_engine('auto') picks fused under the budget, streamed over it."""
    assert resolve_auto(1000) == "pallas_fused"
    assert resolve_auto(10**9) == "pallas_stream"
    # the cutover sits exactly at budget / 8 bytes-per-entry
    cut = DEFAULT_VMEM_BUDGET_BYTES // 8
    assert resolve_auto(cut) == "pallas_fused"
    assert resolve_auto(cut + 1) == "pallas_stream"
    assert get_engine("auto", n_entries=1000).name == "pallas_fused"
    assert get_engine("auto", n_entries=10**9).name == "pallas_stream"
    assert get_engine("auto", n_entries=10**9,
                      vmem_budget_bytes=2**40).name == "pallas_fused"
    with pytest.raises(ValueError):
        get_engine("auto")  # needs the entry volume to resolve
    with pytest.raises(ValueError):
        get_engine("nope")


def test_auto_workspace_builds_matching_plan():
    """build_workspace('auto') constructs exactly the plan the resolved
    engine consumes, and the driver's per-move resolution agrees."""
    g = FIXTURES["powerlaw"]()
    ws_fused = build_workspace(g, LPAConfig(method="mg",
                                            fold_backend="auto"))
    assert ws_fused.fused_plan is not None and ws_fused.stream_plan is None
    ws_stream = build_workspace(
        g, LPAConfig(method="mg", fold_backend="auto",
                     vmem_budget_bytes=1024))
    assert ws_stream.stream_plan is not None and ws_stream.fused_plan is None


def test_stream_engine_registry_parity():
    """pallas_stream resolves through get_engine and agrees bit-exactly
    with the reference on the plan-level engine surface."""
    g = FIXTURES["powerlaw"]()
    rng = np.random.default_rng(0)
    el, ew = _entries(g, rng)
    degrees = np.asarray(g.degrees)
    plan = build_fold_plan(degrees, k=8, chunk=128)
    splan = build_streamed_fold_plan(degrees, k=8, chunk=128, tile_r=32,
                                     window_entries=1024)
    ref_c, ref_w = get_engine("jnp").mg_candidates(plan, None, el, ew)
    c, w = get_engine("pallas_stream").mg_candidates(plan, splan, el, ew)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(ref_c))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(ref_w))
    with pytest.raises(ValueError):
        get_engine("pallas_stream").mg_candidates(plan, None, el, ew)


def test_stream_dispatch_and_residency_economics():
    """The streamed engine's headline numbers: fused dispatch count, fused
    HBM entry volume, and per-step residency bounded by the window cap
    instead of |E|."""
    g = FIXTURES["powerlaw"]()
    degrees = np.asarray(g.degrees)
    cap = 1024
    splan = build_streamed_fold_plan(degrees, k=8, chunk=128,
                                     window_entries=cap)
    fplan = build_fused_fold_plan(degrees, k=8, chunk=128)
    assert streamed_dispatches(splan) == splan.n_rounds
    # same real entries through HBM as the fused engine reads
    assert streamed_hbm_entries(splan) == fused_hbm_entries(fplan)
    # bounded residency: double-buffered window, not the flat entry arrays
    assert streamed_peak_window_bytes(splan) <= 2 * cap * 8
    assert streamed_peak_window_bytes(splan) < 8 * int(degrees.sum())
    # the windowed re-layout's slots cover at least the real entries
    assert streamed_window_slots(splan) >= streamed_hbm_entries(splan)


# ---------------------------------------------------------------------------
# window-aligned layout (LPAConfig(aligned_layout=True), DESIGN.md §13)
# ---------------------------------------------------------------------------

_ALIGNED_KW = dict(k=4, chunk=16, tile_r=8, window_entries=64)


def _aligned_plans(g):
    degrees = np.asarray(g.degrees)
    splan = build_streamed_fold_plan(degrees, **_ALIGNED_KW)
    aplan = build_streamed_fold_plan(degrees, indices=np.asarray(g.indices),
                                     weights=np.asarray(g.weights),
                                     aligned=True, **_ALIGNED_KW)
    return splan, aplan


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_aligned_layout_round_trip(name):
    """The aligned plan's pre-materialized round-0 arrays are EXACTLY what
    the unaligned path's windowed re-layout gather produces at runtime —
    parity with the unaligned engine is structural, not numerical."""
    g = FIXTURES[name]()
    splan, aplan = _aligned_plans(g)
    assert not splan.aligned
    if not splan.rounds:  # no entries -> nothing to align
        assert not aplan.aligned
        return
    assert aplan.aligned
    assert aplan.rounds[0].aligned
    assert all(not r.aligned for r in aplan.rounds[1:])
    # the round-0 gather degenerates to the identity permutation over the
    # real window slots, with -1 kept on pads
    eg = np.asarray(aplan.rounds[0].entry_gather)
    valid = eg >= 0
    np.testing.assert_array_equal(eg[valid], np.nonzero(valid)[0])
    assert aplan.rounds[0].n_entries_in == eg.shape[0]
    # pads carry the n_nodes sentinel and weight 0 (they cannot vote)
    aev = np.asarray(aplan.aligned_entry_vertex)
    aew = np.asarray(aplan.aligned_entry_weights)
    np.testing.assert_array_equal(aev[~valid],
                                  np.full((~valid).sum(), g.n_nodes))
    np.testing.assert_array_equal(aew[~valid], np.zeros((~valid).sum()))
    # round-trip: the driver's one O(slots) label gather reproduces the
    # unaligned re-layout bit-for-bit for any vertex labeling
    rng = np.random.default_rng(zlib.crc32(name.encode()) + 2)
    labels = jnp.asarray(rng.integers(0, max(g.n_nodes, 2),
                                      g.n_nodes).astype(np.int32))
    wl, ww = windowed_entries(splan.rounds[0].entry_gather,
                              labels[g.indices], g.weights)
    labels_ext = jnp.concatenate([labels, jnp.full((1,), -1, labels.dtype)])
    np.testing.assert_array_equal(np.asarray(labels_ext[aev]),
                                  np.asarray(wl))
    np.testing.assert_array_equal(aew, np.asarray(ww))


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_aligned_e2e_bit_parity(name):
    """Full LPA on the aligned streamed layout matches the unaligned
    streamed run (and hence the jnp reference) bit-for-bit."""
    g = FIXTURES[name]()
    base = dict(method="mg", rho=2, chunk=16, max_iters=8,
                fold_backend="pallas_stream", stream_window=256)
    ref = lpa(g, LPAConfig(**base))
    got = lpa(g, LPAConfig(aligned_layout=True, **base))
    assert ref.iterations == got.iterations
    np.testing.assert_array_equal(np.asarray(ref.labels),
                                  np.asarray(got.labels))


@pytest.mark.parametrize("method,rescan", [("mg", True), ("bm", False)])
def test_aligned_sketch_variants_bit_parity(method, rescan):
    """The rescan ablation and the BM sketch also fold bit-identically
    from the aligned layout (both consume the same round-0 arrays)."""
    for name in ("powerlaw", "star_hub"):
        g = FIXTURES[name]()
        base = dict(method=method, rescan=rescan, rho=2, chunk=16,
                    max_iters=8, fold_backend="pallas_stream",
                    stream_window=256)
        ref = lpa(g, LPAConfig(**base))
        got = lpa(g, LPAConfig(aligned_layout=True, **base))
        assert ref.iterations == got.iterations, name
        np.testing.assert_array_equal(np.asarray(ref.labels),
                                      np.asarray(got.labels))


def test_aligned_gather_accounting():
    """streamed_gather_slots declares the aligned layout's saving: the
    whole round-0 window grid — O(|E|) slots — stops being re-gathered
    every iteration, leaving only the tiny chunk-merge rounds."""
    g = FIXTURES["star_hub"]()  # multi-round: merge rounds still gather
    splan, aplan = _aligned_plans(g)
    assert splan.n_rounds > 1
    # unaligned: every window slot is written by the re-layout gather
    assert streamed_gather_slots(splan) == streamed_window_slots(splan)
    saved = streamed_gather_slots(splan) - streamed_gather_slots(aplan)
    r0 = splan.rounds[0]
    assert saved == r0.n_windows * r0.window_entries
    assert saved >= int(np.asarray(g.degrees).sum())  # the O(|E|) term
    # the later rounds' gathers are unchanged (their inputs are compacted
    # chunk-merge outputs, never pre-materializable at build time)
    assert streamed_gather_slots(aplan) == sum(
        r.n_windows * r.window_entries for r in splan.rounds[1:])


def test_aligned_requires_the_entry_arrays():
    degrees = np.asarray([3, 2, 1])
    with pytest.raises(ValueError, match="aligned"):
        build_streamed_fold_plan(degrees, k=4, chunk=16, aligned=True)


def test_auto_aligned_layout_streams_aligned():
    """aligned_layout rides through the auto policy: when the budget
    forces streaming, the workspace plan is aligned and the run still
    bit-matches the jnp reference."""
    g = FIXTURES["powerlaw"]()
    cfg = LPAConfig(method="mg", rho=2, fold_backend="auto",
                    vmem_budget_bytes=1024, aligned_layout=True)
    ws = build_workspace(g, cfg)
    assert ws.stream_plan is not None and ws.stream_plan.aligned
    res = lpa(g, cfg, ws=ws)
    ref = lpa(g, LPAConfig(method="mg", rho=2, fold_backend="jnp"))
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  np.asarray(ref.labels))


def test_lpa_e2e_stream_bit_matches_jnp():
    """End-to-end νMG8-LPA on the streaming backend: labels match the jnp
    backend bit-for-bit through full convergence."""
    g, _ = powerlaw_communities(2048, p_in=0.5, mix=0.02, seed=1)
    res_jnp = lpa(g, LPAConfig(method="mg", rho=2, fold_backend="jnp"))
    res_str = lpa(g, LPAConfig(method="mg", rho=2,
                               fold_backend="pallas_stream",
                               stream_window=1024))
    np.testing.assert_array_equal(np.asarray(res_jnp.labels),
                                  np.asarray(res_str.labels))
    res_auto = lpa(g, LPAConfig(method="mg", rho=2, fold_backend="auto",
                                vmem_budget_bytes=1024))
    np.testing.assert_array_equal(np.asarray(res_jnp.labels),
                                  np.asarray(res_auto.labels))


@pytest.mark.slow
@pytest.mark.streaming_e2e  # |E| >= 4M end-to-end in interpret mode (~30 s)
def test_stream_large_graph_e2e():
    """The ROADMAP's scale blocker: a 4M+-entry graph runs the streamed
    engine end-to-end in interpret mode with bounded per-window residency,
    bit-matching the reference."""
    from repro.graphs.generators import rmat
    g = rmat(17, edge_factor=20, seed=2)
    degrees = np.asarray(g.degrees)
    n_entries = int(degrees.sum())
    assert n_entries >= 4_000_000, n_entries
    cfg = LPAConfig(method="mg", rho=2, fold_backend="pallas_stream",
                    max_iters=2, track_frontier=False)
    ws = build_workspace(g, cfg)
    # far past the fused VMEM budget, yet resident bytes stay window-sized
    assert resolve_auto(n_entries) == "pallas_stream"
    peak = streamed_peak_window_bytes(ws.stream_plan)
    assert peak <= 2 * cfg.stream_window * 8
    assert peak * 100 < 8 * n_entries
    res = lpa(g, cfg, ws=ws)
    ref = lpa(g, LPAConfig(method="mg", rho=2, fold_backend="jnp",
                           max_iters=2, track_frontier=False))
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  np.asarray(ref.labels))
