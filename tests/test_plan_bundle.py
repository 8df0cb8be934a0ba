"""Golden plan-equality for the declarative plan-build layer
(DESIGN.md §15): ``build_plan_bundle`` must reproduce every legacy
builder's output field for field — for every backend, both sketches ride
the same plans, both layouts, and both frontier modes — and the shard
path must stack to exactly the arrays the distributed workspace carries."""
import jax
import numpy as np
import pytest

from repro.core.fold_engine import resolve_auto
from repro.core.lpa import LPAConfig, build_workspace, lpa
from repro.core.plan_bundle import (PlanSpec, ShardSlice, build_plan_bundle,
                                    spec_for, stack_aligned_windows,
                                    stack_shard_bundles,
                                    uniform_round_count)
from repro.graphs.csr import (build_fold_plan, build_fused_fold_plan,
                              build_streamed_fold_plan, fused_active_rows,
                              fused_work_rows, streamed_active_windows,
                              streamed_work_rows)
from repro.graphs.generators import powerlaw_communities

K, CHUNK, TILE_R, WINDOW = 4, 8, 8, 64

# every registered fold backend, spelled out so this file doubles as the
# R5 plan-bundle fixture closure ("jnp", "pallas", "pallas_fused",
# "pallas_stream" must each appear as a golden case)
BACKENDS = ("jnp", "pallas", "pallas_fused", "pallas_stream")


def _graph(n=96, seed=0):
    g, _ = powerlaw_communities(n, p_in=0.4, mix=0.05, seed=seed)
    return g


def _tree_equal(a, b):
    """Field-for-field pytree equality: same treedef (static aux data
    included) and bit-equal leaves."""
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb, (ta, tb)
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def _spec(backend, aligned=False, **kw):
    return PlanSpec(backend=backend, k=K, chunk=CHUNK, tile_r=TILE_R,
                    aligned=aligned, stream_window=WINDOW, **kw)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("aligned", [False, True])
def test_bundle_reproduces_legacy_builders(backend, aligned):
    """The one entry point calls the exact csr builders the legacy
    ``build_workspace`` assembly did, with the same arguments."""
    g = _graph()
    degrees = np.asarray(g.degrees)
    bundle = build_plan_bundle(g, _spec(backend, aligned=aligned))
    _tree_equal(bundle.plan, build_fold_plan(degrees, k=K, chunk=CHUNK))
    if backend == "pallas_fused":
        _tree_equal(bundle.fused_plan,
                    build_fused_fold_plan(degrees, k=K, chunk=CHUNK,
                                          tile_r=TILE_R))
        assert bundle.stream_plan is None
    elif backend == "pallas_stream":
        _tree_equal(bundle.stream_plan,
                    build_streamed_fold_plan(
                        degrees, k=K, chunk=CHUNK, tile_r=TILE_R,
                        window_entries=WINDOW,
                        indices=np.asarray(g.indices),
                        weights=np.asarray(g.weights), aligned=aligned))
        assert bundle.stream_plan.aligned == aligned
        assert bundle.fused_plan is None
    else:
        # bucketed backends: the multi-width plan is the whole story
        assert bundle.fused_plan is None and bundle.stream_plan is None
    assert bundle.spec.backend == backend


def test_auto_spec_resolves_at_build_time():
    g = _graph()
    n_entries = int(np.asarray(g.degrees).sum())
    for budget in (1024, 1 << 40):
        expected = resolve_auto(n_entries, budget)
        bundle = build_plan_bundle(
            g, _spec("auto", vmem_budget_bytes=budget))
        assert bundle.spec.backend == expected
        if expected == "pallas_stream":
            assert bundle.stream_plan is not None
        else:
            assert bundle.fused_plan is not None
    # both policy branches really ran
    assert resolve_auto(n_entries, 1024) == "pallas_stream"
    assert resolve_auto(n_entries, 1 << 40) == "pallas_fused"


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown fold backend"):
        build_plan_bundle(_graph(32), _spec("tpu_v9"))


@pytest.mark.parametrize("backend", BACKENDS)
def test_sizing_policy_matches_csr_helpers(backend):
    """dense_work_rows / sparse_fit / default_cap_rows reproduce the
    sizing logic the drivers used to inline, per backend."""
    g = _graph()
    bundle = build_plan_bundle(g, _spec(backend))
    rng = np.random.default_rng(7)
    frontier = rng.random(g.n_nodes) < 0.3
    fits, work = bundle.sparse_fit(frontier, cap_rows=bundle.cap_rows())
    if backend == "pallas_fused":
        assert bundle.dense_work_rows() == fused_work_rows(bundle.fused_plan)
        counts = fused_active_rows(bundle.fused_plan, frontier)
        assert work == sum(counts)
        assert fits == all(c <= bundle.cap_rows() for c in counts)
    elif backend == "pallas_stream":
        assert bundle.dense_work_rows() == \
            streamed_work_rows(bundle.stream_plan)
        stats = streamed_active_windows(bundle.stream_plan, frontier)
        assert work == sum(r for _, r in stats)
        assert fits == all(w <= bundle.cap_rows() for w, _ in stats)
    else:
        # bucketed backends have no compacted path: always 'fit' dense
        assert bundle.dense_work_rows() == \
            sum(r.n_rows_total for r in bundle.plan.rounds)
        assert fits and work == bundle.dense_work_rows()
    assert bundle.default_cap_rows() >= 1
    capped = build_plan_bundle(g, _spec(backend, frontier_cap_rows=17))
    assert capped.cap_rows() == 17
    assert bundle.cap_rows() == bundle.default_cap_rows()


def test_spec_for_maps_config_fields():
    cfg = LPAConfig(method="mg", fold_backend="pallas_stream", k=4,
                    chunk=16, stream_window=256, aligned_layout=True,
                    vmem_budget_bytes=12345, frontier_cap_rows=9)
    spec = spec_for(cfg)
    assert spec == PlanSpec(backend="pallas_stream", k=4, chunk=16,
                            aligned=True, stream_window=256,
                            vmem_budget_bytes=12345, frontier_cap_rows=9)


def test_build_workspace_is_a_thin_wrapper():
    g = _graph()
    cfg = LPAConfig(method="mg", fold_backend="pallas_fused")
    ws = build_workspace(g, cfg)
    assert ws.bundle.spec == spec_for(cfg)
    # the legacy reads delegate to the bundle, not to copies
    assert ws.plan is ws.bundle.plan
    assert ws.fused_plan is ws.bundle.fused_plan
    assert ws.stream_plan is ws.bundle.stream_plan
    _tree_equal(ws.bundle,
                build_plan_bundle(g, spec_for(cfg)))


@pytest.mark.parametrize("method", ["mg", "bm"])
@pytest.mark.parametrize("backend", BACKENDS[1:])
def test_lpa_runs_bit_identical_through_the_bundle(method, backend):
    """End-to-end golden: every (backend, sketch, layout, frontier mode)
    trajectory through the bundle layer equals the jnp dense reference."""
    g = _graph(64, seed=3)
    ref = lpa(g, LPAConfig(method=method, rho=2))
    for aligned in ((False, True) if backend == "pallas_stream"
                    else (False,)):
        got = lpa(g, LPAConfig(method=method, rho=2, fold_backend=backend,
                               aligned_layout=aligned))
        assert got.iterations == ref.iterations
        np.testing.assert_array_equal(np.asarray(got.labels),
                                      np.asarray(ref.labels))
        sparse = lpa(g, LPAConfig(method=method, rho=2,
                                  fold_backend=backend,
                                  aligned_layout=aligned,
                                  frontier_gate=True,
                                  frontier_sparse=True))
        gated = lpa(g, LPAConfig(method=method, rho=2,
                                 frontier_gate=True))
        np.testing.assert_array_equal(np.asarray(sparse.labels),
                                      np.asarray(gated.labels))


# ---------------------------------------------------------------- shards


def _shards(n_shards=2, n=64, seed=1):
    g = _graph(n, seed=seed)
    degrees = np.asarray(g.degrees)
    bounds = np.linspace(0, g.n_nodes, n_shards + 1).astype(int)
    counts = [degrees[bounds[p]:bounds[p + 1]] for p in range(n_shards)]
    m_pad = int(max(c.sum() for c in counts))
    return g, counts, m_pad


def test_uniform_round_count_is_the_cross_shard_max():
    _, counts, _ = _shards()
    n_rounds = uniform_round_count(counts, k=K, chunk=CHUNK)
    per_shard = [uniform_round_count([c], k=K, chunk=CHUNK)
                 for c in counts]
    assert n_rounds == max(per_shard)


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused",
                                     "pallas_stream"])
def test_stacked_shard_plans_embed_each_bundle(backend):
    """Stacking pads to cross-shard maxima without moving any real row:
    each shard's slice of every stacked array equals its own bundle's
    rounds, and the pad region holds only sentinels."""
    _, counts, m_pad = _shards()
    spec = _spec(backend)
    n_rounds = uniform_round_count(counts, k=K, chunk=CHUNK)
    bundles = [build_plan_bundle(
        ShardSlice(counts=c, n_entries=m_pad, n_rounds=n_rounds), spec)
        for c in counts]
    plans = stack_shard_bundles(bundles)
    assert len(plans.round_gathers) == n_rounds
    for r in range(n_rounds):
        stacked = np.asarray(plans.round_gathers[r])
        for p, b in enumerate(bundles):
            gather = b.rounds[r][0]
            np.testing.assert_array_equal(stacked[p, :len(gather)], gather)
            assert (stacked[p, len(gather):] == -1).all()
    for p, b in enumerate(bundles):
        rv0 = b.rounds[0][1]
        np.testing.assert_array_equal(
            np.asarray(plans.row_vertex0)[p, :len(rv0)], rv0)
        np.testing.assert_array_equal(
            np.asarray(plans.bucket_rank0)[p, :len(rv0)], b.rounds[0][4])
    assert plans.max_rows0 == max(b.max_rows0 for b in bundles)
    if backend == "pallas_fused":
        assert len(plans.fused_starts) == n_rounds
        assert plans.fused_entries[0] == m_pad
        for p, b in enumerate(bundles):
            row_start = b.rounds[0][2]
            flat = np.asarray(plans.fused_starts[0])[p].reshape(-1)
            np.testing.assert_array_equal(flat[:len(row_start)], row_start)
    if backend == "pallas_stream":
        assert len(plans.stream_gathers) == n_rounds
        for p, b in enumerate(bundles):
            rr = b.stream_rounds[0]
            nw, w_s = rr["row_start"].shape[0], rr["window_entries"]
            got = np.asarray(plans.stream_gathers[0])[p, :nw, :w_s]
            np.testing.assert_array_equal(
                got, rr["entry_gather"].reshape(nw, w_s))


def test_remap_labels_is_the_round0_window_gather():
    """remap_labels(table) == gathering the table through round 0's
    window-ordered entry gather, with -1/0.0 pads — the per-iteration
    re-layout gather written once at build time."""
    _, counts, m_pad = _shards()
    spec = _spec("pallas_stream")
    n_rounds = uniform_round_count(counts, k=K, chunk=CHUNK)
    bundles = [build_plan_bundle(
        ShardSlice(counts=c, n_entries=m_pad, n_rounds=n_rounds), spec)
        for c in counts]
    rng = np.random.default_rng(5)
    tables = rng.integers(0, 1000, size=(len(bundles), m_pad)).astype(
        np.int32)
    wtabs = rng.random((len(bundles), m_pad)).astype(np.float32)
    for p, b in enumerate(bundles):
        pos, wts = b.remap_labels(tables[p], wtabs[p])
        rr = b.stream_rounds[0]
        nw, w_s = rr["row_start"].shape[0], rr["window_entries"]
        g0 = rr["entry_gather"].reshape(nw, w_s)
        expect_pos = np.where(g0 >= 0, tables[p][np.maximum(g0, 0)], -1)
        expect_wts = np.where(g0 >= 0, wtabs[p][np.maximum(g0, 0)], 0.0)
        np.testing.assert_array_equal(pos, expect_pos)
        np.testing.assert_array_equal(wts, expect_wts.astype(np.float32))
    ap, aw = stack_aligned_windows(bundles, tables, wtabs)
    ap, aw = np.asarray(ap), np.asarray(aw)
    # stacked layout pads per-shard windows to the cross-shard maxima
    n_win0 = max(x.stream_rounds[0]["row_start"].shape[0] for x in bundles)
    w_max0 = max(x.stream_rounds[0]["window_entries"] for x in bundles)
    for p, b in enumerate(bundles):
        pos, wts = b.remap_labels(tables[p], wtabs[p])
        nw, w_s = pos.shape
        grid_p = ap[p].reshape(n_win0, w_max0)
        grid_w = aw[p].reshape(n_win0, w_max0)
        np.testing.assert_array_equal(grid_p[:nw, :w_s], pos)
        np.testing.assert_array_equal(grid_w[:nw, :w_s], wts)
        assert (grid_p[nw:] == -1).all()
        assert (grid_p[:nw, w_s:] == -1).all()


def test_dist_workspace_rejects_fused_plus_stream():
    from repro.core.distributed import build_dist_workspace
    g = _graph(48)
    with pytest.raises(ValueError, match="mutually"):
        build_dist_workspace(g, 2, fused=True, stream=True)


def test_shard_bundle_auto_resolves_like_the_graph_path():
    _, counts, m_pad = _shards()
    n_rounds = uniform_round_count(counts, k=K, chunk=CHUNK)
    b = build_plan_bundle(
        ShardSlice(counts=counts[0], n_entries=m_pad, n_rounds=n_rounds),
        _spec("auto", vmem_budget_bytes=64))
    assert b.spec.backend == resolve_auto(m_pad, 64) == "pallas_stream"
    assert b.stream_rounds is not None and b.stream_final_rtv is not None


def test_shards_with_different_strides_stack_to_one_stride_per_round():
    """Each shard picks its rounds' strides from its own rows: a shard of
    hub rows keeps wide windows, a shard of short rows takes the narrowest.
    Stacking pads every round to one stride (the widest) and one window
    count without moving a real slot."""
    spec = PlanSpec(backend="pallas_stream", k=4, chunk=16, tile_r=32,
                    stream_window=512)
    counts = [np.asarray([600] * 4 + [6] * 100),
              np.asarray([2] * 300 + [6] * 200)]
    m_pad = int(max(c.sum() for c in counts))
    n_rounds = uniform_round_count(counts, k=4, chunk=16)
    bundles = [build_plan_bundle(
        ShardSlice(counts=c, n_entries=m_pad, n_rounds=n_rounds), spec)
        for c in counts]
    strides = [[r["window_entries"] for r in b.stream_rounds]
               for b in bundles]
    assert strides[0][0] != strides[1][0], strides
    plans = stack_shard_bundles(bundles)
    assert len(plans.stream_gathers) == n_rounds
    for r in range(n_rounds):
        n_win = max(b.stream_rounds[r]["row_start"].shape[0]
                    for b in bundles)
        w_max = max(s[r] for s in strides)
        stacked = np.asarray(plans.stream_gathers[r])
        assert stacked.shape == (len(bundles), n_win, w_max)
        for p, b in enumerate(bundles):
            rr = b.stream_rounds[r]
            nw, w_s = rr["row_start"].shape[0], rr["window_entries"]
            np.testing.assert_array_equal(
                stacked[p, :nw, :w_s], rr["entry_gather"].reshape(nw, w_s))
            assert (stacked[p, :nw, w_s:] == -1).all()
            assert (stacked[p, nw:] == -1).all()
            np.testing.assert_array_equal(
                np.asarray(plans.stream_starts[r])[p, :nw], rr["row_start"])
            np.testing.assert_array_equal(
                np.asarray(plans.stream_counts[r])[p, :nw], rr["row_count"])
