"""Multi-device tests. These need >1 XLA host device, so each runs in a
subprocess with its own XLA_FLAGS (conftest keeps the main process at one
device so smoke tests see the real topology)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dist_lpa_matches_single_device():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.graphs.generators import powerlaw_communities
        from repro.core.distributed import build_dist_workspace, dist_lpa
        from repro.core.lpa import lpa, LPAConfig
        from repro.core.modularity import modularity
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("shard",))
        g, _ = powerlaw_communities(1536, p_in=0.5, mix=0.02, seed=1)
        ws = build_dist_workspace(g, 8)
        labels, iters = dist_lpa(mesh, ws, rho=2)
        res = lpa(g, LPAConfig(method="mg", rho=2))
        assert (np.asarray(labels) == np.asarray(res.labels)).all(), \\
            "distributed labels diverge from single-device"
        print("Q=", float(modularity(g, labels)))
    """)
    assert "Q=" in out


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dist_bundle_workspace_matches_single_host_plain_and_halo():
    """The collapsed workspace builder (edge-balanced partition ->
    per-shard build_plan_bundle -> halo remap) stays bit-identical to
    single-host lpa() on BOTH exchange modes for both sketches — the
    distributed half of the PlanBundle golden-parity contract
    (tests/test_plan_bundle.py covers the single-host half)."""
    _run("""
        import numpy as np, jax
        from repro.graphs.generators import powerlaw_communities
        from repro.core.distributed import build_dist_workspace, dist_lpa
        from repro.core.lpa import lpa, LPAConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("shard",))
        g, _ = powerlaw_communities(768, p_in=0.5, mix=0.02, seed=7)
        ws = build_dist_workspace(g, 4)
        ws_h = build_dist_workspace(g, 4, halo=True)
        for method in ("mg", "bm"):
            ref = lpa(g, LPAConfig(method=method, rho=2))
            for tag, w in (("plain", ws), ("halo", ws_h)):
                got, it = dist_lpa(mesh, w, rho=2, method=method)
                assert it == ref.iterations, (method, tag)
                assert (np.asarray(got) == np.asarray(ref.labels)).all(), \\
                    (method, tag)
        print("bundle dist parity ok")
    """, devices=4)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dist_lpa_2d_mesh_with_partitioner():
    """Distributed LPA over a 2-D mesh (flattened axes) with the
    LPA-community locality reorder feeding the shard layout."""
    _run("""
        import numpy as np, jax
        from repro.graphs.generators import powerlaw_communities
        from repro.graphs.partition import lpa_partition
        from repro.core.distributed import build_dist_workspace, dist_lpa
        from repro.core.modularity import modularity
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        g, _ = powerlaw_communities(1024, p_in=0.5, mix=0.02, seed=3)
        part = lpa_partition(g, 8)
        ws = build_dist_workspace(g, 8, order=part.order)
        labels, iters = dist_lpa(mesh, ws, rho=2)
        q = float(modularity(g, labels))
        assert q > 0.35, q
        assert len(np.unique(np.asarray(labels))) > 4
    """, devices=8)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dp_train_step_with_compression():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.train.steps import make_dp_train_step
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2)

        init, step = make_dp_train_step(loss_fn, mesh, axis_name="data",
                                        peak_lr=3e-2, warmup=1, total=100)
        params = {"w": jnp.zeros((6,))}
        opt, err = init(params)
        k = jax.random.PRNGKey(0)
        w_true = jnp.arange(6, dtype=jnp.float32) / 3 - 1
        losses = []
        for i in range(40):
            kk = jax.random.fold_in(k, i)
            x = jax.random.normal(kk, (32, 6))
            batch = {"x": x, "y": x @ w_true}
            params, opt, err, m = step(params, opt, err, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < 0.05 * losses[0], losses
    """, devices=4)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_compressed_vs_plain_allreduce_agree():
    """int8 EF all-reduce must track plain f32 within quantization error."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("d",))

        def body(g, e):
            mean, new_e = compressed_psum({"g": g}, {"g": e}, "d")
            plain = jax.lax.pmean(g, "d")
            return mean["g"], new_e["g"], plain

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                    in_specs=(P("d"), P("d")), out_specs=(P("d"), P("d"),
                    P("d")), check_vma=False))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
        e = jnp.zeros((4, 64), jnp.float32)
        mean, new_e, plain = f(g, e)
        err = np.abs(np.asarray(mean) - np.asarray(plain)).max()
        scale = np.abs(np.asarray(g)).max() / 127.0
        assert err <= scale + 1e-6, (err, scale)
    """, devices=4)


def test_multihost_checkpoint_shards():
    import numpy as np
    import jax.numpy as jnp
    from repro.checkpoint.manager import CheckpointManager
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2, n_hosts=2)
        t0 = {"w": jnp.arange(4.0)}
        t1 = {"w": jnp.arange(4.0) + 100}
        mgr.save(10, t0, host=0)
        # only one of two host shards present -> step is NOT restorable
        assert mgr.latest_step() is None
        mgr.save(10, t1, host=1)
        assert mgr.latest_step() == 10
        r0, _ = mgr.restore(t0, host=0)
        r1, _ = mgr.restore(t0, host=1)
        np.testing.assert_array_equal(np.asarray(r0["w"]), np.arange(4.0))
        np.testing.assert_array_equal(np.asarray(r1["w"]),
                                      np.arange(4.0) + 100)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dist_fused_engine_matches_reference():
    """The fused fold engine under shard_map (plain and halo label
    exchange) is bit-identical to the bucketed reference engine."""
    _run("""
        import numpy as np, jax
        from repro.graphs.generators import powerlaw_communities
        from repro.core.distributed import build_dist_workspace, dist_lpa
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("shard",))
        g, _ = powerlaw_communities(1024, p_in=0.5, mix=0.02, seed=5)
        ws = build_dist_workspace(g, 4)
        ref, _ = dist_lpa(mesh, ws, rho=2)
        ws_f = build_dist_workspace(g, 4, fused=True, tile_r=32)
        got, _ = dist_lpa(mesh, ws_f, rho=2, engine="pallas_fused")
        assert (np.asarray(ref) == np.asarray(got)).all(), "fused diverges"
        ws_h = build_dist_workspace(g, 4, halo=True, fused=True, tile_r=32)
        got_h, _ = dist_lpa(mesh, ws_h, rho=2, engine="pallas_fused")
        assert (np.asarray(ref) == np.asarray(got_h)).all(), \\
            "halo+fused diverges"
        print("fused dist parity ok")
    """, devices=4)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dist_stream_engine_matches_reference():
    """The HBM-streaming fold engine under shard_map (plain and halo label
    exchange) is bit-identical to the bucketed reference engine."""
    _run("""
        import numpy as np, jax
        from repro.graphs.generators import powerlaw_communities
        from repro.core.distributed import build_dist_workspace, dist_lpa
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("shard",))
        g, _ = powerlaw_communities(1024, p_in=0.5, mix=0.02, seed=5)
        ws = build_dist_workspace(g, 4)
        ref, _ = dist_lpa(mesh, ws, rho=2)
        ws_s = build_dist_workspace(g, 4, stream=True, tile_r=32,
                                    window_entries=512)
        got, _ = dist_lpa(mesh, ws_s, rho=2, engine="pallas_stream")
        assert (np.asarray(ref) == np.asarray(got)).all(), "stream diverges"
        ws_h = build_dist_workspace(g, 4, halo=True, stream=True, tile_r=32,
                                    window_entries=512)
        got_h, _ = dist_lpa(mesh, ws_h, rho=2, engine="pallas_stream")
        assert (np.asarray(ref) == np.asarray(got_h)).all(), \\
            "halo+stream diverges"
        print("stream dist parity ok")
    """, devices=4)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dist_aligned_layout_matches_unaligned():
    """Window-aligned shards (build_dist_workspace(aligned=True)): the
    streamed shard mover gathers round-0 labels straight into window
    order instead of re-laying them each iteration, and stays
    bit-identical to the unaligned streamed run on every exchange mode,
    both sketches, and under the per-shard frontier gate."""
    _run("""
        import numpy as np, jax
        from repro.graphs.generators import powerlaw_communities
        from repro.core.distributed import build_dist_workspace, dist_lpa
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("shard",))
        g, _ = powerlaw_communities(768, p_in=0.5, mix=0.02, seed=5)
        kw = dict(stream=True, tile_r=32, window_entries=512)
        ws = build_dist_workspace(g, 4, **kw)
        ws_a = build_dist_workspace(g, 4, aligned=True, **kw)
        ws_h = build_dist_workspace(g, 4, halo=True, **kw)
        ws_ha = build_dist_workspace(g, 4, halo=True, aligned=True, **kw)
        for method in ("mg", "bm"):
            for ref_ws, got_ws, tag in ((ws, ws_a, "plain"),
                                        (ws_h, ws_ha, "halo")):
                ref, ri = dist_lpa(mesh, ref_ws, rho=2,
                                   engine="pallas_stream", method=method)
                got, gi = dist_lpa(mesh, got_ws, rho=2,
                                   engine="pallas_stream", method=method)
                assert ri == gi, (tag, method)
                assert (np.asarray(ref) == np.asarray(got)).all(), \\
                    (tag, method)
        ref, ri = dist_lpa(mesh, ws, rho=2, engine="pallas_stream",
                           frontier_gate=True)
        got, gi = dist_lpa(mesh, ws_a, rho=2, engine="pallas_stream",
                           frontier_gate=True)
        assert ri == gi and (np.asarray(ref) == np.asarray(got)).all()
        try:
            build_dist_workspace(g, 4, aligned=True)
        except ValueError:
            pass
        else:
            raise AssertionError("aligned=True without stream must raise")
        print("aligned dist parity ok")
    """, devices=4)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dist_stream_shards_with_different_strides_match_single_host():
    """A shard of hub rows and a shard of short rows pick different
    window strides per round; the stacked workspace pads them to one
    stride per round, and the streamed dist_lpa (plain and aligned) still
    gives single-host lpa()'s labels."""
    _run("""
        import numpy as np
        from repro.core.distributed import (_edge_balanced_ranges,
                                            build_dist_workspace, dist_lpa)
        from repro.core.lpa import lpa, LPAConfig
        from repro.core.plan_bundle import (PlanSpec, ShardSlice,
                                            build_plan_bundle,
                                            uniform_round_count)
        from repro.graphs.csr import build_csr
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,), ("shard",))
        n, n_hubs, deg = 600, 4, 600
        chain = np.stack([np.arange(n_hubs, n - 1),
                          np.arange(n_hubs + 1, n)], 1)
        hubs = np.stack([np.repeat(np.arange(n_hubs), deg),
                         n_hubs + np.arange(n_hubs * deg) * 7 % (n - n_hubs)],
                        1)
        g = build_csr(np.concatenate([chain, hubs]), n)
        kw = dict(k=4, chunk=16, tile_r=32)
        degrees = np.asarray(g.degrees)
        bounds = _edge_balanced_ranges(degrees, 2)
        counts = [degrees[bounds[p]:bounds[p + 1]] for p in range(2)]
        n_rounds = uniform_round_count(counts, k=4, chunk=16)
        spec = PlanSpec(backend="pallas_stream", stream_window=512, **kw)
        strides = [build_plan_bundle(ShardSlice(
            counts=c, n_entries=int(max(x.sum() for x in counts)),
            n_rounds=n_rounds), spec).stream_rounds[0]["window_entries"]
            for c in counts]
        assert strides[0] != strides[1], strides
        ref = lpa(g, LPAConfig(method="mg", rho=2, k=4, chunk=16))
        for aligned in (False, True):
            ws = build_dist_workspace(g, 2, stream=True, aligned=aligned,
                                      window_entries=512, **kw)
            assert ws.stream_gathers[0].shape[-1] == max(strides)
            got, it = dist_lpa(mesh, ws, rho=2, engine="pallas_stream")
            assert it == ref.iterations, aligned
            assert (np.asarray(got) == np.asarray(ref.labels)).all(), aligned
        print("different strides dist parity ok")
    """, devices=2)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dist_rescan_matches_single_host():
    """dist_lpa(rescan=True) routes the MG double-scan (§4.4) through the
    same FoldRequest the single-host mover keys on (DESIGN.md §14); the
    second pass re-scores candidates against round 0 per shard and must be
    bit-identical to single-host lpa(rescan=True) on every exchange mode
    and engine."""
    _run("""
        import numpy as np, jax
        from repro.graphs.generators import powerlaw_communities
        from repro.core.distributed import build_dist_workspace, dist_lpa
        from repro.core.lpa import lpa, LPAConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("shard",))
        g, _ = powerlaw_communities(768, p_in=0.5, mix=0.02, seed=5)
        sh = lpa(g, LPAConfig(method="mg", rescan=True, rho=2))
        ref = np.asarray(sh.labels)
        ws = build_dist_workspace(g, 4)
        got, _ = dist_lpa(mesh, ws, rho=2, rescan=True)
        assert (np.asarray(got) == ref).all(), "bucketed rescan diverges"
        fkw = dict(fused=True, tile_r=32)
        skw = dict(stream=True, tile_r=32, window_entries=512)
        for tag, kw, engine in (("fused", fkw, "pallas_fused"),
                                ("stream", skw, "pallas_stream")):
            for halo in (False, True):
                w = build_dist_workspace(g, 4, halo=halo, **kw)
                got, _ = dist_lpa(mesh, w, rho=2, engine=engine,
                                  rescan=True)
                assert (np.asarray(got) == ref).all(), (tag, halo)
        print("dist rescan parity ok")
    """, devices=4)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_halo_exchange_matches_full_gather():
    """Hub+halo label exchange must be bit-identical to the full gather
    (EXPERIMENTS §Perf hillclimb 3) and strictly cheaper on the wire."""
    _run("""
        import numpy as np, jax
        from repro.graphs.generators import powerlaw_communities
        from repro.graphs.partition import lpa_partition
        from repro.core.distributed import build_dist_workspace, dist_lpa
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("shard",))
        g, _ = powerlaw_communities(4096, p_in=0.5, mix=0.02, seed=1)
        part = lpa_partition(g, 8)
        ws_f = build_dist_workspace(g, 8, order=part.order)
        ws_h = build_dist_workspace(g, 8, order=part.order, halo=True)
        lf, _ = dist_lpa(mesh, ws_f, rho=2)
        lh, _ = dist_lpa(mesh, ws_h, rho=2)
        assert (np.asarray(lf) == np.asarray(lh)).all()
        full = ws_f.v_pad * 8
        halo = (ws_h.h_pad + ws_h.hub_pad) * 8
        assert halo < full, (halo, full)
    """, devices=8)


@pytest.mark.slow  # spawns a multi-device subprocess
def test_dist_frontier_gate_matches_single_host():
    """Per-shard dense frontier gating (dist_lpa(frontier_gate=True)):
    the marks come from one changed-flag exchange through the same
    halo/gather machinery as the labels, so the gated trajectory must be
    bit-identical to the single-host frontier_gate=True reference, across
    every exchange mode and engine."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.graphs.generators import powerlaw_communities
        from repro.core.distributed import build_dist_workspace, dist_lpa
        from repro.core.lpa import lpa, LPAConfig
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("shard",))
        g, _ = powerlaw_communities(1024, p_in=0.5, mix=0.02, seed=3)
        sh = lpa(g, LPAConfig(method="mg", rho=2, frontier_gate=True))
        ref = np.asarray(sh.labels)
        ws = build_dist_workspace(g, 4)
        full, it = dist_lpa(mesh, ws, rho=2, frontier_gate=True)
        assert (np.asarray(full) == ref).all()
        assert it == sh.iterations
        ws_h = build_dist_workspace(g, 4, halo=True)
        halo, _ = dist_lpa(mesh, ws_h, rho=2, frontier_gate=True)
        assert (np.asarray(halo) == ref).all()
        ws_f = build_dist_workspace(g, 4, fused=True, tile_r=64)
        fused, _ = dist_lpa(mesh, ws_f, rho=2, engine="pallas_fused",
                            frontier_gate=True)
        assert (np.asarray(fused) == ref).all()
        bm, _ = dist_lpa(mesh, ws, rho=2, method="bm", frontier_gate=True)
        bm_sh = lpa(g, LPAConfig(method="bm", rho=2, frontier_gate=True))
        assert (np.asarray(bm) == np.asarray(bm_sh.labels)).all()
    """, devices=4)


@pytest.mark.slow  # spawns a multi-device subprocess
@pytest.mark.parametrize("halo", [False, True], ids=["all-gather", "halo"])
def test_dist_collectives_carry_the_exchange_scope(halo):
    """Every collective of the four-shard step (the label all-gather, or
    the hub all-gather and halo all-to-all, and the delta psum) lies in
    device scope ``lpa.exchange``."""
    out = _run(f"""
        import re, jax, jax.numpy as jnp
        from repro.core.distributed import build_dist_workspace, dist_lpa_step
        from repro.graphs.generators import powerlaw_communities
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("shard",))
        g, _ = powerlaw_communities(768, p_in=0.5, mix=0.02, seed=7)
        ws = build_dist_workspace(g, 4, halo={halo})
        step = dist_lpa_step(mesh, ws)
        extra = (ws.send_idx, ws.hub_idx) if {halo} else ()
        text = jax.jit(step).lower(
            ws.nbr_pos, ws.weights, ws.round_gathers, ws.final_row_vertex,
            ws.init_labels, jnp.asarray(True), jnp.int32(1),
            *extra).compile().as_text()
        lines = [ln for ln in text.splitlines()
                 if re.search(r" (all-gather|all-to-all|all-reduce)"
                              r"(-start)?\\(", ln)]
        assert lines, "no collective in the step"
        for ln in lines:
            assert "lpa.exchange" in ln, ln
        print("collectives", len(lines))
    """, devices=4)
    assert "collectives" in out


# a seeded weighted Graph500-style R-MAT: chunk 16 and k 4 give its hub
# rows (degree up to about 200) three fold rounds; a 1 KiB VMEM budget
# makes fold_backend="auto" resolve to the streamed engine on each shard
_WEIGHTED_RMAT = """
    import numpy as np, jax
    from repro.core import LPAConfig, build_workspace, lpa
    from repro.core.distributed import ShardedWorkspace
    from repro.graphs.csr import build_csr
    from repro.graphs.generators import rmat
    g0 = rmat(9, edge_factor=8, seed=3)
    src, dst = np.asarray(g0.sources()), np.asarray(g0.indices)
    e = np.stack([src[src < dst], dst[src < dst]], 1)
    g = build_csr(e, g0.n_nodes, weights=np.random.default_rng(3).random(
        len(e)).astype(np.float32))
    BASE = dict(method="mg", k=4, chunk=16, rho=3, max_iters=20,
                fold_backend="auto", vmem_budget_bytes=1024,
                aligned_layout=True, stream_window=512,
                track_frontier=False)
"""


@pytest.mark.slow  # spawns a multi-device subprocess
@pytest.mark.parametrize("routed, gate", [(True, False), (False, False),
                                          (True, True)],
                         ids=["halo", "all-gather", "halo-gated"])
def test_lpa_shards_routes_to_the_distributed_solve(routed, gate):
    """lpa(g, LPAConfig(shards=4)) on the workspace build_workspace places
    runs the four-shard halo solve and gives single-host lpa()'s labels,
    iteration count and every iteration's moved count, Pick-Less
    iterations included, with one host read per non-Pick-Less
    iteration. The all-gather exchange (build_dist_workspace(halo=False),
    which dist_lpa's callers keep) runs the same loop, dist_solve, to the
    same result."""
    out = _run(_WEIGHTED_RMAT + f"""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import (build_dist_workspace, dist_solve,
                                        label_positions)
    from repro.graphs.csr import build_fold_plan
    from repro.launch.mesh import make_mesh
    assert len(build_fold_plan(np.asarray(g.degrees), k=4,
                               chunk=16).rounds) >= 2
    gate = {gate}
    ref = lpa(g, LPAConfig(**BASE, frontier_gate=gate))
    cfg = LPAConfig(**BASE, shards=4, frontier_gate=gate)
    if {routed}:
        ws = jax.block_until_ready(build_workspace(g, cfg))
        assert isinstance(ws, ShardedWorkspace)
        assert ws.bundle.spec.backend == "pallas_stream"
        assert ws.stream_plan is None
        assert ws.dist.stream_aligned_pos is not None
        assert ws.dist.send_idx is not None  # the halo tables
        solve = lambda: lpa(g, cfg, ws=ws)
    else:
        mesh = make_mesh((4,), ("shard",))
        d = build_dist_workspace(g, 4, k=4, chunk=16, halo=False,
                                 stream=True, window_entries=512,
                                 aligned=True,
                                 sharding=NamedSharding(mesh, P("shard")))
        assert d.send_idx is None and d.hub_idx is None
        pos = jax.device_put(label_positions(d.init_labels),
                             NamedSharding(mesh, P()))
        solve = lambda: dist_solve(mesh, d, pos, rho=3, max_iters=20,
                                   engine="pallas_stream",
                                   frontier_gate=gate)
    for _ in range(2):  # the placed workspace serves solve after solve
        res = solve()
        assert (np.asarray(res.labels) == np.asarray(ref.labels)).all()
        assert res.iterations == ref.iterations
        assert res.changed_history == ref.changed_history
        assert res.converged == ref.converged
        pick_less = sum(it % 3 == 0 for it in range(res.iterations))
        assert pick_less >= 2
        assert res.host_syncs == res.iterations - pick_less
    print("routed", res.iterations, res.changed_history)
    """, devices=4)
    assert "routed" in out


@pytest.mark.parametrize("refused", [
    dict(frontier_gate=True, frontier_sparse=True),
    dict(track_frontier=True),
    dict(method="exact"),
    dict(mg_variant="exact_weighted"),
    dict(shards=64),
], ids=["frontier_sparse", "track_frontier", "exact", "mg_variant",
        "more_shards_than_devices"])
def test_lpa_shards_refuses_what_the_distributed_loop_lacks(refused):
    from repro.core import LPAConfig, build_workspace, lpa
    from repro.graphs.generators import ring_of_cliques
    g, _ = ring_of_cliques(4, 5)
    kw = dict(shards=2, track_frontier=False)
    kw.update(refused)
    cfg = LPAConfig(**kw)
    # this process holds one device, so any shards > 1 meets the device
    # check last: each refusal must name its own option first
    match = "devices" if "shards" in refused else "does not carry"
    with pytest.raises(ValueError, match=match):
        build_workspace(g, cfg)
    with pytest.raises(ValueError, match=match):
        lpa(g, cfg)


def test_lpa_shards_one_is_the_single_host_path():
    """shards=1, the default, written out or left out, changes nothing:
    the same workspace type and the same result, read for read."""
    from repro.core import LPAConfig, LPAWorkspace, build_workspace, lpa
    from repro.graphs.generators import powerlaw_communities
    g, _ = powerlaw_communities(512, p_in=0.5, mix=0.02, seed=2)
    base = lpa(g, LPAConfig(rho=2))
    for cfg in (LPAConfig(rho=2), LPAConfig(rho=2, shards=1)):
        assert isinstance(build_workspace(g, cfg), LPAWorkspace)
        res = lpa(g, cfg)
        assert (np.asarray(res.labels) == np.asarray(base.labels)).all()
        assert res.iterations == base.iterations
        assert res.changed_history == base.changed_history
        assert res.frontier_history == base.frontier_history
        assert res.host_syncs == base.host_syncs


@pytest.mark.parametrize("engine", ["pallas_fused", "pallas_stream"])
def test_dist_workspace_places_no_bucketed_tables_its_fold_skips(engine):
    """The fused and streamed shard folds never read the bucketed round
    gathers (nor, streamed, the bucketed row maps): the workspace keeps
    the step's operands as empty [P, 0, ...] stand-ins instead of
    placing them."""
    from repro.core.distributed import build_dist_workspace
    from repro.graphs.generators import powerlaw_communities
    g, _ = powerlaw_communities(512, p_in=0.5, mix=0.02, seed=2)
    stream = engine == "pallas_stream"
    ws = build_dist_workspace(g, 2, stream=stream, fused=not stream,
                              tile_r=32, window_entries=512)
    full = build_dist_workspace(g, 2)
    assert len(ws.round_gathers) == len(full.round_gathers)
    for g_r, f_r in zip(ws.round_gathers, full.round_gathers):
        assert g_r.shape == (2, 0, f_r.shape[2]) and f_r.shape[1] > 0
    kept = (ws.final_row_vertex, ws.row_vertex0, ws.bucket_rank0)
    for a, f in zip(kept, (full.final_row_vertex, full.row_vertex0,
                           full.bucket_rank0)):
        assert a.shape == ((2, 0) if stream else f.shape)
