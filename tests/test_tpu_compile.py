"""Ahead-of-time compiles of the fold kernels for a TPU v5e chip.

Nothing runs: each test lowers a kernel round (or the four-chip step) at
real widths for a described ``v5e:2x2`` topology and asserts that the TPU
compiler accepts it with the Pallas kernel compiled in
(``tpu_custom_call``). This is what interpret mode cannot show — block
tiling, unsupported in-kernel ops, VMEM capacity — checked without a chip.
The topology is described inside a fixture, never at import, and the tests
skip where it cannot be described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.fold_engine import DEFAULT_VMEM_BUDGET_BYTES
from repro.graphs.csr import FusedRound, StreamedRound
from repro.kernels.mg_sketch import fused, streaming

W, TILE_R, CHUNK, K = 8192, 128, 128, 8  # LPAConfig defaults at real widths
N_STEPS = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _streamed(one_chip, aligned, w=W):
    """Round-0-sized streamed metadata + entry shapes on one chip, at
    window stride ``w``."""
    s = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    meta = (s((N_STEPS * w,)), s((N_STEPS, TILE_R)), s((N_STEPS, TILE_R)),
            s((N_STEPS, 1)))
    entries = (s((N_STEPS * w,)), s((N_STEPS * w,), jnp.float32))

    def rnd(gather, starts, counts, dmax):
        return StreamedRound(entry_gather=gather, row_start=starts,
                             row_count=counts, step_dmax=dmax,
                             n_entries_in=N_STEPS * w, window_entries=w,
                             aligned=aligned)
    return s, meta, entries, rnd


@pytest.mark.parametrize("aligned", [True, False],
                         ids=["aligned", "regathered"])
def test_stream_fold_compiles(one_chip, aligned):
    _, meta, entries, rnd = _streamed(one_chip, aligned)
    _compile(lambda g, rs, rc, dm, el, ew: streaming.stream_fold_round(
        rnd(g, rs, rc, dm), el, ew, k=K, chunk=CHUNK, interpret=False),
        *meta, *entries)


def test_stream_select_compiles(one_chip):
    s, meta, entries, rnd = _streamed(one_chip, True)
    _compile(lambda g, rs, rc, dm, el, ew, inc, seed:
             streaming.stream_select_round(
                 rnd(g, rs, rc, dm), el, ew, inc, seed, k=K, chunk=CHUNK,
                 interpret=False),
             *meta, *entries, s((N_STEPS * TILE_R,)), s(()))


@pytest.mark.parametrize("w", [384, 256])
@pytest.mark.parametrize("kernel", ["fold-aligned", "fold-regathered",
                                    "select"])
def test_stream_kernels_compile_at_narrow_strides(one_chip, kernel, w):
    """The plan picks each round's stride from its rows
    (``csr.build_streamed_rounds``), so short rows get strides far under
    the 8,192 cap, down to the kernels' narrowest: one 128-entry row's
    lane rows plus one (3 and 2 lane rows a window here)."""
    s, meta, entries, rnd = _streamed(one_chip, kernel != "fold-regathered",
                                      w)
    if kernel == "select":
        _compile(lambda g, rs, rc, dm, el, ew, inc, seed:
                 streaming.stream_select_round(
                     rnd(g, rs, rc, dm), el, ew, inc, seed, k=K,
                     chunk=CHUNK, interpret=False),
                 *meta, *entries, s((N_STEPS * TILE_R,)), s(()))
    else:
        _compile(lambda g, rs, rc, dm, el, ew: streaming.stream_fold_round(
            rnd(g, rs, rc, dm), el, ew, k=K, chunk=CHUNK, interpret=False),
            *meta, *entries)


@pytest.mark.parametrize("w", [W, 384, 256])
@pytest.mark.parametrize("kernel", ["fold", "select"])
def test_stream_marks_kernels_compile(one_chip, kernel, w):
    """The aligned round-0 kernels that also mark the frontier
    (``marks=True``: strip the packed bit, OR it per row, one more
    [1, tile_r] output), at the cap and at the narrowest strides."""
    s, meta, entries, rnd = _streamed(one_chip, True, w)
    if kernel == "select":
        _compile(lambda g, rs, rc, dm, el, ew, inc, seed:
                 streaming.stream_select_round(
                     rnd(g, rs, rc, dm), el, ew, inc, seed, k=K,
                     chunk=CHUNK, interpret=False, marks=True),
                 *meta, *entries, s((N_STEPS * TILE_R,)), s(()))
    else:
        _compile(lambda g, rs, rc, dm, el, ew: streaming.stream_fold_round(
            rnd(g, rs, rc, dm), el, ew, k=K, chunk=CHUNK, interpret=False,
            marks=True), *meta, *entries)


def test_stream_bm_compiles(one_chip):
    s, meta, entries, rnd = _streamed(one_chip, True)
    _compile(lambda g, rs, rc, dm, el, ew, init:
             streaming.bm_fold_round_stream(
                 rnd(g, rs, rc, dm), el, ew, init, chunk=CHUNK,
                 interpret=False),
             *meta, *entries, s((N_STEPS * TILE_R,)))


def test_stream_rescan_compiles(one_chip):
    s, meta, entries, rnd = _streamed(one_chip, True)
    _compile(lambda g, rs, rc, dm, el, ew, cand:
             streaming.rescan_round_stream(
                 rnd(g, rs, rc, dm), el, ew, cand, k=K, chunk=CHUNK,
                 interpret=False),
             *meta, *entries, s((N_STEPS * TILE_R, K)))


def _fused(one_chip):
    """The largest round 0 ``auto`` hands the fused engine: the whole VMEM
    budget of resident entries."""
    n_entries = DEFAULT_VMEM_BUDGET_BYTES // 8
    s = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    meta = (s((N_STEPS, TILE_R)), s((N_STEPS, TILE_R)), s((N_STEPS, 1)))
    entries = (s((n_entries,)), s((n_entries,), jnp.float32))

    def rnd(starts, counts, dmax):
        return FusedRound(row_start=starts, row_count=counts,
                          step_dmax=dmax, n_entries_in=n_entries)
    return s, meta, entries, rnd


def test_fused_fold_compiles_at_budget(one_chip):
    _, meta, entries, rnd = _fused(one_chip)
    _compile(lambda rs, rc, dm, el, ew: fused.fused_fold_round(
        rnd(rs, rc, dm), el, ew, k=K, chunk=CHUNK, interpret=False),
        *meta, *entries)


def test_fused_select_compiles_at_budget(one_chip):
    s, meta, entries, rnd = _fused(one_chip)
    _compile(lambda rs, rc, dm, el, ew, inc, seed: fused.fused_select_round(
        rnd(rs, rc, dm), el, ew, inc, seed, k=K, chunk=CHUNK,
        interpret=False),
        *meta, *entries, s((N_STEPS * TILE_R,)), s(()))


@pytest.mark.parametrize("halo", [False, True], ids=["all-gather", "halo"])
def test_dist_stream_step_compiles_on_four_chips(topo, halo, monkeypatch):
    """The four-chip step (shard_map over a (4,) mesh, streamed aligned
    kernels, label all-gather or halo exchange) compiles for 2x2 v5e."""
    from repro.core.distributed import build_dist_workspace, dist_lpa_step
    from repro.graphs.generators import powerlaw_communities
    # the step asks the backend whether to interpret; here it is the CPU
    monkeypatch.setattr(fused, "_interpret_default", lambda: False)
    mesh = Mesh(topo.devices, ("shard",))
    shard = NamedSharding(mesh, P("shard"))
    rep = NamedSharding(mesh, P())
    g, _ = powerlaw_communities(4096, p_in=0.5, mix=0.02, seed=1)
    ws = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard),
        build_dist_workspace(g, 4, halo=halo, stream=True, aligned=True))
    step = dist_lpa_step(mesh, ws, engine="pallas_stream")
    # the workspace rides as the last argument, so it stays a jit argument
    _compile(lambda *a: step(*a[:-1], ws_arrays=a[-1]), ws.nbr_pos,
             ws.weights, ws.round_gathers, ws.final_row_vertex,
             ws.init_labels,
             jax.ShapeDtypeStruct((), jnp.bool_, sharding=rep),
             jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), ws)



@pytest.mark.parametrize("aligned,marks", [(True, False), (False, False),
                                           (True, True)],
                         ids=["aligned", "regathered", "aligned-marks"])
def test_mover_names_its_module_kernels_and_scopes(one_chip, aligned, marks,
                                                   monkeypatch):
    """The whole streamed mover compiles as ``jit_lpa_move``, its Pallas
    kernels as custom calls named ``lpa_fold``/``lpa_select``, and every
    scope in its ops' ``op_name``; marking the frontier
    (``mover(marks=True)``) adds the ``lpa.marks`` scope and no kernel."""
    import re
    from repro.core.lpa import LPAConfig, build_workspace, mover
    from repro.graphs.generators import rmat
    monkeypatch.setattr(fused, "_interpret_default", lambda: False)
    g = rmat(12, 16, seed=1)
    cfg = LPAConfig(fold_backend="pallas_stream", aligned_layout=aligned)
    ws = build_workspace(g, cfg)
    rounds = len(ws.stream_plan.rounds)
    assert rounds >= 2
    s = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                       sharding=one_chip)
    flag = s(jnp.zeros((), jnp.bool_))
    mask = s(jnp.zeros(g.n_nodes, jnp.bool_))
    # as lpa() jits it
    if marks:
        move = jax.jit(mover(cfg, 0, marks=True), donate_argnames=("last",))
        kw = dict(last=(mask, mask), last_pick_less=flag)
    else:
        move = jax.jit(mover(cfg, 0), static_argnames=("sparse",))
        kw = dict(frontier=None, sparse=False)
    text = move.lower(
        jax.tree.map(s, ws), s(jnp.zeros(g.n_nodes, jnp.int32)), flag,
        s(jnp.zeros((), jnp.int32)), **kw).compile().as_text()
    assert text.startswith("HloModule jit_lpa_move")
    kernels = re.findall(r"%(lpa_\w+)(?:\.\d+)? = .*tpu_custom_call", text)
    assert sorted(set(kernels)) == ["lpa_fold", "lpa_select"]
    assert len(kernels) == rounds
    scopes = set(re.findall(r"(lpa\.[\w.]+)/", text))
    assert scopes >= {"lpa.gather", "lpa.relayout", "lpa.apply"} | {
        f"lpa.fold.r{i}" for i in range(rounds)}
    assert ("lpa.marks" in scopes) == marks
