"""HBM-streaming fused Pallas kernels: windowed sketch folds, one
dispatch per round — MG, BM (one round-0 dispatch) and the rescan second
pass (one round-0 dispatch), all with O(window) residency (DESIGN.md
§10/§11).

The fused engine (``fused.py``) passes each round's flat entry arrays whole,
so they are VMEM-resident for the duration of the dispatch — round 0 is |E|
entries, capped by ``fold_engine.DEFAULT_VMEM_BUDGET_BYTES``. The streaming
kernels here remove that cap by processing each round in fixed-size
**entry windows** (``repro.graphs.csr.build_streamed_fold_plan``):

  * the round's entries are re-laid into ``[n_windows * W]`` windowed
    arrays (one XLA gather per round; W = ``window_entries``). The plan
    window-aligns every row — rows pack contiguously inside a window with
    ``rel_start + chunk <= W`` — so no row's range ever crosses a window
    edge;
  * the kernel grid runs one step per window. Each step's BlockSpec selects
    only its own window, viewed as ``[W / 128, 128]`` lane rows, so the
    Pallas pipeline streams window ``i+1`` HBM -> VMEM (double-buffered
    block copies) while window ``i`` folds: per-step entry residency is
    ``2 * W * 8`` bytes (two label+weight window buffers), independent of
    |E|;
  * within a step the kernels ARE the fused ones (``fused._fold_kernel``
    and friends): the in-kernel gather of the row tile from the window,
    the lane-per-row fold bounded by the window's ``step_dmax``, and — on
    the final round — fused move selection. Later rounds merge a vertex's
    partial sketches via the plan's position-table gather.

Cost vs the fused engine: same dispatch count (``n_rounds`` per MG
iteration, the last fused with selection) and the same real entries read,
plus the windowed re-layout gathers (<= ``streamed_gather_slots`` padded
slots through HBM per iteration) — the price of bounded VMEM. With the
window-aligned CSR layout (``build_streamed_fold_plan(aligned=True)``,
DESIGN.md §13) round 0 — the O(|E|) share of that cost — is
pre-materialized at build time: aligned rounds (``StreamedRound.aligned``)
arrive with their entries already windowed and every round driver below
skips ``windowed_entries`` for them, so the per-iteration re-layout
traffic shrinks to the small later-round merges. Bit-identical to
``repro.core.sketch`` (tests/test_stream_engine.py in interpret mode;
tests/test_tpu_compile.py compiles the kernels for a v5e chip).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.graphs.csr import (StreamedFoldPlan, StreamedRound,
                              compact_active_rows)
from repro.kernels.mg_sketch.fused import (
    LANES, _bm_kernel, _fold_kernel, _fold_marks_kernel, _lane_groups,
    _rescan_kernel, _round_call, _select_kernel, _select_marks_kernel,
    fold_scope, rescan_select_generic, row_major, run_bm_plan_generic,
    slot_major, unpack_marks)


def windowed_entries(gather: jnp.ndarray, entry_labels: jnp.ndarray,
                     entry_weights: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Re-lay flat entry arrays into the plan's windowed layout.

    ``gather`` is a round's ``entry_gather`` [n_windows * W] int32 (source
    position per windowed slot, -1 = pad). Pad slots become (label -1,
    weight 0.0) — no-ops for the fold. Returns ([n_windows * W] int32
    labels, [n_windows * W] float32 weights).
    """
    if entry_labels.shape[0] == 0:  # edgeless graph: all slots are pads
        return (jnp.full(gather.shape, -1, jnp.int32),
                jnp.zeros(gather.shape, jnp.float32))
    safe = jnp.maximum(gather, 0)
    valid = gather >= 0
    wl = jnp.where(valid, entry_labels.astype(jnp.int32)[safe], -1)
    ww = jnp.where(valid, entry_weights.astype(jnp.float32)[safe], 0.0)
    return wl, ww


def _aligned_window_entries(entry_labels: jnp.ndarray,
                            entry_weights: jnp.ndarray
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Aligned-round fast path of :func:`windowed_entries`: the entries are
    already in the windowed layout (``StreamedRound.aligned`` — pads hold
    label -1 / weight 0.0 by plan construction), so the re-layout gather
    degenerates to dtype normalization. This is the no-op slice that saves
    the O(|E|) per-iteration HBM round-trip (DESIGN.md §13)."""
    return entry_labels.astype(jnp.int32), entry_weights.astype(jnp.float32)


def _stream_round(kernel, rnd: StreamedRound, extras, entry_labels,
                  entry_weights, outs, *, chunk: int, interpret,
                  index: int):
    """One streamed dispatch: grid over windows, one W-entry window of
    [W/128, 128] lane rows resident per step (the kernels are the fused
    ones; ``row_start`` holds window-relative offsets). The re-layout of
    the entries into windows is device scope ``lpa.relayout``, the launch
    ``lpa.fold.r<index>``."""
    rows = rnd.window_entries // LANES
    need = _lane_groups(chunk) + 1
    with jax.named_scope("lpa.relayout"):
        if rnd.aligned:  # entries pre-materialized window-aligned
            wl, ww = _aligned_window_entries(entry_labels, entry_weights)
        else:
            wl, ww = windowed_entries(rnd.entry_gather, entry_labels,
                                      entry_weights)
        wl = wl.reshape(rnd.n_windows, rows, LANES)
        ww = ww.reshape(rnd.n_windows, rows, LANES)
        if rows < need:  # a window narrower than one row gather's load
            pad = ((0, 0), (0, need - rows), (0, 0))
            wl = jnp.pad(wl, pad, constant_values=-1)
            ww = jnp.pad(ww, pad, constant_values=0.0)
            rows = need
    window = pl.BlockSpec((None, rows, LANES), lambda i: (i, 0, 0))
    with fold_scope(index):
        return _round_call(kernel, rnd, extras, (wl, ww), window, outs,
                           chunk=chunk, interpret=interpret)


def stream_fold_round(rnd: StreamedRound, entry_labels: jnp.ndarray,
                      entry_weights: jnp.ndarray, *, k: int, chunk: int,
                      interpret: bool | None = None, index: int = 0,
                      marks: bool = False):
    """One streamed dispatch: grid over windows, one W-entry window resident
    per step. ``index`` is the plan's round number, which names the
    launch's device scope.

    ``entry_labels``/``entry_weights`` are the round's flat source arrays
    (round 0: CSR-order neighbor labels/edge weights — or, on aligned
    rounds, the pre-windowed [n_windows * W] arrays the driver gathered
    from the plan's aligned layout; later rounds: the previous round's
    flattened padded [n_windows * tile_r * k] sketches).
    Returns padded ([n_windows * tile_r, k] int32, [..., k] float32)
    sketches in window-slot order (pad rows fold to empty sketches).
    ``marks`` (static): the labels are packed (``fused.pack_marks``), and
    each row slot's frontier mark [n_windows * tile_r] int32 is returned
    third.
    """
    block = (k, rnd.tile_r)
    kernel = _fold_marks_kernel if marks else _fold_kernel
    outs = [(jnp.int32, block), (jnp.float32, block)]
    outs += [(jnp.int32, (1, rnd.tile_r))] if marks else []
    s_k, s_v, *row_marks = _stream_round(
        functools.partial(kernel, k=k), rnd, (), entry_labels,
        entry_weights, outs, chunk=chunk, interpret=interpret, index=index)
    with fold_scope(index):
        return (row_major(s_k), row_major(s_v),
                *[m.reshape(-1) for m in row_marks])


def stream_select_round(rnd: StreamedRound, entry_labels: jnp.ndarray,
                        entry_weights: jnp.ndarray, incumbents: jnp.ndarray,
                        seed: jnp.ndarray, *, k: int, chunk: int,
                        interpret: bool | None = None,
                        index: int = 0, marks: bool = False):
    """Final-round (round ``index``) streamed dispatch: fold + per-row
    winning label.

    ``incumbents`` [n_windows * tile_r] int32 carries each row slot's
    current vertex label (-1 on pad slots). Returns the chosen label per
    row slot [n_windows * tile_r] int32; with ``marks`` (packed labels, a
    single-round plan), each row's frontier mark rides in its choice's
    sign bit (``fused.pack_marks``).
    """
    row = (1, rnd.tile_r)
    kernel = _select_marks_kernel if marks else _select_kernel
    (out,) = _stream_round(
        functools.partial(kernel, k=k), rnd,
        [(incumbents, row), (seed.astype(jnp.int32).reshape(1, 1), None)],
        entry_labels, entry_weights, [(jnp.int32, row)], chunk=chunk,
        interpret=interpret, index=index)
    return out.reshape(-1)


def run_mg_plan_stream(plan: StreamedFoldPlan, entry_labels: jnp.ndarray,
                       entry_weights: jnp.ndarray,
                       interpret: bool | None = None, *, selection=None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All fold rounds, one streamed dispatch each.

    ``entry_labels``/``entry_weights`` are the round-0 arrays — CSR order
    (the same inputs the jnp/pallas/fused engines take), or window-slot
    order when the plan is aligned (``plan.aligned``: the driver gathers
    them from ``aligned_entry_vertex``/``aligned_entry_weights``). Returns
    the final-round padded sketches ([last n_windows * tile_r, k] labels,
    weights) in window-slot order — map to vertices via
    ``plan.row_to_vertex``.

    With a ``selection`` (RoundSelection) each round grids only over the
    frontier-compacted active windows and scatters its sketches back to
    dense window-slot order, so the output layout is selection-invariant.
    """
    labels, weights = entry_labels, entry_weights
    if selection is None:
        for i, rnd in enumerate(plan.rounds):
            s_k, s_v = stream_fold_round(rnd, labels, weights, k=plan.k,
                                         chunk=plan.chunk,
                                         interpret=interpret, index=i)
            labels, weights = s_k.reshape(-1), s_v.reshape(-1)
    else:
        for i, rnd in enumerate(plan.rounds):
            sub, widx, _ = _sparse_stream_round(rnd, selection.frontier,
                                                selection.cap_rows)
            c_k, c_v = stream_fold_round(sub, labels, weights, k=plan.k,
                                         chunk=plan.chunk,
                                         interpret=interpret, index=i)
            s_k = _scatter_sparse_windows(widx, c_k, rnd.n_windows,
                                          rnd.tile_r, jnp.int32(-1))
            s_v = _scatter_sparse_windows(widx, c_v, rnd.n_windows,
                                          rnd.tile_r, jnp.float32(0.0))
            labels, weights = s_k.reshape(-1), s_v.reshape(-1)
    return s_k, s_v


def select_best_stream(plan: StreamedFoldPlan, entry_labels: jnp.ndarray,
                       entry_weights: jnp.ndarray, labels: jnp.ndarray,
                       seed: jnp.ndarray, interpret: bool | None = None,
                       *, selection=None, marks: bool = False):
    """Full streamed MG iteration: ``n_rounds`` dispatches, the last fused
    with move selection. Bit-identical to ``run_mg_plan`` + ``select_best``
    on the reference backend (and to ``fused.select_best_fused``).

    ``labels`` [N] int32 are the incumbent vertex labels; returns the
    wanted label per vertex [N] int32 (degree-0 vertices keep theirs).

    With a ``selection``, every round grids only over the compacted active
    windows: bit-identical on the frontier to the dense run; off-frontier
    wanted labels may differ (inactive rows sharing an active window
    compute, others carry through) — the frontier gate masks both, exactly
    as it masks the dense mover's off-frontier moves.

    ``marks`` (dense; an aligned plan whose round-0 labels are packed by
    ``fused.pack_marks``): the round-0 kernel strips the bits before it
    folds and ORs them over each row; device scope ``lpa.marks`` ORs the
    rows of each vertex, and the result is the pair (wanted labels, [N]
    bool: some neighbour's bit was set). A single-round plan's rows are
    its vertices, and their marks ride in the choices' sign bits.
    """
    if plan.n_nodes == 0:
        return (labels, jnp.zeros((0,), jnp.bool_)) if marks else labels
    el, ew = entry_labels, entry_weights
    row_marks = None
    if selection is None:
        for i, rnd in enumerate(plan.rounds[:-1]):
            s_k, s_v, *m = stream_fold_round(rnd, el, ew, k=plan.k,
                                             chunk=plan.chunk,
                                             interpret=interpret, index=i,
                                             marks=marks and i == 0)
            el, ew = s_k.reshape(-1), s_v.reshape(-1)
            row_marks = m[0] if m else row_marks
        last, rv = plan.rounds[-1], plan.row_to_vertex
    else:
        for i, rnd in enumerate(plan.rounds[:-1]):
            sub, widx, _ = _sparse_stream_round(rnd, selection.frontier,
                                                selection.cap_rows)
            c_k, c_v = stream_fold_round(sub, el, ew, k=plan.k,
                                         chunk=plan.chunk,
                                         interpret=interpret, index=i)
            el = _scatter_sparse_windows(widx, c_k, rnd.n_windows,
                                         rnd.tile_r,
                                         jnp.int32(-1)).reshape(-1)
            ew = _scatter_sparse_windows(widx, c_v, rnd.n_windows,
                                         rnd.tile_r,
                                         jnp.float32(0.0)).reshape(-1)
        last, _, rv = _sparse_stream_round(plan.rounds[-1],
                                           selection.frontier,
                                           selection.cap_rows)
    n = plan.n_nodes
    real = rv >= 0
    with jax.named_scope("lpa.apply"):
        incumbents = jnp.where(real, labels[jnp.maximum(rv, 0)], -1)
    choice = stream_select_round(last, el, ew, incumbents, seed,
                                 k=plan.k, chunk=plan.chunk,
                                 interpret=interpret,
                                 index=len(plan.rounds) - 1,
                                 marks=marks and plan.n_rounds == 1)
    # [N] scatter of per-row winners (pad/sentinel rows land in the dump
    # slot); degree-0 (or off-frontier) vertices keep their label —
    # identical to choose_from_candidates with an empty candidate set.
    with jax.named_scope("lpa.apply"):
        buf = jnp.concatenate([labels, jnp.zeros((1,), labels.dtype)])
        buf = buf.at[jnp.where(real, rv, n)].set(
            jnp.where(real, choice, -1))
        want = buf[:n]
    if not marks:
        return want
    with jax.named_scope("lpa.marks"):
        if row_marks is None:  # one round: the marks are the sign bits
            return unpack_marks(want)
        marked = jnp.zeros((n,), jnp.bool_).at[plan.row_to_vertex0].max(
            row_marks > 0, mode="drop", wrap_negative_indices=False)
        return want, marked


# ---------------------------------------------------------------------------
# Boyer-Moore fold: round 0 streamed through windows (DESIGN.md §11)
# ---------------------------------------------------------------------------


def bm_fold_round_stream(rnd: StreamedRound, entry_labels: jnp.ndarray,
                         entry_weights: jnp.ndarray,
                         init_labels: jnp.ndarray, *, chunk: int,
                         interpret: bool | None = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One streamed dispatch covering the whole BM fold: grid over round-0
    windows, one W-entry window resident per step.

    ``init_labels`` [n_windows * tile_r] int32 carries each row slot's
    incumbent label (-1 on pad slots). Returns per-slot ([rows] candidate
    label, [rows] vote weight) partial states in window-slot order.
    """
    row = (1, rnd.tile_r)
    ck, wk = _stream_round(_bm_kernel, rnd, [(init_labels, row)],
                           entry_labels, entry_weights,
                           [(jnp.int32, row), (jnp.float32, row)],
                           chunk=chunk, interpret=interpret, index=0)
    return ck.reshape(-1), wk.reshape(-1)


def run_bm_plan_stream(plan: StreamedFoldPlan, entry_labels: jnp.ndarray,
                       entry_weights: jnp.ndarray, cur_labels: jnp.ndarray,
                       interpret: bool | None = None, *, selection=None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Streamed νBM iteration core: ONE dispatch (window grid inside) +
    the max-reduce merge of per-slot partial states. Bit-identical to
    ``repro.core.sketch.run_bm_plan`` (same per-row entry sequences; the
    ``sketch.bm_merge_rows`` merge is order-insensitive). Per-step entry
    residency is the double-buffered window, independent of |E|. Returns
    per-vertex (label [N], weight [N]); no-entry vertices get -1.

    With a ``selection``, the single dispatch grids only over active
    round-0 windows. Active vertices merge their complete row set (every
    row of an active vertex lives in an active window); vertices only
    partially covered by active windows produce gate-masked off-frontier
    values.
    """
    if selection is None:
        return run_bm_plan_generic(plan, entry_labels, entry_weights,
                                   cur_labels, bm_fold_round_stream,
                                   interpret)
    from repro.core.sketch import bm_init_rows, bm_merge_rows
    n = plan.n_nodes
    if n == 0:
        return (jnp.full((0,), -1, jnp.int32), jnp.zeros((0,), jnp.float32))
    sub, _, rv_c = _sparse_stream_round(plan.rounds[0], selection.frontier,
                                        selection.cap_rows)
    init = bm_init_rows(rv_c, cur_labels)
    ck, wk = bm_fold_round_stream(sub, entry_labels, entry_weights, init,
                                  chunk=plan.chunk, interpret=interpret)
    return bm_merge_rows(n, cur_labels, rv_c, ck, wk)


def rescan_round_stream(rnd: StreamedRound, entry_labels: jnp.ndarray,
                        entry_weights: jnp.ndarray, cand_rows: jnp.ndarray,
                        *, k: int, chunk: int, interpret: bool | None = None
                        ) -> jnp.ndarray:
    """One streamed dispatch re-reading round 0 to score each row slot's
    candidates through the windowed layout. ``cand_rows``
    [n_windows * tile_r, k] int32. Returns [n_windows * tile_r, k] float32
    partial linking weights in window-slot order.
    """
    block = (k, rnd.tile_r)
    (out,) = _stream_round(_rescan_kernel, rnd,
                           [(slot_major(cand_rows, rnd.tile_r), block)],
                           entry_labels, entry_weights,
                           [(jnp.float32, block)], chunk=chunk,
                           interpret=interpret, index=0)
    with fold_scope(0):
        return row_major(out)


def rescan_select_stream(plan: StreamedFoldPlan, entry_labels: jnp.ndarray,
                         entry_weights: jnp.ndarray, labels: jnp.ndarray,
                         seed: jnp.ndarray, interpret: bool | None = None,
                         *, selection=None) -> jnp.ndarray:
    """Full double-scan MG iteration on the streaming engine: ``n_rounds``
    fold dispatches + ONE rescan dispatch, all with O(window) residency.
    Bit-identical to the reference ``run_mg_plan`` + ``rescan_candidates``
    (shared accumulate order and merge — see ``sketch.rescan_candidates``).

    With a ``selection``, the fold rounds and the rescan dispatch grid
    only over compacted active round-0 windows; off-frontier vertices keep
    an all-empty candidate set and their label.
    """
    if selection is None:
        return rescan_select_generic(plan, entry_labels, entry_weights,
                                     labels, seed, run_mg_plan_stream,
                                     rescan_round_stream, interpret)
    from repro.core.sketch import choose_from_candidates, merge_rescan_partials
    n, k = plan.n_nodes, plan.k
    if n == 0:
        return labels
    s_k, _ = run_mg_plan_stream(plan, entry_labels, entry_weights,
                                interpret=interpret, selection=selection)
    rtv = plan.row_to_vertex
    cand = jnp.full((n + 1, k), -1, jnp.int32).at[
        jnp.where(rtv >= 0, rtv, n)].set(s_k)[:n]
    rnd0 = plan.rounds[0]
    sub0, widx0, rv0_c = _sparse_stream_round(rnd0, selection.frontier,
                                              selection.cap_rows)
    cand_ext = jnp.concatenate([cand, jnp.full((1, k), -1, jnp.int32)])
    cand_rows = cand_ext[jnp.where(rv0_c >= 0, rv0_c, n)]
    parts_c = rescan_round_stream(sub0, entry_labels, entry_weights,
                                  cand_rows, k=k, chunk=plan.chunk,
                                  interpret=interpret)
    parts = _scatter_sparse_windows(widx0, parts_c, rnd0.n_windows,
                                    rnd0.tile_r, jnp.float32(0.0))
    acc = merge_rescan_partials(n, k, plan.max_rows0, plan.row_to_vertex0,
                                plan.row_rank0, parts)
    return choose_from_candidates(jnp.where(acc > 0, cand, -1), acc,
                                  labels, seed)


# ---------------------------------------------------------------------------
# Sparse frontier path: grid only over active windows (DESIGN.md §8.5)
# ---------------------------------------------------------------------------
#
# The streaming analogue of ``fused``'s sparse drivers, compacted at
# *window* granularity: a window is active when any of its rows is owned by
# a frontier vertex, and the synthetic round gathers the active windows'
# entry_gather blocks / row metadata into a ``min(cap_rows, n_windows)``
# -window buffer (every active window holds >= 1 active row, so a row
# capacity that fits the fused path always fits here). The UNCHANGED
# streamed kernels then grid over the compacted windows. Inactive rows
# that share a window with an active one are folded too — on round 0 they
# compute the same values the dense path would (then masked by the gate);
# on later rounds they read their vertex's empty scatter-back partials and
# fold to empty sketches. Capacity fit is checked on the host
# (``csr.streamed_active_windows``) with a dense fallback on overflow.


def _sparse_stream_round(rnd: StreamedRound, frontier: jnp.ndarray,
                         cap_rows: int):
    """Compact one round's active windows into a capped synthetic round.

    Returns ``(sub_round, widx, row_vertex)``: a ``StreamedRound`` over
    ``min(cap_rows, n_windows)`` windows with traced gathered metadata
    (sentinel windows are all-pad: entry_gather -1, counts 0), the [cap_w]
    compacted window indices (sentinel = dense window count), and the
    [cap_w * tile_r] owning vertex per compacted row slot (-1 on sentinel
    windows' slots).

    Aligned rounds compose transparently: their ``entry_gather`` is the
    identity permutation over window slots, so the compacted sub-round's
    gather (``eg_ext[widx]``) holds exactly the active windows' slot
    indices into the aligned source arrays. The sub-round deliberately
    keeps ``aligned=False`` — it must re-gather, because its windows are
    a compacted subset of the aligned layout, not a prefix of it.
    """
    n_win, tile_r = rnd.row_start.shape
    w = rnd.window_entries
    n = frontier.shape[0]
    rv = rnd.row_vertex
    real = rv >= 0
    cap_w = min(cap_rows, n_win)
    with jax.named_scope("lpa.relayout"):
        front_ext = jnp.concatenate([frontier.astype(jnp.bool_),
                                     jnp.zeros((1,), jnp.bool_)])
        active = real & front_ext[jnp.where(real, rv, n)]
        win_active = active.reshape(n_win, tile_r).any(axis=1)
        widx = compact_active_rows(win_active, cap_w)
        eg_ext = jnp.concatenate([rnd.entry_gather.reshape(n_win, w),
                                  jnp.full((1, w), -1, jnp.int32)])
        zero_tile = jnp.zeros((1, tile_r), jnp.int32)
        rs_ext = jnp.concatenate([rnd.row_start, zero_tile])
        rc_ext = jnp.concatenate([rnd.row_count, zero_tile])
        dm_ext = jnp.concatenate([rnd.step_dmax,
                                  jnp.zeros((1, 1), jnp.int32)])
        rv_ext = jnp.concatenate([rv.reshape(n_win, tile_r),
                                  jnp.full((1, tile_r), -1, jnp.int32)])
        sub = StreamedRound(entry_gather=eg_ext[widx].reshape(-1),
                            row_start=rs_ext[widx], row_count=rc_ext[widx],
                            step_dmax=dm_ext[widx],
                            n_entries_in=rnd.n_entries_in, window_entries=w)
        return sub, widx, rv_ext[widx].reshape(-1)


def _scatter_sparse_windows(widx: jnp.ndarray, values: jnp.ndarray,
                            n_win: int, tile_r: int, fill) -> jnp.ndarray:
    """Scatter compacted per-row-slot results back to dense slot positions
    (whole windows at a time; sentinel windows land in a sliced-off dump
    window; unwritten dense slots keep the empty-sketch ``fill``)."""
    with jax.named_scope("lpa.relayout"):
        targets = (widx[:, None].astype(jnp.int32) * tile_r
                   + jnp.arange(tile_r, dtype=jnp.int32)[None, :]
                   ).reshape(-1)
        buf = jnp.full(((n_win + 1) * tile_r,) + values.shape[1:], fill,
                       values.dtype)
        return buf.at[targets].set(values)[:n_win * tile_r]
