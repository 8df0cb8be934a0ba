"""Fused Pallas TPU kernels: whole-round sketch folds in one dispatch.

Covers every paper sketch family through one plan/kernel split (DESIGN.md
§11): the MG fold (one dispatch per round, the last fused with move
selection), the BM fold (ONE dispatch — only round 0 is ever folded, the
partials merge with an XLA max-reduce), and the rescan second pass of the
double-scan ablation (ONE dispatch re-reading round 0).

The per-bucket kernel in ``mg_sketch.py`` needs XLA to materialize a padded
[R, D] gather tile in HBM per width bucket per round — ``O(rounds x
buckets)`` dispatches plus full gather/scatter round-trips. The fused
kernels here exploit the structure of the fold plan (every gather is a
masked contiguous range, see ``repro.graphs.csr.build_fused_fold_plan``):

  * the *entry gather happens inside the kernel* — the flat entry arrays
    are passed whole, viewed as ``[rows, 128]`` lane rows, and each grid
    step copies the ``chunk``-wide range of each of its ``tile_r`` rows
    into a VMEM tile (:func:`_gather_tile`). The padded [tile_r, chunk]
    tile never exists in HBM; pad lanes are masked in-register from
    (start, count) scalars (8 bytes/row of metadata instead of
    ``4*width`` bytes of gather indices);
  * all width buckets of a round share ONE grid — per-step ``step_dmax``
    bounds the fold loop, so a step of deg-2 road rows runs 2 accumulate
    iterations, not 128, with no per-width dispatch;
  * the final round is fused with move selection: the kernel folds the last
    partial sketches AND picks the winning label (incumbent + per-iteration
    hash tie-break, bit-identical to ``repro.core.sketch
    .choose_from_candidates``), so one MG iteration costs ``n_rounds``
    dispatches total and the [N, k] candidate scatter shrinks to an [N]
    label scatter.

Addressing (what Mosaic compiles): per-step scalars — the fold-loop bound
``step_dmax``, the row starts and the selection seed — live in SMEM, so
loop bounds and load addresses are true scalars. A row's range starts at
an arbitrary entry offset, so the gather loads the ``G + 1`` lane rows
that cover it (a dynamic sublane slice) and lines the lanes up with a
dynamic lane rotation (``pltpu.roll``). The gathered rows are then
transposed once to a ``[chunk, tile_r]`` tile: entry ``i`` of every row
is sublane row ``i``, which the fold loops read from the scratch ref. The
sketches live the same way round, ``[k, tile_r]`` (slot on sublanes, row
on lanes), and are transposed back to the plan's ``[rows, k]`` order on
the way out.

VMEM per grid step (tile_r=128, chunk=128, k=8): two 64 KiB gather tiles
and their transposes, plus the [k, tile_r] sketches. The flat entry
arrays are resident for the whole dispatch (round 0 = |E| entries at 8
bytes each), which caps a single-core fused round 0 at the
``fold_engine.DEFAULT_VMEM_BUDGET_BYTES`` budget — past it use the
HBM-streaming engine (``kernels.mg_sketch.streaming`` /
``fold_backend="pallas_stream"``, or ``"auto"`` which picks per graph),
or shard the graph (repro.core.distributed).

Bit-identical to ``repro.core.sketch`` (tests/test_fused_engine.py runs
the kernels in interpret mode; tests/test_tpu_compile.py compiles them
for a v5e chip).
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.graphs.csr import FusedFoldPlan, FusedRound, compact_active_rows

INT_MAX = jnp.iinfo(jnp.int32).max
LANES = 128  # lane width of a TPU vector register: entries load in rows of it
# hash_mix's uint32 constants as wrapped int32 (np scalars inline as kernel
# literals), and the sign bit that turns a hash into an int32 order key
_HASH_MUL = np.int32(2654435761 - 2**32)
_HASH_SEED_MUL = np.int32(0x9E3779B9 - 2**32)
_HASH_FIN_MUL = np.int32(0x85EBCA77 - 2**32)
_SIGN_BIT = np.int32(-2**31)
_LABEL_MASK = np.int32(2**31 - 1)  # clears the sign bit pack_marks sets


def _interpret_default() -> bool:
    """Run the Pallas kernels in interpret mode exactly when no TPU backs
    JAX (the CPU test suite); on a TPU they always compile."""
    return jax.default_backend() != "tpu"


def _lane_groups(chunk: int) -> int:
    """128-lane groups one row's ``chunk`` entries span."""
    return -(-chunk // LANES)


# ---------------------------------------------------------------------------
# Kernel phases, shared by the fused and the streamed (``streaming.py``)
# kernels. Tiles are column-major: [entry, row] and [slot, row].
# ---------------------------------------------------------------------------


def _gather_tile(start_ref, src_l_ref, src_w_ref, rows_l_ref, rows_w_ref,
                 tile_l_ref, tile_w_ref):
    """Phase 1: in-kernel gather of the step's [chunk, tile_r] entry tiles.

    ``start_ref`` (SMEM [1, tile_r]) holds each row's entry offset into the
    source ``src_*_ref`` ([n_src, 128] lane rows of the flat label/weight
    entries). Row ``r`` lands in row ``r`` of the ``rows_*_ref`` scratch
    ([tile_r, G*128]); one transpose then writes the column-major
    ``tile_*_ref`` ([G*128, tile_r]). Lanes past a row's count hold
    neighbouring entries; :func:`_column` masks them.

    The source holds at least ``G + 1`` lane rows. A row that ends in the
    last lane row of the source (the end of a stream window) loads from
    one row earlier (``late``); its entries then sit one lane row further
    down the loaded block.
    """
    tile_r, width = rows_l_ref.shape
    groups = width // LANES
    last = src_l_ref.shape[0] - groups - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def load_row(r, carry):
        s = start_ref[0, r]
        q = s // LANES
        base = jnp.minimum(q, last)
        late = q > base
        off = s - q * LANES
        low = lane + off < LANES       # lane l reads lane row g, else g + 1
        shift = (LANES - off) % LANES  # rotate lane `off` down to lane 0
        for src_ref, dst_ref in ((src_l_ref, rows_l_ref),
                                 (src_w_ref, rows_w_ref)):
            blk = src_ref[pl.ds(base, groups + 1), :]
            for g in range(groups):
                nxt = min(g + 2, groups)
                lo = jnp.where(late, blk[g + 1:g + 2], blk[g:g + 1])
                hi = jnp.where(late, blk[nxt:nxt + 1], blk[g + 1:g + 2])
                dst_ref[pl.ds(r, 1), g * LANES:(g + 1) * LANES] = jnp.where(
                    low, pltpu.roll(lo, shift, 1), pltpu.roll(hi, shift, 1))
        return carry

    jax.lax.fori_loop(0, tile_r, load_row, 0)
    tile_l_ref[...] = rows_l_ref[...].T
    tile_w_ref[...] = rows_w_ref[...].T


def _column(tile_l_ref, tile_w_ref, counts, i):
    """Entry ``i`` of every row of the tile ([1, tile_r] label, weight);
    rows with fewer than ``i + 1`` entries read the no-op (-1, 0.0)."""
    live = i < counts
    return (jnp.where(live, tile_l_ref[pl.ds(i, 1), :], -1),
            jnp.where(live, tile_w_ref[pl.ds(i, 1), :], 0.0))


def pack_marks(labels: jnp.ndarray, changed: jnp.ndarray) -> jnp.ndarray:
    """Labels with each vertex's ``changed`` bit in the sign bit (DESIGN.md
    §8.5): what the aligned round-0 gather reads when the mover marks the
    frontier. Labels lie in ``[0, n)`` with ``n < 2**31 - 1``, so a packed
    label is never the pad -1; :func:`unpack_marks` decodes it."""
    return jnp.where(changed, labels | _SIGN_BIT, labels)


def unpack_marks(packed: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The inverse of :func:`pack_marks`: (labels, bits); a pad (-1) stays
    -1 and carries no bit."""
    pad = packed == -1
    return (jnp.where(pad, packed, packed & _LABEL_MASK),
            (packed < 0) & ~pad)


def _strip_marks(tile_l_ref, counts):
    """Each row's frontier mark: the OR of the packed ``changed`` bit
    over the row's live entries ([1, tile_r] int32). Clears the bits in
    the tile (:func:`unpack_marks`), so the fold reads the plain labels."""
    tile, bit = unpack_marks(tile_l_ref[...])
    tile_l_ref[...] = tile
    live = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) < counts
    return jnp.max((live & bit).astype(jnp.int32), axis=0, keepdims=True)


def _any(mask):
    """Per-row (lane) OR over the sketch slots (sublanes)."""
    return jnp.max(mask.astype(jnp.int32), axis=0, keepdims=True) > 0


def _mg_fold(tile_l_ref, tile_w_ref, counts, k: int, dmax):
    """Phase 2: lane-per-row weighted MG fold into [k, tile_r] sketches,
    loop bound = step's max width (``dmax`` — a deg-2 step runs 2
    iterations, not 128). Identical accumulate semantics to
    ``repro.core.sketch.mg_fold_tile``.
    """
    slot = jax.lax.broadcasted_iota(jnp.int32, (k, counts.shape[1]), 0)

    def body(i, carry):
        s_k, s_v = carry
        c, w = _column(tile_l_ref, tile_w_ref, counts, i)
        valid = (w > 0) & (c >= 0)
        occupied = s_v > 0
        match = occupied & (s_k == c) & valid
        any_match = _any(match)
        s_v = s_v + jnp.where(match, w, 0.0)
        free = ~occupied
        has_free = _any(free)
        first_free = jnp.min(jnp.where(free, slot, k), axis=0,
                             keepdims=True)
        claim = (valid & ~any_match & has_free) & (slot == first_free)
        s_k = jnp.where(claim, c, s_k)
        s_v = jnp.where(claim, w, s_v)
        dec = valid & ~any_match & ~has_free
        s_v = jnp.maximum(s_v - jnp.where(dec, w, 0.0), 0.0)
        return s_k, s_v

    init = (jnp.full(slot.shape, -1, jnp.int32),
            jnp.zeros(slot.shape, jnp.float32))
    return jax.lax.fori_loop(0, dmax, body, init)


def _bm_fold(tile_l_ref, tile_w_ref, counts, init, dmax):
    """Phase 2 (BM): lane-per-row weighted Boyer-Moore scan, loop bound =
    the step's max width. ``init`` [1, tile_r] carries each row's incumbent
    label (paper Alg. 3 l. 13). Identical accumulate semantics to
    ``repro.core.sketch.bm_fold_tile`` (pad entries are exact no-ops).
    Returns ([1, tile_r] candidate, [1, tile_r] vote weight).
    """
    def body(i, carry):
        ck, wk = carry
        c, w = _column(tile_l_ref, tile_w_ref, counts, i)
        valid = (w > 0) & (c >= 0)
        same = valid & (c == ck)
        bigger = valid & ~same & (wk > w)
        replace = valid & ~same & ~bigger
        wk = wk + jnp.where(same, w, 0.0) - jnp.where(bigger, w, 0.0)
        ck = jnp.where(replace, c, ck)
        wk = jnp.where(replace, w, wk)
        return ck, wk

    return jax.lax.fori_loop(0, dmax, body,
                             (init, jnp.zeros(init.shape, jnp.float32)))


def _rescan_acc(tile_l_ref, tile_w_ref, counts, cand, dmax):
    """Phase 2 (rescan): exact per-candidate linking weights of a gathered
    tile. Accumulates sequentially over the entry axis — the same order as
    ``repro.core.sketch.rescan_row_partials``, so partials are
    bit-identical to the reference (pad entries add exact 0.0 no-ops).
    ``cand`` [k, tile_r] holds each row's candidate labels (-1 empties).
    """
    def body(i, acc):
        c, w = _column(tile_l_ref, tile_w_ref, counts, i)
        hit = (cand == c) & (cand >= 0)
        return acc + jnp.where(hit, w, 0.0)

    return jax.lax.fori_loop(0, dmax, body,
                             jnp.zeros(cand.shape, jnp.float32))


def _hash_key(x, seed):
    """In-kernel clone of repro.core.sketch.hash_mix as an int32 order key.

    The hash runs in int32 (wrapping products and logical shifts give the
    same bits as the reference's uint32 arithmetic); flipping the sign bit
    then makes signed order equal the hash's unsigned order, because
    Mosaic reduces no unsigned integers. ``UINT_MAX`` maps to ``INT_MAX``.
    """
    srl = jax.lax.shift_right_logical
    h = x * _HASH_MUL
    h = h ^ (seed * _HASH_SEED_MUL)
    h = h ^ srl(h, jnp.int32(15))
    h = h * _HASH_FIN_MUL
    h = h ^ srl(h, jnp.int32(13))
    return h ^ _SIGN_BIT


def _select_rows(s_k, s_v, inc, seed):
    """In-kernel move selection over folded [k, tile_r] sketches.

    Replays ``select_best``'s candidate preprocessing and
    ``choose_from_candidates`` bit-for-bit over the sketch + the incumbent
    ``inc`` [1, tile_r]: max weight wins, ties resolved by the
    per-iteration hash, then the smaller label; no candidate -> keep the
    incumbent. The k slots and the incumbent reduce separately and then
    combine — max/min are exact, so this equals one (k+1)-wide reduction.
    Returns the chosen label per row [1, tile_r].
    """
    cand = jnp.where(s_v > 0, s_k, -1)  # select_best's preprocessing
    cur_w = jnp.max(jnp.where((cand == inc) & (s_v > 0), s_v, 0.0),
                    axis=0, keepdims=True)
    w_s = jnp.where(cand >= 0, s_v, -1.0)
    w_i = jnp.where(inc >= 0, cur_w, -1.0)
    w_best = jnp.maximum(jnp.max(w_s, axis=0, keepdims=True), w_i)
    tied_s = (cand >= 0) & (w_s >= w_best)
    tied_i = (inc >= 0) & (w_i >= w_best)
    h_s = jnp.where(tied_s, _hash_key(cand, seed), INT_MAX)
    h_i = jnp.where(tied_i, _hash_key(inc, seed), INT_MAX)
    h_best = jnp.minimum(jnp.min(h_s, axis=0, keepdims=True), h_i)
    c_s = jnp.min(jnp.where(tied_s & (h_s <= h_best), cand, INT_MAX),
                  axis=0, keepdims=True)
    c_best = jnp.minimum(c_s, jnp.where(tied_i & (h_i <= h_best), inc,
                                        INT_MAX))
    return jnp.where(c_best == INT_MAX, inc, c_best)


# ---------------------------------------------------------------------------
# Kernels: (dmax, starts, counts, [extras...], labels, weights) -> outputs,
# then the four gather scratch tiles. Shared by fused and streamed rounds.
# ---------------------------------------------------------------------------


def _fold_kernel(dmax_ref, start_ref, count_ref, lab_ref, wgt_ref,
                 out_k_ref, out_v_ref, *scratch, k: int):
    """One MG step: gather the tile and fold it into [k, tile_r] sketches."""
    _gather_tile(start_ref, lab_ref, wgt_ref, *scratch)
    out_k_ref[...], out_v_ref[...] = _mg_fold(scratch[2], scratch[3],
                                              count_ref[...], k,
                                              dmax_ref[0, 0])


def _fold_marks_kernel(dmax_ref, start_ref, count_ref, lab_ref, wgt_ref,
                       out_k_ref, out_v_ref, out_m_ref, *scratch, k: int):
    """:func:`_fold_kernel` on packed labels (an aligned round 0 that marks
    the frontier): also writes each row's mark (:func:`_strip_marks`)."""
    _gather_tile(start_ref, lab_ref, wgt_ref, *scratch)
    out_m_ref[...] = _strip_marks(scratch[2], count_ref[...])
    out_k_ref[...], out_v_ref[...] = _mg_fold(scratch[2], scratch[3],
                                              count_ref[...], k,
                                              dmax_ref[0, 0])


def _select_kernel(dmax_ref, start_ref, count_ref, inc_ref, seed_ref,
                   lab_ref, wgt_ref, out_c_ref, *scratch, k: int):
    """Final-round fold + move selection in one step. The final round has
    at most one row per vertex, so the row's choice IS the vertex's."""
    _gather_tile(start_ref, lab_ref, wgt_ref, *scratch)
    s_k, s_v = _mg_fold(scratch[2], scratch[3], count_ref[...], k,
                        dmax_ref[0, 0])
    out_c_ref[...] = _select_rows(s_k, s_v, inc_ref[...], seed_ref[0, 0])


def _select_marks_kernel(dmax_ref, start_ref, count_ref, inc_ref, seed_ref,
                         lab_ref, wgt_ref, out_c_ref, *scratch, k: int):
    """:func:`_select_kernel` on packed labels (a single-round aligned
    plan that marks the frontier): each row's mark rides in the sign bit
    of its choice (:func:`pack_marks`; a pad row's -1 has no mark), so the
    marks cost no output of their own."""
    _gather_tile(start_ref, lab_ref, wgt_ref, *scratch)
    mark = _strip_marks(scratch[2], count_ref[...])
    s_k, s_v = _mg_fold(scratch[2], scratch[3], count_ref[...], k,
                        dmax_ref[0, 0])
    choice = _select_rows(s_k, s_v, inc_ref[...], seed_ref[0, 0])
    out_c_ref[...] = pack_marks(choice, mark > 0)


def _bm_kernel(dmax_ref, start_ref, count_ref, init_ref, lab_ref, wgt_ref,
               out_c_ref, out_w_ref, *scratch):
    """One BM step: gather the tile and run the majority-vote scan."""
    _gather_tile(start_ref, lab_ref, wgt_ref, *scratch)
    ck, wk = _bm_fold(scratch[2], scratch[3], count_ref[...],
                      init_ref[...], dmax_ref[0, 0])
    out_c_ref[...] = ck
    out_w_ref[...] = wk


def _rescan_kernel(dmax_ref, start_ref, count_ref, cand_ref, lab_ref,
                   wgt_ref, out_ref, *scratch):
    """One rescan step: gather the tile and score the row candidates."""
    _gather_tile(start_ref, lab_ref, wgt_ref, *scratch)
    out_ref[...] = _rescan_acc(scratch[2], scratch[3], count_ref[...],
                               cand_ref[...], dmax_ref[0, 0])


#: Stable kernel names (``pallas_call(name=...)``): the TPU custom call
#: and its profiler events carry them. A kernel that also marks the
#: frontier keeps the name of the fold it runs.
KERNEL_NAMES = {_fold_kernel: "lpa_fold", _select_kernel: "lpa_select",
                _fold_marks_kernel: "lpa_fold",
                _select_marks_kernel: "lpa_select",
                _bm_kernel: "lpa_bm_fold", _rescan_kernel: "lpa_rescan"}


def fold_scope(index: int):
    """Device scope (``op_name``) of fold round ``index``'s kernel launch;
    a sibling of the ``lpa.relayout`` scope of the round's entries."""
    return jax.named_scope(f"lpa.fold.r{index}")


def slot_major(rows_k: jnp.ndarray, tile_r: int) -> jnp.ndarray:
    """[n_steps * tile_r, k] per-row slots -> the kernels' lane-dense
    [n_steps, k, tile_r] step blocks (slot on sublanes, row on lanes)."""
    k = rows_k.shape[1]
    return rows_k.reshape(-1, tile_r, k).transpose(0, 2, 1)


def row_major(steps: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`slot_major`: [n_steps, k, tile_r] -> the plan's
    [n_steps * tile_r, k] row order, which later rounds read flattened."""
    return steps.transpose(0, 2, 1).reshape(-1, steps.shape[1])


def _step_spec(*block):
    """Grid step ``i``'s block of a per-step [n_steps, *block] operand."""
    return pl.BlockSpec((None,) + block, lambda i: (i,) + (0,) * len(block))


def _round_call(kernel, rnd, extras, entries, entries_spec, outs, *,
                chunk: int, interpret: bool | None):
    """Launch one round's kernel over its ``n_steps`` grid.

    ``rnd`` is a ``FusedRound`` or ``StreamedRound`` (the per-step
    ``step_dmax``/``row_start``/``row_count`` metadata); ``extras`` are
    (operand, per-step block) pairs of [n_steps, *block] operands between
    the metadata and the entries, or (operand, None) for a whole-array
    SMEM scalar; ``entries`` are the (labels, weights) lane-row sources
    under ``entries_spec``; ``outs`` are (dtype, per-step block) pairs.
    """
    n_steps, tile_r = rnd.row_start.shape
    width = _lane_groups(chunk) * LANES
    smem = pltpu.SMEM
    in_specs = [
        pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0), memory_space=smem),
        pl.BlockSpec((None, 1, tile_r), lambda i: (i, 0, 0),
                     memory_space=smem),
        _step_spec(1, tile_r),
    ]
    args = [rnd.step_dmax.reshape(n_steps, 1, 1),
            rnd.row_start.reshape(n_steps, 1, tile_r),
            rnd.row_count.reshape(n_steps, 1, tile_r)]
    for x, block in extras:
        in_specs.append(pl.BlockSpec(memory_space=smem) if block is None
                        else _step_spec(*block))
        args.append(x if block is None else x.reshape((n_steps,) + block))
    in_specs += [entries_spec, entries_spec]
    args += list(entries)
    return pl.pallas_call(
        kernel,
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=[_step_spec(*block) for _, block in outs],
        out_shape=[jax.ShapeDtypeStruct((n_steps,) + block, dtype)
                   for dtype, block in outs],
        scratch_shapes=[pltpu.VMEM((tile_r, width), jnp.int32),
                        pltpu.VMEM((tile_r, width), jnp.float32),
                        pltpu.VMEM((width, tile_r), jnp.int32),
                        pltpu.VMEM((width, tile_r), jnp.float32)],
        interpret=_interpret_default() if interpret is None else interpret,
        name=KERNEL_NAMES[getattr(kernel, "func", kernel)],
    )(*args)


def _pad_entry_rows(x: jnp.ndarray, length: int, chunk: int, fill
                    ) -> jnp.ndarray:
    """Flat entry array -> [rows, 128] lane rows, padded so that every
    row range of the ``length``-entry round (start <= length) finds the
    ``G + 1`` lane rows :func:`_gather_tile` loads from its start."""
    rows = -(-max(length, x.shape[0]) // LANES) + _lane_groups(chunk) + 1
    x = jnp.concatenate(
        [x, jnp.full((rows * LANES - x.shape[0],), fill, x.dtype)])
    return x.reshape(rows, LANES)


def _fused_round(kernel, rnd: FusedRound, extras, entry_labels,
                 entry_weights, outs, *, chunk: int, interpret, index: int):
    """One fused dispatch: the round's whole entry arrays stay resident in
    VMEM (a whole-array block, copied in once) for every grid step."""
    with jax.named_scope("lpa.relayout"):
        el = _pad_entry_rows(entry_labels.astype(jnp.int32),
                             rnd.n_entries_in, chunk, -1)
        ew = _pad_entry_rows(entry_weights.astype(jnp.float32),
                             rnd.n_entries_in, chunk, 0.0)
    with fold_scope(index):
        return _round_call(kernel, rnd, extras, (el, ew),
                           pl.BlockSpec(memory_space=pltpu.VMEM), outs,
                           chunk=chunk, interpret=interpret)


def fused_fold_round(rnd: FusedRound, entry_labels: jnp.ndarray,
                     entry_weights: jnp.ndarray, *, k: int, chunk: int,
                     interpret: bool | None = None, index: int = 0
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One dispatch covering every width bucket of the round (the plan's
    round ``index``, which names its device scope).

    Returns padded ([n_steps*tile_r, k], [n_steps*tile_r, k]) sketches in
    fused row order (pad rows fold to empty sketches).
    """
    block = (k, rnd.tile_r)
    s_k, s_v = _fused_round(
        functools.partial(_fold_kernel, k=k), rnd, (), entry_labels,
        entry_weights, [(jnp.int32, block), (jnp.float32, block)],
        chunk=chunk, interpret=interpret, index=index)
    with fold_scope(index):
        return row_major(s_k), row_major(s_v)


def fused_select_round(rnd: FusedRound, entry_labels: jnp.ndarray,
                       entry_weights: jnp.ndarray, incumbents: jnp.ndarray,
                       seed: jnp.ndarray, *, k: int, chunk: int,
                       interpret: bool | None = None,
                       index: int = 0) -> jnp.ndarray:
    """Final-round dispatch (round ``index``): fold + per-row winning
    label [n_steps*tile_r]."""
    tile_r = rnd.tile_r
    (out,) = _fused_round(
        functools.partial(_select_kernel, k=k), rnd,
        [(incumbents, (1, tile_r)),
         (seed.astype(jnp.int32).reshape(1, 1), None)],
        entry_labels, entry_weights, [(jnp.int32, (1, tile_r))],
        chunk=chunk, interpret=interpret, index=index)
    return out.reshape(-1)


def run_mg_plan_fused(plan: FusedFoldPlan, entry_labels: jnp.ndarray,
                      entry_weights: jnp.ndarray,
                      interpret: bool | None = None, *, selection=None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All fold rounds, one dispatch each. Returns the final-round padded
    sketches in fused row order (map to vertices via plan.row_to_vertex).

    With a ``selection`` (RoundSelection) each round grids only over the
    frontier-compacted active rows and scatters its sketches back to dense
    row order (inactive rows hold empty sketches), so the output layout is
    selection-invariant.
    """
    labels, weights = entry_labels, entry_weights
    if selection is None:
        for i, rnd in enumerate(plan.rounds):
            s_k, s_v = fused_fold_round(rnd, labels, weights, k=plan.k,
                                        chunk=plan.chunk,
                                        interpret=interpret, index=i)
            labels, weights = s_k.reshape(-1), s_v.reshape(-1)
    else:
        for i, rnd in enumerate(plan.rounds):
            sub, idx, _ = _sparse_fused_round(rnd, selection.frontier,
                                              selection.cap_rows)
            c_k, c_v = fused_fold_round(sub, labels, weights, k=plan.k,
                                        chunk=plan.chunk,
                                        interpret=interpret, index=i)
            rows = rnd.row_vertex.shape[0]
            s_k = _scatter_sparse_rows(idx, c_k, rows, jnp.int32(-1))
            s_v = _scatter_sparse_rows(idx, c_v, rows, jnp.float32(0.0))
            labels, weights = s_k.reshape(-1), s_v.reshape(-1)
    return s_k, s_v


def select_best_fused(plan: FusedFoldPlan, entry_labels: jnp.ndarray,
                      entry_weights: jnp.ndarray, labels: jnp.ndarray,
                      seed: jnp.ndarray, interpret: bool | None = None,
                      *, selection=None) -> jnp.ndarray:
    """Full fused MG iteration: ``n_rounds`` dispatches, the last one fused
    with move selection. Bit-identical to ``run_mg_plan`` + ``select_best``
    on the reference backend.

    With a ``selection``, every round grids only over the compacted active
    rows: off-frontier vertices keep their label verbatim (never computed),
    and on the frontier the wanted label is bit-identical to the dense run
    — the caller must have checked ``selection.cap_rows`` fits the
    frontier (``csr.fused_active_rows``).
    """
    if plan.n_nodes == 0:
        return labels
    el, ew = entry_labels, entry_weights
    if selection is None:
        for i, rnd in enumerate(plan.rounds[:-1]):
            s_k, s_v = fused_fold_round(rnd, el, ew, k=plan.k,
                                        chunk=plan.chunk,
                                        interpret=interpret, index=i)
            el, ew = s_k.reshape(-1), s_v.reshape(-1)
        last, rv = plan.rounds[-1], plan.row_to_vertex
    else:
        for i, rnd in enumerate(plan.rounds[:-1]):
            sub, idx, _ = _sparse_fused_round(rnd, selection.frontier,
                                              selection.cap_rows)
            c_k, c_v = fused_fold_round(sub, el, ew, k=plan.k,
                                        chunk=plan.chunk,
                                        interpret=interpret, index=i)
            rows = rnd.row_vertex.shape[0]
            el = _scatter_sparse_rows(idx, c_k, rows,
                                      jnp.int32(-1)).reshape(-1)
            ew = _scatter_sparse_rows(idx, c_v, rows,
                                      jnp.float32(0.0)).reshape(-1)
        last, _, rv = _sparse_fused_round(plan.rounds[-1],
                                          selection.frontier,
                                          selection.cap_rows)
    n = plan.n_nodes
    real = rv >= 0
    with jax.named_scope("lpa.apply"):
        incumbents = jnp.where(real, labels[jnp.maximum(rv, 0)], -1)
    choice = fused_select_round(last, el, ew, incumbents, seed,
                                k=plan.k, chunk=plan.chunk,
                                interpret=interpret,
                                index=len(plan.rounds) - 1)
    # [N] scatter of per-row winners (pad/sentinel rows land in the dump
    # slot); vertices with no fold rows — degree 0, or off-frontier under a
    # selection — keep their label, identical to choose_from_candidates
    # with an empty candidate set.
    with jax.named_scope("lpa.apply"):
        buf = jnp.concatenate([labels, jnp.zeros((1,), labels.dtype)])
        buf = buf.at[jnp.where(real, rv, n)].set(
            jnp.where(real, choice, -1))
        return buf[:n]


# ---------------------------------------------------------------------------
# Boyer-Moore fold: round 0 in one dispatch (DESIGN.md §11)
# ---------------------------------------------------------------------------


def bm_fold_round_fused(rnd: FusedRound, entry_labels: jnp.ndarray,
                        entry_weights: jnp.ndarray,
                        init_labels: jnp.ndarray, *, chunk: int,
                        interpret: bool | None = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One dispatch covering the whole BM fold (only round 0 is ever
    folded — BM partials merge by max-reduce, not by re-folding).

    ``init_labels`` [n_steps * tile_r] int32 carries each row's incumbent
    label (-1 on pad rows). Returns per-row ([rows] candidate label,
    [rows] vote weight) partial states in fused row order.
    """
    row = (1, rnd.tile_r)
    ck, wk = _fused_round(_bm_kernel, rnd, [(init_labels, row)],
                          entry_labels, entry_weights,
                          [(jnp.int32, row), (jnp.float32, row)],
                          chunk=chunk, interpret=interpret, index=0)
    return ck.reshape(-1), wk.reshape(-1)


def run_bm_plan_generic(plan, entry_labels: jnp.ndarray,
                        entry_weights: jnp.ndarray, cur_labels: jnp.ndarray,
                        fold_round_fn, interpret: bool | None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shared νBM driver for the fused and streamed engines.

    Incumbent-initializes each round-0 row from ``plan.row_to_vertex0``,
    runs the engine's single round-0 dispatch
    (``fold_round_fn(rnd, el, ew, init, *, chunk, interpret)``) and merges
    the per-row partial states per vertex with the order-insensitive
    ``sketch.bm_merge_rows`` max-reduce. One copy of this logic keeps the
    engines' init-label and merge conventions from ever diverging.
    Returns per-vertex (label [N], weight [N]); no-entry vertices get -1.
    """
    from repro.core.sketch import bm_init_rows, bm_merge_rows
    n = plan.n_nodes
    if n == 0:
        return (jnp.full((0,), -1, jnp.int32), jnp.zeros((0,), jnp.float32))
    rtv0 = plan.row_to_vertex0
    with jax.named_scope("lpa.apply"):
        init = bm_init_rows(rtv0, cur_labels)
    ck, wk = fold_round_fn(plan.rounds[0], entry_labels, entry_weights,
                           init, chunk=plan.chunk, interpret=interpret)
    with jax.named_scope("lpa.apply"):
        return bm_merge_rows(n, cur_labels, rtv0, ck, wk)


def run_bm_plan_fused(plan: FusedFoldPlan, entry_labels: jnp.ndarray,
                      entry_weights: jnp.ndarray, cur_labels: jnp.ndarray,
                      interpret: bool | None = None, *, selection=None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused νBM iteration core: ONE kernel dispatch (vs one per round-0
    width bucket) + the max-reduce merge of per-row partial states.
    Bit-identical to ``repro.core.sketch.run_bm_plan`` — per-row folds
    replay the same entry sequences, and the merge
    (``sketch.bm_merge_rows``) is an order-insensitive max/min scatter.
    Returns per-vertex (label [N], weight [N]); no-entry vertices get -1.

    With a ``selection``, the single dispatch grids only over active
    round-0 rows. ``bm_merge_rows`` is order-insensitive over whatever
    rows it is handed, and activity is per-vertex (every row of an active
    vertex is in the compacted set), so active vertices merge the complete
    bit-identical partial set; vertices with no compacted rows come back
    (-1, 0) — the gate masks them, like dense off-frontier moves.
    """
    if selection is None:
        return run_bm_plan_generic(plan, entry_labels, entry_weights,
                                   cur_labels, bm_fold_round_fused,
                                   interpret)
    from repro.core.sketch import bm_init_rows, bm_merge_rows
    n = plan.n_nodes
    if n == 0:
        return (jnp.full((0,), -1, jnp.int32), jnp.zeros((0,), jnp.float32))
    sub, _, rv_c = _sparse_fused_round(plan.rounds[0], selection.frontier,
                                       selection.cap_rows)
    init = bm_init_rows(rv_c, cur_labels)
    ck, wk = bm_fold_round_fused(sub, entry_labels, entry_weights, init,
                                 chunk=plan.chunk, interpret=interpret)
    return bm_merge_rows(n, cur_labels, rv_c, ck, wk)


# ---------------------------------------------------------------------------
# Rescan (double-scan ablation): the second pass in one dispatch
# ---------------------------------------------------------------------------


def rescan_round_fused(rnd: FusedRound, entry_labels: jnp.ndarray,
                       entry_weights: jnp.ndarray, cand_rows: jnp.ndarray,
                       *, k: int, chunk: int, interpret: bool | None = None
                       ) -> jnp.ndarray:
    """One dispatch re-reading round 0 to score each row's candidates.

    ``cand_rows`` [n_steps * tile_r, k] int32 holds each row's (owning
    vertex's) consolidated candidate labels. Returns [n_steps * tile_r, k]
    float32 partial linking weights in fused row order.
    """
    block = (k, rnd.tile_r)
    (out,) = _fused_round(_rescan_kernel, rnd,
                          [(slot_major(cand_rows, rnd.tile_r), block)],
                          entry_labels, entry_weights,
                          [(jnp.float32, block)], chunk=chunk,
                          interpret=interpret, index=0)
    with fold_scope(0):
        return row_major(out)


def rescan_select_generic(plan, entry_labels: jnp.ndarray,
                          entry_weights: jnp.ndarray, labels: jnp.ndarray,
                          seed: jnp.ndarray, run_plan_fn, rescan_round_fn,
                          interpret: bool | None) -> jnp.ndarray:
    """Shared double-scan driver for the fused and streamed engines.

    Runs the engine's MG fold (``run_plan_fn``), scatters the final
    sketches to per-vertex candidate sets, broadcasts them to round-0 rows
    via ``plan.row_to_vertex0`` and runs the engine's single rescan
    dispatch (``rescan_round_fn``); partials merge through the shared
    deterministic ``sketch.merge_rescan_partials``. One copy of this logic
    keeps the engines' candidate-mask and merge conventions aligned (they
    are what the cross-backend bit-parity rests on).
    """
    from repro.core.sketch import choose_from_candidates, merge_rescan_partials
    n, k = plan.n_nodes, plan.k
    if n == 0:
        return labels
    s_k, _ = run_plan_fn(plan, entry_labels, entry_weights,
                         interpret=interpret)
    rtv = plan.row_to_vertex
    rtv0 = plan.row_to_vertex0
    with jax.named_scope("lpa.apply"):
        cand = jnp.full((n + 1, k), -1, jnp.int32).at[
            jnp.where(rtv >= 0, rtv, n)].set(s_k)[:n]
        cand_ext = jnp.concatenate([cand, jnp.full((1, k), -1, jnp.int32)])
        cand_rows = cand_ext[jnp.where(rtv0 >= 0, rtv0, n)]
    parts = rescan_round_fn(plan.rounds[0], entry_labels, entry_weights,
                            cand_rows, k=k, chunk=plan.chunk,
                            interpret=interpret)
    with jax.named_scope("lpa.apply"):
        acc = merge_rescan_partials(n, k, plan.max_rows0, rtv0,
                                    plan.row_rank0, parts)
        return choose_from_candidates(jnp.where(acc > 0, cand, -1), acc,
                                      labels, seed)


def rescan_select_fused(plan: FusedFoldPlan, entry_labels: jnp.ndarray,
                        entry_weights: jnp.ndarray, labels: jnp.ndarray,
                        seed: jnp.ndarray, interpret: bool | None = None,
                        *, selection=None) -> jnp.ndarray:
    """Full double-scan MG iteration on the fused engine: ``n_rounds``
    fold dispatches + ONE rescan dispatch (vs a per-bucket second walk).
    Bit-identical to the reference ``run_mg_plan`` + ``rescan_candidates``
    — shared accumulate order and merge (see ``sketch.rescan_candidates``).

    With a ``selection``, the fold rounds and the rescan dispatch grid
    only over compacted active rows. Inactive vertices end with an
    all-empty candidate set (zero accumulated weight), so
    ``choose_from_candidates`` keeps their label — bit-identical on the
    frontier to the dense run.
    """
    if selection is None:
        return rescan_select_generic(plan, entry_labels, entry_weights,
                                     labels, seed, run_mg_plan_fused,
                                     rescan_round_fused, interpret)
    from repro.core.sketch import choose_from_candidates, merge_rescan_partials
    n, k = plan.n_nodes, plan.k
    if n == 0:
        return labels
    s_k, _ = run_mg_plan_fused(plan, entry_labels, entry_weights,
                               interpret=interpret, selection=selection)
    rtv = plan.row_to_vertex
    cand = jnp.full((n + 1, k), -1, jnp.int32).at[
        jnp.where(rtv >= 0, rtv, n)].set(s_k)[:n]
    sub0, idx0, rv0_c = _sparse_fused_round(plan.rounds[0],
                                            selection.frontier,
                                            selection.cap_rows)
    cand_ext = jnp.concatenate([cand, jnp.full((1, k), -1, jnp.int32)])
    cand_rows = cand_ext[jnp.where(rv0_c >= 0, rv0_c, n)]
    parts_c = rescan_round_fused(sub0, entry_labels, entry_weights,
                                 cand_rows, k=k, chunk=plan.chunk,
                                 interpret=interpret)
    rows0 = plan.rounds[0].row_vertex.shape[0]
    parts = _scatter_sparse_rows(idx0, parts_c, rows0, jnp.float32(0.0))
    acc = merge_rescan_partials(n, k, plan.max_rows0, plan.row_to_vertex0,
                                plan.row_rank0, parts)
    return choose_from_candidates(jnp.where(acc > 0, cand, -1), acc,
                                  labels, seed)


# ---------------------------------------------------------------------------
# Sparse frontier path: grid only over active rows (DESIGN.md §8.5)
# ---------------------------------------------------------------------------
#
# The dense gated mover computes every fold row and lets the frontier mask
# discard off-frontier moves after the fact — correct, but zero FLOPs
# saved. The sparse drivers below compact each round's *active* rows (rows
# whose owning vertex is on the frontier) into a fixed-capacity synthetic
# ``FusedRound`` whose metadata is traced, then run the UNCHANGED kernels
# above over the compacted grid. Activity is per-vertex, so an active
# vertex's whole multi-round reduction chain is computed from real inputs
# and stays bit-identical to the dense fold; inactive vertices' partials
# are left as empty sketches (label -1 / weight 0) in the scatter-back
# buffers and are only ever read by rows that are themselves inactive.
# Capacity fit is the CALLER's job: the host checks the concrete frontier
# against ``csr.fused_active_rows`` and falls back to the dense mover on
# overflow (``compact_active_rows`` silently drops overflowing rows).


def _sparse_fused_round(rnd: FusedRound, frontier: jnp.ndarray,
                        cap_rows: int):
    """Compact one round's active rows into a capped synthetic round.

    Returns ``(sub_round, idx, row_vertex)``: a ``FusedRound`` of
    ``min(ceil(cap_rows / tile_r), n_steps)`` steps whose metadata is
    gathered (traced) from the dense round, the [cap] compacted row
    indices (sentinel = dense row count, pointing at an appended neutral
    row), and the [cap] owning vertex per compacted row (-1 on sentinel
    slots).
    """
    n_steps, tile_r = rnd.row_start.shape
    n = frontier.shape[0]
    rv = rnd.row_vertex
    real = rv >= 0
    cap_steps = min(-(-cap_rows // tile_r), n_steps)
    with jax.named_scope("lpa.relayout"):
        front_ext = jnp.concatenate([frontier.astype(jnp.bool_),
                                     jnp.zeros((1,), jnp.bool_)])
        active = real & front_ext[jnp.where(real, rv, n)]
        idx = compact_active_rows(active, cap_steps * tile_r)
        zero_row = jnp.zeros((1,), jnp.int32)
        rs = jnp.concatenate([rnd.row_start.reshape(-1), zero_row])[idx]
        rc = jnp.concatenate([rnd.row_count.reshape(-1), zero_row])[idx]
        rv_c = jnp.concatenate([rv, jnp.full((1,), -1, jnp.int32)])[idx]
        rs2 = rs.reshape(cap_steps, tile_r)
        rc2 = rc.reshape(cap_steps, tile_r)
        sub = FusedRound(row_start=rs2, row_count=rc2,
                         step_dmax=jnp.max(rc2, axis=1, keepdims=True),
                         n_entries_in=rnd.n_entries_in)
    return sub, idx, rv_c


def _scatter_sparse_rows(idx: jnp.ndarray, values: jnp.ndarray, rows: int,
                         fill) -> jnp.ndarray:
    """Scatter compacted per-row results back to dense row positions.

    Sentinel slots land in a dump row that is sliced off; unwritten dense
    rows keep ``fill`` (the empty-sketch value, so later rounds read
    exact no-op entries for inactive vertices).
    """
    with jax.named_scope("lpa.relayout"):
        buf = jnp.full((rows + 1,) + values.shape[1:], fill, values.dtype)
        return buf.at[idx].set(values)[:rows]
