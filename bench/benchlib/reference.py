"""Plain host reference of sketch label propagation (νMG-LPA), in numpy.

The semantics, written from the paper (Algorithms 1, 2 and 4) and the
program's documented synchronous schedule, independent of its code:

* Labels start unique (vertex id). Every iteration is synchronous: each
  vertex reads its neighbours' labels from the previous iteration.
* A vertex's neighbour list (CSR order, ascending id) is cut into rows of
  at most ``chunk`` entries ("virtual vertices"). Each row is folded into
  a ``k``-slot weighted Misra-Gries sketch, one entry after another: a
  matching occupied slot adds the weight; else the first free slot takes
  the label and weight; else every slot loses the weight, clamped at 0
  (a slot at 0 is free). Entries of weight 0 are skipped.
* While a vertex owns more than one row, its rows' sketches, k slots each
  in row order, form its entry list of the next round, which is cut and
  folded the same way.
* The new label is the heaviest of the final sketch's labels and the
  incumbent (at its sketched weight, 0 if absent); ties go to the smaller
  per-iteration hash of the label, then to the smaller label.
* Pick-Less: every ``rho`` iterations from iteration 0 a vertex may only
  move to a smaller label. The run stops after a non-Pick-Less iteration
  in which fewer than ``tau`` of the vertices moved, or at ``max_iters``.

``counter_dtype`` is the type of the edge and sketch weights (float32 as
the configurations state); ``k`` the sketch slots. ``CONTROLS`` are the
reference with one of these cut, in the program's place. A configuration
names in ``control`` the one that its comparison has to reject: ``bf16``,
the precision below float32, where the weights are real numbers; ``k4``,
half the sketch slots, where every weight is 1, so that every counter is
a whole count that bfloat16 holds exactly and no number could tell the
two precisions apart (PERF.md).
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np


CONTROLS = {
    "bf16": {"counter_dtype": ml_dtypes.bfloat16},  # the next precision down
    "k4": {"k": 4},                                 # half the sketch slots
}


@dataclasses.dataclass
class RefResult:
    labels: np.ndarray      # [N] int32
    iterations: int
    changed_history: list   # vertices that moved, per iteration


def hash_mix(x: np.ndarray, seed: int) -> np.ndarray:
    """Knuth multiplicative hash with xorshifts, in wrapping uint32."""
    h = x.astype(np.uint32) * np.uint32(2654435761)
    h ^= np.uint32((seed * 0x9E3779B9) & 0xFFFFFFFF)
    h ^= h >> np.uint32(15)
    h *= np.uint32(0x85EBCA77)
    return h ^ (h >> np.uint32(13))


@dataclasses.dataclass
class _Round:
    start: np.ndarray    # [R] entry offset of each row, rows by count desc
    count: np.ndarray    # [R] entries of each row, descending
    canon: np.ndarray    # [R] canonical (vertex-major, rank) position
    active: np.ndarray   # [max count] rows with count > j


def plan_rounds(degrees: np.ndarray, chunk: int, k: int):
    """Row structure of every fold round; depends on degrees alone.
    Returns (rounds, final_row_vertex)."""
    n = len(degrees)
    counts = degrees.astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rounds = []
    while True:
        n_rows = (counts + chunk - 1) // chunk
        row_vertex = np.repeat(np.arange(n), n_rows)
        rank = np.arange(len(row_vertex)) - np.repeat(
            np.cumsum(n_rows) - n_rows, n_rows)
        row_start = starts[row_vertex] + rank * chunk
        row_count = np.minimum(counts[row_vertex] - rank * chunk, chunk)
        order = np.argsort(-row_count, kind="stable")
        cnt = row_count[order]
        active = np.searchsorted(-cnt, -np.arange(int(cnt[0]) if len(cnt)
                                                  else 0), side="left")
        rounds.append(_Round(row_start[order], cnt, order, active))
        if np.all(n_rows <= 1):
            return rounds, row_vertex
        counts = n_rows * k
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])


def fold_round(rnd: _Round, ent_lab: np.ndarray, ent_w: np.ndarray, k: int):
    """Weighted Misra-Gries fold of every row. Slots are kept slot-major,
    [k, rows], so that each step is elementwise over rows. Returns the
    sketches as [rows, k] in canonical row order."""
    r = len(rnd.start)
    sk = np.full((k, r), -1, dtype=np.int32)
    sv = np.zeros((k, r), dtype=ent_w.dtype)
    zero = np.zeros((), dtype=ent_w.dtype)
    for j, a in enumerate(rnd.active):
        c = ent_lab[rnd.start[:a] + j]
        w = ent_w[rnd.start[:a] + j]
        # a slot is claimed only as the first free one, so after j entries
        # no slot past the j-th has been touched
        k_a, v_a = sk[:j + 1, :a], sv[:j + 1, :a]
        valid = (w > 0) & (c >= 0)
        free = v_a <= 0
        match = ~free & (k_a == c) & valid
        any_match = match.any(axis=0)
        np.add(v_a, w, out=v_a, where=match)
        has_free = free.any(axis=0)
        cols = np.nonzero(valid & ~any_match & has_free)[0]
        slot = free[:, cols].argmax(axis=0)
        k_a[slot, cols] = c[cols]
        v_a[slot, cols] = w[cols]
        dec = valid & ~any_match & ~has_free
        np.subtract(v_a, w, out=v_a, where=dec)
        np.maximum(v_a, zero, out=v_a, where=dec)
    out_k = np.empty((r, k), dtype=np.int32)
    out_v = np.empty((r, k), dtype=ent_w.dtype)
    out_k[rnd.canon] = sk.T
    out_v[rnd.canon] = sv.T
    return out_k, out_v


def choose(cand_c: np.ndarray, cand_w: np.ndarray, labels: np.ndarray,
           seed: int) -> np.ndarray:
    """Heaviest of the [k, N] candidates and the incumbent; ties by hash,
    then smaller label."""
    k, n = cand_c.shape
    zero = np.zeros((), dtype=cand_w.dtype)
    valid = cand_w > 0
    cur_w = np.where(valid & (cand_c == labels), cand_w, zero).max(axis=0)
    # the incumbent always competes, at a weight >= 0
    w_best = np.maximum(np.where(valid, cand_w, zero).max(axis=0), cur_w)
    best = np.full(n, np.uint64(0xFFFFFFFFFFFFFFFF))
    for j in range(k + 1):
        c, tied = ((cand_c[j], valid[j] & (cand_w[j] >= w_best)) if j < k
                   else (labels, cur_w >= w_best))
        rows = np.nonzero(tied)[0]
        c = c[rows]
        key = (hash_mix(c, seed).astype(np.uint64) << np.uint64(32)) \
            | c.astype(np.uint64)
        best[rows] = np.minimum(best[rows], key)
    return (best & np.uint64(0xFFFFFFFF)).astype(np.int32)


def mg_lpa(offsets: np.ndarray, indices: np.ndarray, weights: np.ndarray, *,
           k: int, chunk: int, rho: int, tau: float, max_iters: int,
           counter_dtype=np.float32) -> RefResult:
    n = len(offsets) - 1
    rounds, final_vertex = plan_rounds(np.diff(offsets), chunk, k)
    ent_w0 = weights.astype(counter_dtype)
    labels = np.arange(n, dtype=np.int32)
    history = []
    it = 0
    for it in range(max_iters):
        pick_less = it % rho == 0
        lab, w = labels[indices], ent_w0
        for rnd in rounds:
            sk, sv = fold_round(rnd, lab, w, k)
            lab, w = sk.reshape(-1), sv.reshape(-1)
        cand_c = np.full((k, n), -1, dtype=np.int32)
        cand_w = np.zeros((k, n), dtype=counter_dtype)
        cand_c[:, final_vertex] = sk.T
        cand_w[:, final_vertex] = sv.T
        want = choose(cand_c, cand_w, labels, it + 1)
        moved = (want < labels) if pick_less else (want != labels)
        delta = int(moved.sum())
        labels = np.where(moved, want, labels)
        history.append(delta)
        if not pick_less and delta / max(n, 1) < tau:
            break
    return RefResult(labels=labels, iterations=it + 1, changed_history=history)
