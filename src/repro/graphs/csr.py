"""CSR graph container and sketch fold-plan construction.

The fold plan is the host-side preprocessing that turns a power-law CSR
adjacency into dense, padded, statically-shaped tiles suitable for the
vectorized (lane-per-vertex) weighted Misra-Gries / Boyer-Moore folds — the
TPU analogue of the paper's low/high-degree kernel split:

  * every vertex's neighbor list is chunked into rows of at most ``chunk``
    entries ("virtual vertices"; chunk = the paper's D_H = 128 by default);
  * each row is assigned to a power-of-two width bucket so low-degree
    vertices (road networks, k-mer graphs: deg ~ 2) don't pad to 128;
  * a row folds into one k-slot partial sketch; rows of the same vertex are
    merged in later rounds (MG summaries are mergeable) — each round reduces
    per-vertex entries by ~chunk/k, so rounds are O(log_{chunk/k} D_max).

All plan arrays are static per graph (they depend only on the degree
structure, never on labels), so the whole multi-round fold jits cleanly.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

PAD = np.int32(-1)  # gather sentinel for padded entries


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSRGraph:
    """Symmetric weighted graph in CSR form (device arrays)."""

    offsets: jnp.ndarray  # [N+1] int32 — row offsets
    indices: jnp.ndarray  # [M] int32 — neighbor ids (both directions stored)
    weights: jnp.ndarray  # [M] float32 — edge weights (w_ij == w_ji)
    n_nodes: int  # int — vertex count N
    n_edges: int  # int — directed edge slots == len(indices)

    def tree_flatten(self):
        return (self.offsets, self.indices, self.weights), (self.n_nodes, self.n_edges)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def degrees(self) -> jnp.ndarray:
        return self.offsets[1:] - self.offsets[:-1]

    @property
    def total_weight(self) -> jnp.ndarray:
        """m = half the sum of all directed edge weights."""
        return 0.5 * jnp.sum(self.weights)

    def sources(self) -> jnp.ndarray:
        """Per-directed-edge source vertex id (expanded CSR rows)."""
        return jnp.asarray(np.repeat(np.arange(self.n_nodes, dtype=np.int32),
                                     np.asarray(self.degrees)))


def build_csr(edges: np.ndarray, n_nodes: int, weights: np.ndarray | None = None,
              symmetrize: bool = True, dedupe: bool = True) -> CSRGraph:
    """Build a CSRGraph from an [E, 2] int array of (possibly directed) edges.

    Self-loops are dropped (the paper's LPA skips j == i during voting).
    Duplicate edges have their weights accumulated.
    """
    edges = np.asarray(edges, dtype=np.int64)
    if weights is None:
        weights = np.ones(len(edges), dtype=np.float32)
    weights = np.asarray(weights, dtype=np.float32)
    keep = edges[:, 0] != edges[:, 1]
    edges, weights = edges[keep], weights[keep]
    if symmetrize and len(edges):
        edges = np.concatenate([edges, edges[:, ::-1]], axis=0)
        weights = np.concatenate([weights, weights], axis=0)
    if len(edges):
        key = edges[:, 0] * n_nodes + edges[:, 1]
        order = np.argsort(key, kind="stable")
        key, edges, weights = key[order], edges[order], weights[order]
        if dedupe:
            first = np.concatenate([[True], key[1:] != key[:-1]])
            group = np.cumsum(first) - 1
            weights = np.bincount(group, weights=weights,
                                  minlength=int(group[-1]) + 1).astype(np.float32)
            edges = edges[first]
    counts = np.bincount(edges[:, 0], minlength=n_nodes) if len(edges) else \
        np.zeros(n_nodes, dtype=np.int64)
    offsets = np.zeros(n_nodes + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    return CSRGraph(
        offsets=jnp.asarray(offsets, dtype=jnp.int32),
        indices=jnp.asarray(edges[:, 1], dtype=jnp.int32),
        weights=jnp.asarray(weights, dtype=jnp.float32),
        n_nodes=int(n_nodes),
        n_edges=int(len(edges)),
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FoldBucket:
    """One statically-shaped padded tile group inside a fold round."""

    width: int           # int — D, entries per row (power of two, <= chunk)
    gather: jnp.ndarray  # [R, D] int32 — indices into the round's entry arrays (PAD = -1)
    out_pos: jnp.ndarray  # [R] int32 — canonical (vertex, chunk-rank) row position
    vertex: jnp.ndarray  # [R] int32 — owning vertex of each row
    n_rows: int          # int — R, rows in this bucket's tile

    def tree_flatten(self):
        return (self.gather, self.out_pos, self.vertex), (self.width, self.n_rows)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], *children, aux[1])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FoldRound:
    buckets: Tuple[FoldBucket, ...]  # tuple[FoldBucket] — one padded tile per width
    n_entries_in: int    # int — length of the entry arrays this round consumes
    n_rows_total: int    # int — partial sketches produced (canonical rows)

    def tree_flatten(self):
        return (self.buckets,), (self.n_entries_in, self.n_rows_total)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FoldPlan:
    """Static multi-round reduction plan for the sketch folds.

    ``row_rank0`` maps each *canonical* round-0 row (the out_pos space the
    buckets scatter into) to its chunk rank within its vertex; together
    with ``FoldBucket.vertex`` it gives every round-0 partial a static
    (vertex, rank) coordinate — what the BM merge and the rescan second
    pass reduce over (``max_rows0`` = max chunk rows any vertex owns).
    """

    rounds: Tuple[FoldRound, ...]  # tuple[FoldRound] — one bucketed fold round each
    row_to_vertex: jnp.ndarray  # [final n_rows] int32 — owning vertex of each final sketch
    n_nodes: int  # int — vertex count N of the planned graph
    k: int        # int — sketch slots per row
    chunk: int    # int — entries per virtual-vertex row (paper D_H)
    row_rank0: Optional[jnp.ndarray] = None  # [round-0 n_rows] int32 — chunk rank
    max_rows0: int = 1  # int — max chunk rows any vertex owns on round 0

    def tree_flatten(self):
        return ((self.rounds, self.row_to_vertex, self.row_rank0),
                (self.n_nodes, self.k, self.chunk, self.max_rows0))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux[:3],
                   row_rank0=children[2], max_rows0=aux[3])

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)


def _bucket_widths(chunk: int, min_width: int = 4) -> List[int]:
    widths, w = [], min_width
    while w < chunk:
        widths.append(w)
        w *= 2
    widths.append(chunk)
    return widths


def _plan_round(counts: np.ndarray, starts: np.ndarray, chunk: int,
                widths: Sequence[int]):
    """Chunk per-vertex entry ranges [starts, starts+counts) into bucketed rows.

    Row order before bucketing is canonical: grouped by vertex, then chunk
    rank. Returns (buckets, n_chunks_per_vertex, row_vertex_canonical) where
    each bucket is (width, gather[R, D], out_pos[R], vertex[R]).
    """
    n = len(counts)
    n_chunks = ((counts + chunk - 1) // chunk).astype(np.int64)
    total_rows = int(n_chunks.sum())
    row_vertex = np.repeat(np.arange(n, dtype=np.int64), n_chunks)
    row_rank = np.arange(total_rows, dtype=np.int64) - np.repeat(
        np.cumsum(n_chunks) - n_chunks, n_chunks)
    row_start = starts[row_vertex] + row_rank * chunk
    row_count = np.minimum(counts[row_vertex] - row_rank * chunk, chunk)

    buckets = []
    widths_arr = np.asarray(widths)
    which = np.searchsorted(widths_arr, row_count)  # smallest width >= count
    for wi, width in enumerate(widths):
        sel = np.nonzero(which == wi)[0]
        if sel.size == 0:
            continue
        rs, rc, rv = row_start[sel], row_count[sel], row_vertex[sel]
        gather = rs[:, None] + np.arange(width)[None, :]
        mask = np.arange(width)[None, :] < rc[:, None]
        gather = np.where(mask, gather, PAD).astype(np.int32)
        buckets.append((int(width), gather, sel.astype(np.int32),
                        rv.astype(np.int32)))
    return buckets, n_chunks, row_vertex


def build_fold_plan(degrees: np.ndarray, k: int = 8, chunk: int = 128,
                    min_width: int = 4) -> FoldPlan:
    """Construct the static multi-round fold plan from the degree sequence."""
    degrees = np.asarray(degrees, dtype=np.int64)
    n = len(degrees)
    if chunk <= k:
        raise ValueError(f"chunk ({chunk}) must exceed sketch slots k ({k})")
    widths = _bucket_widths(chunk, min_width)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(degrees, out=offsets[1:])

    rounds: List[FoldRound] = []
    counts, starts = degrees, offsets[:-1].copy()
    n_entries = int(degrees.sum())
    row_rank0 = None
    max_rows0 = 1
    while True:
        np_buckets, n_chunks, row_vertex = _plan_round(counts, starts, chunk, widths)
        n_rows = int(n_chunks.sum())
        if row_rank0 is None:  # round 0: static (vertex, rank) coordinates
            row_rank0 = np.arange(n_rows, dtype=np.int64) - np.repeat(
                np.cumsum(n_chunks) - n_chunks, n_chunks)
            max_rows0 = max(int(n_chunks.max()) if len(n_chunks) else 0, 1)
        rounds.append(FoldRound(
            buckets=tuple(
                FoldBucket(width=w, gather=jnp.asarray(g), out_pos=jnp.asarray(p),
                           vertex=jnp.asarray(v), n_rows=len(v))
                for (w, g, p, v) in np_buckets),
            n_entries_in=n_entries,
            n_rows_total=n_rows,
        ))
        if np.all(n_chunks <= 1):
            final_row_vertex = row_vertex
            break
        # Next round consumes the flattened [n_rows, k] canonical sketches;
        # vertex i's entries are contiguous at k * [chunk-row span of i].
        counts = n_chunks * k
        starts = np.zeros(n, dtype=np.int64)
        starts[1:] = np.cumsum(counts)[:-1]
        n_entries = n_rows * k

    return FoldPlan(rounds=tuple(rounds),
                    row_to_vertex=jnp.asarray(final_row_vertex, dtype=jnp.int32),
                    n_nodes=n, k=k, chunk=chunk,
                    row_rank0=jnp.asarray(row_rank0, dtype=jnp.int32),
                    max_rows0=max_rows0)


def plan_padded_entries(plan: FoldPlan) -> int:
    """Total padded entry slots across all rounds (the fold's compute volume)."""
    return sum(b.width * b.n_rows for r in plan.rounds for b in r.buckets)


# ---------------------------------------------------------------------------
# Fused plan: one kernel dispatch per round (DESIGN.md §9)
# ---------------------------------------------------------------------------
#
# The bucketed FoldPlan above materializes a padded [R, D] gather tile per
# width bucket — one pallas_call each, with the tile round-tripping HBM. The
# fused layout exploits that every gather the plan ever produces is a
# *masked contiguous range* (row_start + arange(width), masked by count), so
# a round needs only two scalars per row: (start, count). The kernel
# generates indices arithmetically and dynamic-slices entries straight from
# the flat entry array, so the padded [R, D] tile exists only in VMEM.
#
# Rows are ordered vertex-major (all chunk rows of a vertex contiguous, in
# rank order) with vertices sorted by ascending entry count. Contiguity is
# load-bearing: round r+1 reads vertex v's round-r partial sketches as ONE
# contiguous slice of the round-r output. The count sort is a compute
# optimization only — it groups similar-width rows into the same tile_r
# step so the per-step fold loop bound (step_dmax) stays near the true row
# width instead of being dragged to `chunk` by one hub row.


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FusedRound:
    """Per-round metadata of the fused single-dispatch fold."""

    row_start: jnp.ndarray  # [n_steps, tile_r] int32 — offset into the flat entries (0 on pad rows)
    row_count: jnp.ndarray  # [n_steps, tile_r] int32 — valid entries of the row (0 on pad rows)
    step_dmax: jnp.ndarray  # [n_steps, 1] int32 — max row_count within the step
    n_entries_in: int       # int — flat entry-array length this round consumes
    # [n_steps * tile_r] int32 — owning vertex of each padded row (-1 on pad
    # rows); what the sparse frontier path compacts on (None: pre-sparse
    # synthetic rounds, e.g. the distributed per-shard movers)
    row_vertex: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        return ((self.row_start, self.row_count, self.step_dmax,
                 self.row_vertex),
                (self.n_entries_in,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], aux[0],
                   row_vertex=children[3])

    @property
    def n_steps(self) -> int:
        return self.row_start.shape[0]

    @property
    def tile_r(self) -> int:
        return self.row_start.shape[1]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FusedFoldPlan:
    """Static fused reduction plan: ~one kernel dispatch per round.

    ``row_to_vertex0``/``row_rank0`` map each *round-0* padded row to its
    (owning vertex, chunk rank) — the static coordinates the BM fold and
    the rescan second pass (both round-0-only walks) reduce over. For
    single-round plans ``row_to_vertex0`` equals ``row_to_vertex``.
    """

    rounds: Tuple[FusedRound, ...]  # tuple[FusedRound] — one fused fold round each
    row_to_vertex: jnp.ndarray  # [last n_steps * tile_r] int32 — owning vertex (-1 pad)
    n_nodes: int  # int — vertex count N of the planned graph
    k: int        # int — sketch slots per row
    chunk: int    # int — entries per virtual-vertex row (paper D_H)
    row_to_vertex0: Optional[jnp.ndarray] = None  # [round-0 n_steps * tile_r] int32
    row_rank0: Optional[jnp.ndarray] = None       # [round-0 n_steps * tile_r] int32
    max_rows0: int = 1  # int — max chunk rows any vertex owns on round 0

    def tree_flatten(self):
        return ((self.rounds, self.row_to_vertex, self.row_to_vertex0,
                 self.row_rank0),
                (self.n_nodes, self.k, self.chunk, self.max_rows0))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux[:3],
                   row_to_vertex0=children[2], row_rank0=children[3],
                   max_rows0=aux[3])

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)


def build_fused_fold_plan(degrees: np.ndarray, k: int = 8, chunk: int = 128,
                          tile_r: int = 128) -> FusedFoldPlan:
    """Construct the fused multi-round plan from the degree sequence.

    Folds the identical entry sequences as ``build_fold_plan`` (same chunking,
    same within-row order), so per-vertex results are bit-identical; only the
    row ordering and the dispatch structure differ.
    """
    degrees = np.asarray(degrees, dtype=np.int64)
    n = len(degrees)
    if chunk <= k:
        raise ValueError(f"chunk ({chunk}) must exceed sketch slots k ({k})")

    counts = degrees.copy()
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(degrees, out=offsets[1:])
    starts = offsets[:-1].copy()
    n_entries = int(degrees.sum())

    rounds: List[FusedRound] = []
    rtv0 = rank0 = None
    max_rows0 = 1
    while True:
        order = np.argsort(counts, kind="stable")  # ascending entry count
        n_chunks = ((counts + chunk - 1) // chunk).astype(np.int64)
        nc_ord = n_chunks[order]
        total_rows = int(nc_ord.sum())
        row_vertex = np.repeat(order, nc_ord)
        row_rank = np.arange(total_rows, dtype=np.int64) - np.repeat(
            np.cumsum(nc_ord) - nc_ord, nc_ord)
        row_start = starts[row_vertex] + row_rank * chunk
        row_count = np.minimum(counts[row_vertex] - row_rank * chunk, chunk)

        pad = (-total_rows) % tile_r if total_rows else tile_r
        rs = np.concatenate([row_start, np.zeros(pad, np.int64)])
        rc = np.concatenate([row_count, np.zeros(pad, np.int64)])
        rv_pad = np.concatenate(
            [row_vertex, np.full(pad, -1, np.int64)]).astype(np.int32)
        n_steps = len(rs) // tile_r
        rs2 = rs.reshape(n_steps, tile_r).astype(np.int32)
        rc2 = rc.reshape(n_steps, tile_r).astype(np.int32)
        rounds.append(FusedRound(
            row_start=jnp.asarray(rs2), row_count=jnp.asarray(rc2),
            step_dmax=jnp.asarray(rc2.max(axis=1, keepdims=True)),
            n_entries_in=n_entries, row_vertex=jnp.asarray(rv_pad)))
        if rtv0 is None:  # round 0: (vertex, rank) per padded row
            rtv0 = rv_pad
            rank0 = np.concatenate(
                [row_rank, np.zeros(pad, np.int64)]).astype(np.int32)
            max_rows0 = max(int(n_chunks.max()) if len(n_chunks) else 0, 1)
        if np.all(n_chunks <= 1):
            rtv = rv_pad
            break
        # Next round consumes this round's padded output [n_steps*tile_r, k]
        # flattened; vertex v's entries start at (v's first row) * k.
        first_row = np.zeros(n, dtype=np.int64)
        first_row[order] = np.cumsum(nc_ord) - nc_ord
        starts = first_row * k
        counts = n_chunks * k
        n_entries = n_steps * tile_r * k

    return FusedFoldPlan(rounds=tuple(rounds), row_to_vertex=jnp.asarray(rtv),
                         n_nodes=n, k=k, chunk=chunk,
                         row_to_vertex0=jnp.asarray(rtv0),
                         row_rank0=jnp.asarray(rank0), max_rows0=max_rows0)


# ---------------------------------------------------------------------------
# Streamed plan: fixed-size entry windows through VMEM (DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# The fused plan above keeps each round's flat entry arrays VMEM-resident
# (round 0 = |E| entries), which caps a single core at the VMEM budget.
# The streamed plan re-lays every round's entries into fixed-size windows
# of at most ``window_entries`` slots such that **no row straddles a window
# boundary**: each window owns at most ``tile_r`` rows whose entries are
# packed contiguously at window-relative offsets, with the invariant
# ``rel_start + chunk <= window_entries`` so the kernel's gather of any
# row stays inside the window. One grid step then
# consumes exactly one window: the Pallas pipeline streams each window's
# entry block HBM -> VMEM (double-buffered across grid steps) while the
# previous window folds, so per-step residency is O(window_entries), not
# O(|E|). Windows are closed greedily on whichever cap hits first (rows ==
# tile_r or entries past the slice-safe packing cap), and the materialized
# window stride is shrunk to the widest window actually produced
# (lane-aligned). ``window_entries`` is only the upper bound on that cap:
# each round picks its own cap from its rows (_choose_stream_windows), so a
# round of short rows is not laid out at the stride its hub rows need.

_STREAM_ALIGN = 128  # lane-align the materialized window stride

# A streamed window's cost in gathered window slots, the exchange rate of
# the per-round stride choice (_choose_stream_windows). On a TPU v5e a
# fold or select grid step costs about 13 us whatever its stride, and
# every window slot a round gathers (the aligned round-0 label gather, the
# merge rounds' label and weight re-layouts) about 8.6 ns, pad or real:
# the mover's device scopes over the graph500 scale-19 and 2^20-vertex
# k-mer cells' window and slot counts (PERF.md §5). 13 us / 8.6 ns ~ 1,500.
WINDOW_COST_SLOTS = 1500


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StreamedRound:
    """Per-round metadata of the HBM-streaming windowed fold.

    Shapes (W = ``window_entries``, R = rows per window = the plan's
    ``tile_r``): the round covers ``n_windows`` windows; window ``w`` owns
    entry slots ``[w*W, (w+1)*W)`` of the windowed layout and row slots
    ``[w*R, (w+1)*R)`` of the padded output.
    """

    entry_gather: jnp.ndarray  # [n_windows * W] int32 — source position per windowed slot (-1 = pad)
    row_start: jnp.ndarray     # [n_windows, R] int32 — window-RELATIVE entry offset (0 on pad rows)
    row_count: jnp.ndarray     # [n_windows, R] int32 — valid entries of the row (0 on pad rows)
    step_dmax: jnp.ndarray     # [n_windows, 1] int32 — max row_count within the window
    n_entries_in: int          # int — flat source entry-array length this round consumes
    window_entries: int        # int — W, entry slots per window (slice-safe: rel+chunk <= W)
    # [n_windows * R] int32 — owning vertex of each row slot (-1 on pad
    # slots); what the sparse frontier path compacts windows on (None:
    # pre-sparse synthetic rounds, e.g. the distributed per-shard movers)
    row_vertex: Optional[jnp.ndarray] = None
    # bool (static) — True when the round's source entries are ALREADY in
    # the windowed layout (build_streamed_fold_plan(aligned=True) round 0):
    # entry_gather degenerates to the identity permutation over real slots
    # (n_entries_in == n_windows * W) and the streaming kernels skip the
    # windowed re-layout gather entirely (kernels.mg_sketch.streaming)
    aligned: bool = False

    def tree_flatten(self):
        return ((self.entry_gather, self.row_start, self.row_count,
                 self.step_dmax, self.row_vertex),
                (self.n_entries_in, self.window_entries, self.aligned))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], children[3],
                   aux[0], aux[1], row_vertex=children[4], aligned=aux[2])

    @property
    def n_windows(self) -> int:
        return self.row_start.shape[0]

    @property
    def tile_r(self) -> int:
        return self.row_start.shape[1]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StreamedFoldPlan:
    """Static windowed reduction plan: one dispatch per round, one window
    of at most ``window_cap`` entries resident per grid step.

    With ``aligned_entry_vertex``/``aligned_entry_weights`` set (built by
    ``build_streamed_fold_plan(aligned=True)``), round 0's entry arrays are
    pre-materialized in the windowed layout: the driver gathers neighbor
    labels straight into window slots (one O(slots) gather from the label
    vector) and the round-0 kernel consumes them without the per-iteration
    windowed re-layout gather — the O(|E|) HBM round-trip the unaligned
    path pays every iteration (DESIGN.md §13).
    """

    rounds: Tuple[StreamedRound, ...]  # tuple[StreamedRound] — one windowed fold round each
    row_to_vertex: jnp.ndarray  # [last n_windows * tile_r] int32 — owning vertex (-1 pad)
    n_nodes: int   # int — vertex count N of the planned graph
    k: int         # int — sketch slots per row
    chunk: int     # int — entries per virtual-vertex row (paper D_H)
    # round-0 slot coordinates (BM fold / rescan second pass — see
    # FusedFoldPlan.row_to_vertex0):
    row_to_vertex0: Optional[jnp.ndarray] = None  # [round-0 n_windows * tile_r] int32
    row_rank0: Optional[jnp.ndarray] = None       # [round-0 n_windows * tile_r] int32
    max_rows0: int = 1  # int — max chunk rows any vertex owns on round 0
    # [round-0 n_windows * W] int32 — neighbor VERTEX id per round-0 window
    # slot, sentinel n_nodes on pad slots (None: unaligned layout). The
    # driver gathers labels_ext[aligned_entry_vertex] where labels_ext
    # appends one -1 slot, yielding windowed entry labels directly.
    aligned_entry_vertex: Optional[jnp.ndarray] = None
    # [round-0 n_windows * W] float32 — edge weight per round-0 window slot
    # (0.0 on pad slots; the fold's no-op weight). None: unaligned layout.
    aligned_entry_weights: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        return ((self.rounds, self.row_to_vertex, self.row_to_vertex0,
                 self.row_rank0, self.aligned_entry_vertex,
                 self.aligned_entry_weights),
                (self.n_nodes, self.k, self.chunk, self.max_rows0))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux[:3],
                   row_to_vertex0=children[2], row_rank0=children[3],
                   max_rows0=aux[3], aligned_entry_vertex=children[4],
                   aligned_entry_weights=children[5])

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)

    @property
    def aligned(self) -> bool:
        """True when round 0 carries the pre-materialized windowed layout."""
        return self.aligned_entry_vertex is not None


def _lane_align(n: int) -> int:
    return -(-n // _STREAM_ALIGN) * _STREAM_ALIGN


def _min_stream_stride(chunk: int) -> int:
    """Narrowest stride the streamed kernels take without padding: one
    row's ``chunk`` lane groups plus one (``streaming._stream_round``)."""
    return _STREAM_ALIGN * (-(-chunk // _STREAM_ALIGN) + 1)


def _window_firsts(cum: Sequence[int], chunk: int, tile_r: int,
                   window_cap: int) -> List[int]:
    """First row of each window of the greedy packing (``cum``: the rows'
    entry-count prefix sum, a memoryview so the walk reads Python ints)."""
    n_rows = len(cum) - 1
    firsts = []
    p = 0
    while p < n_rows:
        # the window ends before the first row q (p < q <= p + tile_r)
        # whose rel_start = cum[q] - cum[p] passes cap - chunk
        firsts.append(p)
        p = bisect.bisect_right(cum, cum[p] + window_cap - chunk, p + 1,
                                min(p + tile_r, n_rows))
    return firsts


def _window_need(cum: np.ndarray, firsts: np.ndarray, chunk: int) -> int:
    """A packing's widest ``rel_start + chunk`` (its last row's, in the
    window that reaches furthest)."""
    lasts = np.append(firsts[1:], len(cum) - 1) - 1
    return int((cum[lasts] - cum[firsts]).max()) + chunk


def _window_stride(need: int, chunk: int, window_cap: int) -> int:
    """Lane-aligned stride for a packing's ``need``, raised to the kernels'
    narrowest stride when the cap allows it (so they never pad a window at
    run time)."""
    floor = _min_stream_stride(chunk)
    w = _lane_align(need)
    return max(w, floor) if window_cap >= floor else w


def _pack_stream_windows(row_count: np.ndarray, chunk: int, tile_r: int,
                         window_cap: int) -> dict:
    """Greedily assign rows (kept in order) to slice-safe entry windows.

    Rows pack contiguously: row i's window-relative start is the sum of the
    counts of the rows before it in the same window. A window closes when it
    holds ``tile_r`` rows or when the next row's ``rel_start + chunk`` would
    exceed ``window_cap`` (so the kernel's full-chunk slice never crosses the
    window edge — "no row straddles a window unsafely"). ``window_cap`` is
    the packing cap :func:`_choose_stream_windows` picked for the round.

    Returns numpy arrays: ``win_of_row``/``rel_start``/``slot_of_row`` per
    row, plus ``n_windows`` and the lane-aligned ``window_entries`` stride
    actually needed (<= aligned ``window_cap``; >= ``chunk``, and >= the
    kernels' narrowest stride whenever the cap is).
    """
    if window_cap < chunk:
        raise ValueError(f"window_cap ({window_cap}) must be >= chunk "
                         f"({chunk}) for slice-safe rows")
    n_rows = len(row_count)
    if n_rows == 0:
        return {"win_of_row": np.zeros(0, np.int64),
                "rel_start": np.zeros(0, np.int64),
                "slot_of_row": np.zeros(0, np.int64),
                "n_windows": 1, "window_entries": _lane_align(chunk)}
    cum = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(row_count, out=cum[1:])
    firsts_arr = np.asarray(_window_firsts(memoryview(cum), chunk, tile_r,
                                           window_cap), dtype=np.int64)
    n_windows = len(firsts_arr)
    rows_per_win = np.diff(np.concatenate([firsts_arr, [n_rows]]))
    win_of_row = np.repeat(np.arange(n_windows, dtype=np.int64), rows_per_win)
    rel_start = cum[:-1] - cum[firsts_arr[win_of_row]]
    slot_of_row = win_of_row * tile_r + (np.arange(n_rows) -
                                         firsts_arr[win_of_row])
    return {"win_of_row": win_of_row, "rel_start": rel_start,
            "slot_of_row": slot_of_row, "n_windows": n_windows,
            "window_entries": _window_stride(
                _window_need(cum, firsts_arr, chunk), chunk, window_cap)}


def stream_round_cost(n_windows: int, window_entries: int,
                      gathers: int) -> int:
    """A streamed round's device cost in gathered-slot units: ``gathers``
    arrays gathered over every window slot, plus each window's kernel
    step at :data:`WINDOW_COST_SLOTS`."""
    return (gathers * n_windows * window_entries
            + WINDOW_COST_SLOTS * n_windows)


def _stream_cap_ladder(floor: int, top: int) -> List[int]:
    """Lane-aligned packing caps from ``floor`` to ``top``, each at most a
    quarter wider than the one before (every lane count to 8, then steps
    of 2, 4, 8, ... lanes)."""
    caps, lanes = [], floor // _STREAM_ALIGN
    while lanes * _STREAM_ALIGN <= top:
        caps.append(lanes * _STREAM_ALIGN)
        lanes += 1 << max(lanes.bit_length() - 3, 0)
    return caps


def _choose_stream_windows(row_count: np.ndarray, chunk: int, tile_r: int,
                           window_cap: int, gathers: int) -> dict:
    """Pack one round at the cap (<= ``window_cap``) of least
    :func:`stream_round_cost`.

    ``gathers`` is the number of arrays the round gathers per window slot:
    1 on a window-aligned round 0 (its weights are laid out at build
    time), 2 on a re-gathered round (labels and weights). Only where the
    windows are cut changes: every row folds the same entries in the same
    order at any cap, so the fold is bit-identical at every choice.

    The candidates are ``window_cap`` and a ladder of lane-aligned caps
    below it (:func:`_stream_cap_ladder`), down to the kernels' narrowest
    stride; a ``window_cap`` below that stride is kept as it is. Packing
    walks the windows in Python, so candidates are tried cheapest lower
    bound first and the search stops at the first bound that reaches the
    best cost found. The bound: at least ``max(rows / tile_r, entries /
    cap)`` windows and every real entry gathered; and below ``need`` (the
    widest window of tile_r rows), some window closes on the cap, so the
    stride exceeds ``cap - chunk``. At or above ``need`` every cap packs
    the same tile_r-row windows, which cost nothing to count.
    """
    floor = _min_stream_stride(chunk)
    n_rows = len(row_count)
    if window_cap < floor or n_rows == 0:
        return _pack_stream_windows(row_count, chunk, tile_r, window_cap)
    cum = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(row_count, out=cum[1:])
    real = int(cum[-1])
    by_rows = np.arange(0, n_rows, tile_r, dtype=np.int64)
    need = _window_need(cum, by_rows, chunk)
    if need <= window_cap:  # the tile_r-row packing, counted directly
        best_cap = window_cap
        best = stream_round_cost(
            len(by_rows), _window_stride(need, chunk, window_cap), gathers)
        caps = [c for c in _stream_cap_ladder(floor, window_cap) if c < need]
    else:
        best_cap = best = None
        caps = sorted(set(_stream_cap_ladder(floor, window_cap))
                      | {window_cap})

    def bound(cap):
        n_lb = max(len(by_rows), -(-real // cap))
        return (gathers * max(real, n_lb * _lane_align(cap - chunk + 1))
                + WINDOW_COST_SLOTS * n_lb)

    cum_view = memoryview(cum)
    for cap in sorted(caps, key=bound):
        if best is not None and bound(cap) >= best:
            break
        firsts = np.asarray(_window_firsts(cum_view, chunk, tile_r, cap),
                            dtype=np.int64)
        cost = stream_round_cost(len(firsts), _window_stride(
            _window_need(cum, firsts, chunk), chunk, cap), gathers)
        if best is None or cost < best or (cost == best and cap > best_cap):
            best, best_cap = cost, cap
    return _pack_stream_windows(row_count, chunk, tile_r, best_cap)


def _materialize_stream_round(row_vstart: np.ndarray, row_count: np.ndarray,
                              pack: dict, pos_table: np.ndarray | None,
                              tile_r: int) -> dict:
    """Build one round's device arrays from a window packing.

    ``row_vstart`` is each row's start in the round's *virtual* vertex-major
    entry space; ``pos_table`` (None on round 0) maps virtual positions to
    actual positions in the previous round's padded flattened output.
    Returns int32 numpy arrays: ``entry_gather`` [n_windows * W],
    ``row_start``/``row_count`` [n_windows, R], ``step_dmax`` [n_windows, 1].
    """
    n_rows = len(row_count)
    n_windows, w = pack["n_windows"], pack["window_entries"]
    gather = np.full(n_windows * w, -1, dtype=np.int64)
    if n_rows:
        cum = np.zeros(n_rows + 1, dtype=np.int64)
        np.cumsum(row_count, out=cum[1:])
        total = int(cum[-1])
        row_of_entry = np.repeat(np.arange(n_rows, dtype=np.int64), row_count)
        intra = np.arange(total, dtype=np.int64) - np.repeat(cum[:-1],
                                                             row_count)
        out_pos = (pack["win_of_row"][row_of_entry] * w
                   + pack["rel_start"][row_of_entry] + intra)
        src = row_vstart[row_of_entry] + intra
        if pos_table is not None:
            src = pos_table[src]
        gather[out_pos] = src
    rs = np.zeros((n_windows * tile_r,), dtype=np.int64)
    rc = np.zeros((n_windows * tile_r,), dtype=np.int64)
    rs[pack["slot_of_row"]] = pack["rel_start"]
    rc[pack["slot_of_row"]] = row_count
    rs = rs.reshape(n_windows, tile_r).astype(np.int32)
    rc = rc.reshape(n_windows, tile_r).astype(np.int32)
    return {"entry_gather": gather.astype(np.int32), "row_start": rs,
            "row_count": rc,
            "step_dmax": rc.max(axis=1, keepdims=True).astype(np.int32)}


def build_streamed_rounds(counts: np.ndarray, starts: np.ndarray,
                          n_entries: int, *, k: int, chunk: int, tile_r: int,
                          window_cap: int, min_rounds: int = 1,
                          aligned: bool = False
                          ) -> Tuple[List[dict], np.ndarray]:
    """Host-side core of the streamed plan (shared with the distributed
    workspace builder).

    ``counts``/``starts`` [N] give each vertex's entry range in the round-0
    source array of length ``n_entries`` (for the single-host plan: CSR
    degrees/offsets). Folds the identical per-row entry sequences as
    ``build_fused_fold_plan`` (same chunking, same ascending-count row
    sort), so per-vertex results are bit-identical to the reference; only
    the window re-layout differs. ``min_rounds`` forces extra merge rounds
    (the distributed builder pads all shards to a common round count).

    ``window_cap`` bounds every window (and so the kernels' VMEM
    residency); each round packs at the cap :func:`_choose_stream_windows`
    finds cheapest for its own rows, so the rounds' strides differ.
    ``aligned`` says round 0 will be stored window-aligned, which makes its
    windows cheaper to gather (one array per slot, not two).

    Returns (one numpy dict per round with the ``StreamedRound`` fields,
    final ``row_to_vertex`` [last n_windows * tile_r], -1 on pad slots).
    """
    counts = np.asarray(counts, dtype=np.int64).copy()
    starts = np.asarray(starts, dtype=np.int64).copy()
    n = len(counts)
    rounds: List[dict] = []
    pos_table: np.ndarray | None = None
    r = 0
    while True:
        order = np.argsort(counts, kind="stable")  # ascending entry count
        n_chunks = ((counts + chunk - 1) // chunk).astype(np.int64)
        nc_ord = n_chunks[order]
        total_rows = int(nc_ord.sum())
        row_vertex = np.repeat(order, nc_ord)
        row_rank = np.arange(total_rows, dtype=np.int64) - np.repeat(
            np.cumsum(nc_ord) - nc_ord, nc_ord)
        row_vstart = starts[row_vertex] + row_rank * chunk
        row_count = np.minimum(counts[row_vertex] - row_rank * chunk, chunk)
        pack = _choose_stream_windows(row_count, chunk, tile_r, window_cap,
                                      1 if aligned and r == 0 else 2)
        rnd = _materialize_stream_round(row_vstart, row_count, pack,
                                        pos_table, tile_r)
        rnd.update(n_entries_in=int(n_entries),
                   window_entries=pack["window_entries"])
        # slot -> (owning vertex, chunk rank) of this round's rows (-1/0 on
        # pad slots) — round 0's is what the BM fold and rescan reduce over
        slot_v = np.full(pack["n_windows"] * tile_r, -1, dtype=np.int64)
        slot_r = np.zeros(pack["n_windows"] * tile_r, dtype=np.int64)
        slot_v[pack["slot_of_row"]] = row_vertex
        slot_r[pack["slot_of_row"]] = row_rank
        rnd.update(row_to_vertex=slot_v.astype(np.int32),
                   row_rank=slot_r.astype(np.int32),
                   max_rows=max(int(n_chunks.max()) if len(n_chunks) else 0,
                                1))
        rounds.append(rnd)
        if np.all(n_chunks <= 1) and (r + 1) >= min_rounds:
            rtv = np.full(pack["n_windows"] * tile_r, -1, dtype=np.int64)
            rtv[pack["slot_of_row"]] = row_vertex
            return rounds, rtv.astype(np.int32)
        # Next round consumes each vertex's partial [k]-slot sketches in
        # (vertex, rank) order; pos_table maps that vertex-major virtual
        # space to the actual padded slots of this round's output.
        vm = np.lexsort((row_rank, row_vertex))
        slots_vm = pack["slot_of_row"][vm]
        pos_table = (slots_vm[:, None] * k
                     + np.arange(k, dtype=np.int64)).reshape(-1)
        counts = n_chunks * k
        starts = np.zeros(n, dtype=np.int64)
        starts[1:] = np.cumsum(counts)[:-1]
        n_entries = pack["n_windows"] * tile_r * k
        r += 1


def build_streamed_fold_plan(degrees: np.ndarray, k: int = 8,
                             chunk: int = 128, tile_r: int = 128,
                             window_entries: int = 8192, *,
                             indices: np.ndarray | None = None,
                             weights: np.ndarray | None = None,
                             aligned: bool = False) -> StreamedFoldPlan:
    """Construct the HBM-streaming windowed plan from the degree sequence.

    ``window_entries`` caps the entry slots per window (units: entries; the
    per-step VMEM residency is at most ~``2 * window_entries * 8`` bytes
    for the double-buffered label+weight window); each round's stride is
    chosen under it from the round's rows (``build_streamed_rounds``).
    Folds the identical entry sequences as ``build_fold_plan``/
    ``build_fused_fold_plan``, so per-vertex results are bit-identical;
    only the windowed layout and the per-window grid differ.

    ``aligned=True`` (requires the CSR ``indices``/``weights``) stores the
    round-0 entry arrays window-aligned at build time: the plan carries
    ``aligned_entry_vertex``/``aligned_entry_weights`` (windowed neighbor
    vertices + weights), round 0's ``entry_gather`` becomes the identity
    permutation over window slots (real slots -> themselves, pads -> -1)
    and its ``n_entries_in`` the window-slot count. Parity with the
    unaligned plan is structural: the arrays hold exactly what the
    unaligned path's per-iteration re-layout gather would produce, only
    materialized once. Later rounds consume prior rounds' padded outputs
    through their position tables and are unchanged.
    """
    degrees = np.asarray(degrees, dtype=np.int64)
    n = len(degrees)
    if chunk <= k:
        raise ValueError(f"chunk ({chunk}) must exceed sketch slots k ({k})")
    if aligned and (indices is None or weights is None):
        raise ValueError("aligned=True needs the CSR indices and weights to "
                         "pre-materialize the windowed round-0 entries")
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(degrees, out=offsets[1:])
    rounds_np, rtv = build_streamed_rounds(
        degrees, offsets[:-1], int(degrees.sum()), k=k, chunk=chunk,
        tile_r=tile_r, window_cap=window_entries, aligned=aligned)
    aev = aew = None
    rounds = []
    for ri, r in enumerate(rounds_np):
        eg, n_in, is_aligned = r["entry_gather"], r["n_entries_in"], False
        if aligned and ri == 0:
            idx = np.asarray(indices, dtype=np.int64)
            wgt = np.asarray(weights, dtype=np.float32)
            valid = eg >= 0
            safe = np.maximum(eg, 0)
            src_v = idx[safe] if idx.size else np.zeros_like(safe)
            src_w = wgt[safe] if wgt.size else np.zeros(safe.shape, np.float32)
            # pad slots: sentinel vertex n (the driver's appended -1 label
            # slot) and weight 0.0 — the fold's no-op entry, exactly what
            # windowed_entries would have produced at runtime
            aev = jnp.asarray(np.where(valid, src_v, n).astype(np.int32))
            aew = jnp.asarray(np.where(valid, src_w, 0.0).astype(np.float32))
            n_slots = eg.shape[0]
            eg = np.where(valid, np.arange(n_slots, dtype=np.int64),
                          -1).astype(np.int32)
            n_in, is_aligned = n_slots, True
        rounds.append(
            StreamedRound(entry_gather=jnp.asarray(eg),
                          row_start=jnp.asarray(r["row_start"]),
                          row_count=jnp.asarray(r["row_count"]),
                          step_dmax=jnp.asarray(r["step_dmax"]),
                          n_entries_in=int(n_in),
                          window_entries=r["window_entries"],
                          row_vertex=jnp.asarray(r["row_to_vertex"]),
                          aligned=is_aligned))
    return StreamedFoldPlan(rounds=tuple(rounds),
                            row_to_vertex=jnp.asarray(rtv),
                            n_nodes=n, k=k, chunk=chunk,
                            row_to_vertex0=jnp.asarray(
                                rounds_np[0]["row_to_vertex"]),
                            row_rank0=jnp.asarray(rounds_np[0]["row_rank"]),
                            max_rows0=rounds_np[0]["max_rows"],
                            aligned_entry_vertex=aev,
                            aligned_entry_weights=aew)


def streamed_dispatches(plan: StreamedFoldPlan) -> int:
    """Kernel dispatches per MG iteration: one per round (the final round's
    dispatch also performs candidate selection), same as the fused engine —
    the window grid lives *inside* each dispatch."""
    return plan.n_rounds


def streamed_window_slots(plan: StreamedFoldPlan) -> int:
    """Total windowed entry slots materialized per iteration across rounds
    (units: entries; the windowed re-layout's HBM footprint — pad slots
    included, unlike :func:`streamed_hbm_entries`)."""
    return sum(r.n_windows * r.window_entries for r in plan.rounds)


def streamed_gather_slots(plan: StreamedFoldPlan) -> int:
    """Windowed re-layout gather slots the streamed engine materializes
    PER ITERATION (units: entries). Aligned rounds are excluded: their
    windowed entries were materialized once at build time
    (``build_streamed_fold_plan(aligned=True)``), so the per-iteration
    re-layout gather — round 0's O(|E|) share of
    :func:`streamed_window_slots` — drops out. This is the declared gather
    count kernelcheck R6 ties to the ``aligned`` round flag."""
    return sum(r.n_windows * r.window_entries for r in plan.rounds
               if not r.aligned)


def streamed_hbm_entries(plan: StreamedFoldPlan) -> int:
    """Real entries the streamed fold reads per iteration (units: entries;
    equals :func:`fused_hbm_entries` of the fused plan — the window
    re-layout adds pad slots but no extra real entries)."""
    return int(sum(int(np.asarray(r.row_count).sum()) for r in plan.rounds))


def streamed_peak_window_bytes(plan: StreamedFoldPlan) -> int:
    """Peak per-step resident entry bytes of the streamed kernels: the
    double-buffered (label int32 + weight float32) window of the widest
    round — ``2 * W * 8`` bytes. This replaces the fused engine's full
    flat-entry residency (~``8 * n_entries_in`` bytes on round 0)."""
    if not plan.rounds:
        return 0
    return max(2 * r.window_entries * 8 for r in plan.rounds)


def fused_hbm_entries(plan: FusedFoldPlan) -> int:
    """Real entries the fused fold reads from HBM (padded lanes are generated
    in-register, so — unlike ``plan_padded_entries`` — pad slots cost no
    HBM traffic)."""
    return int(sum(int(np.asarray(r.row_count).sum()) for r in plan.rounds))


def fused_dispatches(plan: FusedFoldPlan) -> int:
    """Kernel dispatches per MG iteration: one per round (the final round's
    dispatch also performs candidate selection — see kernels.mg_sketch.fused)."""
    return plan.n_rounds


def plan_dispatches(plan: FoldPlan) -> int:
    """Kernel dispatches per MG iteration of the per-bucket Pallas backend:
    one pallas_call per width bucket per round."""
    return sum(len(r.buckets) for r in plan.rounds)


def plan_round0_dispatches(plan: FoldPlan) -> int:
    """Kernel dispatches of one round-0-only pass on the per-bucket Pallas
    backend (the BM fold and the rescan second scan both walk only round 0:
    one pallas_call per round-0 width bucket). The fused and streamed
    engines cover the same pass in ONE dispatch each (the window grid of
    the streamed BM/rescan kernels lives inside the dispatch)."""
    return len(plan.rounds[0].buckets) if plan.rounds else 0


# ---------------------------------------------------------------------------
# Sparse frontier compaction (DESIGN.md §8.5)
# ---------------------------------------------------------------------------
#
# The sparse frontier path compacts each round's *active* rows — rows whose
# owning vertex is on the frontier — into a fixed-capacity index buffer, so
# the fused/streamed kernels grid only over active rows while the jit
# contract stays static. Unfilled capacity slots hold a sentinel index one
# past the last real slot; the drivers append one neutral row (start 0,
# count 0, vertex -1) at that sentinel position, so padded gathers read
# all-empty rows that fold to empty sketches and scatter into a discarded
# dump slot. Whether a frontier *fits* the capacity is decided on the host
# between iterations (the frontier is concrete there) via the
# ``*_active_rows`` helpers below — overflow falls back to the dense gated
# mover, keeping both jitted movers free of traced control flow.


def compact_active_rows(active: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Compact the set lanes of ``active`` [rows] bool into a [cap] int32
    index buffer (traced; static output shape).

    Slot ``j`` holds the row index of the j-th active lane; slots past the
    number of active lanes hold the sentinel ``rows`` (one past the last
    real row — callers gather from sentinel-extended arrays). Active lanes
    beyond ``cap`` are dropped, so callers must pre-check the fit on the
    host (``fused_active_rows``/``streamed_active_windows``) before
    trusting the result.
    """
    rows = active.shape[0]
    idx = jnp.full((cap + 1,), jnp.int32(rows), dtype=jnp.int32)
    if rows == 0:
        return idx[:cap]
    pos = jnp.cumsum(active.astype(jnp.int32)) - 1
    # inactive lanes and overflow both land in the sliced-off dump slot cap
    slot = jnp.where(active & (pos < cap), pos, cap)
    return idx.at[slot].set(jnp.arange(rows, dtype=jnp.int32))[:cap]


def _round_active(row_vertex, frontier: np.ndarray) -> np.ndarray:
    """Per-row activity mask of one round (host side): real rows whose
    owning vertex is on the frontier."""
    rv = np.asarray(row_vertex).reshape(-1)
    active = np.zeros(rv.shape, dtype=bool)
    real = rv >= 0
    active[real] = np.asarray(frontier)[rv[real]]
    return active


def fused_active_rows(plan: FusedFoldPlan, frontier: np.ndarray) -> List[int]:
    """Per-round active fold-row counts of a concrete frontier (host side).

    The sparse fused mover fits a row capacity ``cap_rows`` iff every
    round's count here is <= ``cap_rows``.
    """
    return [int(np.count_nonzero(_round_active(r.row_vertex, frontier)))
            for r in plan.rounds]


def streamed_active_windows(plan: StreamedFoldPlan,
                            frontier: np.ndarray) -> List[Tuple[int, int]]:
    """Per-round ``(active_windows, rows_in_active_windows)`` of a concrete
    frontier (host side).

    The sparse streamed mover compacts at *window* granularity: a window is
    active when any of its rows is, and every row of an active window is
    folded (inactive rows there compute dense-identical values that the
    gate then masks). Each active window holds at least one active row, so
    ``active_windows <= active_rows`` — a row capacity that admits the
    fused path admits the streamed one too.
    """
    out = []
    for rnd in plan.rounds:
        active = _round_active(rnd.row_vertex, frontier)
        per_win = active.reshape(rnd.n_windows, rnd.tile_r)
        win_active = per_win.any(axis=1)
        real = (np.asarray(rnd.row_vertex).reshape(
            rnd.n_windows, rnd.tile_r) >= 0) & win_active[:, None]
        out.append((int(np.count_nonzero(win_active)),
                    int(np.count_nonzero(real))))
    return out


def fused_work_rows(plan: FusedFoldPlan) -> int:
    """Real fold rows one dense iteration computes (all rounds)."""
    return sum(int(np.count_nonzero(np.asarray(r.row_vertex) >= 0))
               for r in plan.rounds)


def streamed_work_rows(plan: StreamedFoldPlan) -> int:
    """Real fold rows one dense iteration computes (all rounds)."""
    return sum(int(np.count_nonzero(np.asarray(r.row_vertex) >= 0))
               for r in plan.rounds)
