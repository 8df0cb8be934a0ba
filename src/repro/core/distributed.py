"""Multi-device LPA: vertex-sharded shard_map with explicit label all-gather.

Distribution model (DESIGN.md §4):

  * vertices are split into P contiguous, edge-balanced ranges (optionally
    after a locality reorder from ``repro.graphs.partition``);
  * every shard owns its CSR rows, a single-width virtual-vertex fold plan
    (width = ``chunk``), and its slice of the label vector;
  * per iteration the only collective is one ``all_gather`` of the label
    vector (4·|V| bytes per device) — sketches, folds, selection and the
    Pick-Less/hash-tie move rule are entirely shard-local;
  * ΔN convergence uses a ``psum``.

Label *values* are real global vertex ids (so Pick-Less comparisons agree
across shards); label *positions* live in a padded global layout
[P · V_pad], which is what the all-gather produces and what the remapped
neighbor ids index into.

All per-shard arrays are padded to the max across shards so the stacked
[P, ...] pytree has uniform shapes — the price is pad lanes that fold to
empty sketches (weight 0 entries are no-ops by construction).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import obs
from repro.core import sketch as sketch_lib
from repro.core.fold_engine import resolve_auto
from repro.core.fold_program import FoldRequest
from repro.core.lpa import LPAResult
from repro.core.plan_bundle import (PlanSpec, ShardSlice, build_plan_bundle,
                                    spec_for, stack_aligned_windows,
                                    stack_shard_bundles,
                                    uniform_round_count)
from repro.launch.mesh import make_mesh

PAD = -1
INT_MAX = jnp.iinfo(jnp.int32).max


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DistLPAWorkspace:
    """Stacked per-shard arrays (leading axis P).

    Two label-exchange modes (EXPERIMENTS.md §Perf hillclimb — LPA):
      full gather (send_idx None): nbr_pos indexes the padded-global label
        layout produced by one all_gather of 4·|V| bytes per iteration.
      halo (send_idx set): nbr_pos indexes a LOCAL table [own labels ++
        halo slots]; per iteration each shard sends only the labels its
        peers actually reference (all_to_all of [P, H_pad]), cutting the
        exchanged bytes by the boundary fraction of the partition.
    """

    nbr_pos: jnp.ndarray       # [P, M_pad] int32 — label positions (see above)
    weights: jnp.ndarray       # [P, M_pad] float32
    # per round: [P, R_pad_r, chunk] int32 (R_pad_r, and R_pad_0 of the
    # bucketed round-0 maps below, 0 where the fold never reads them)
    round_gathers: Tuple[jnp.ndarray, ...]
    # [P, R_last] int32 — local vertex per final row (-1 pad; R_last 0 when
    # streamed)
    final_row_vertex: jnp.ndarray
    init_labels: jnp.ndarray   # [P, V_pad] int32 — real global ids (-1 on pad slots)
    n_nodes: int               # |V| — real (unpadded) global vertex count
    v_pad: int                 # per-shard label-slot count (max shard size)
    k: int                     # sketch width (candidate slots per vertex)
    chunk: int                 # fold-plan row width (entries per chunk row)
    send_idx: jnp.ndarray | None = None  # [P(owner), P(dest), H_pad] int32 local slots
    h_pad: int = 0             # halo-exchange pad width (slots per shard pair)
    hub_idx: jnp.ndarray | None = None   # [P, HUB_pad] int32 local slots of hubs
    hub_pad: int = 0           # hub all-gather pad width (hubs per shard)
    # fused-engine metadata (same (start, count) range encoding as
    # repro.graphs.csr.build_fused_fold_plan; rows in the gather row order):
    fused_starts: Tuple[jnp.ndarray, ...] | None = None  # per round [P, S_r, tile_r] int32
    fused_counts: Tuple[jnp.ndarray, ...] | None = None  # per round [P, S_r, tile_r] int32
    fused_dmax: Tuple[jnp.ndarray, ...] | None = None    # per round [P, S_r, 1] int32
    fused_entries: Tuple[int, ...] = ()  # per round: flat entry-array length
    # streaming-engine metadata (windowed layout per
    # repro.graphs.csr.build_streamed_rounds, padded across shards):
    stream_gathers: Tuple[jnp.ndarray, ...] | None = None  # per round [P, n_win_r, W_r] int32
    stream_starts: Tuple[jnp.ndarray, ...] | None = None   # per round [P, n_win_r, tile_r] int32
    stream_counts: Tuple[jnp.ndarray, ...] | None = None   # per round [P, n_win_r, tile_r] int32
    stream_dmax: Tuple[jnp.ndarray, ...] | None = None     # per round [P, n_win_r, 1] int32
    stream_final_rv: jnp.ndarray | None = None  # [P, n_win_last * tile_r] int32 local vertex (-1 pad)
    # round-0 row -> local vertex maps, one per plan encoding (the BM fold
    # and the rescan second pass walk only round 0; -1 on pad rows/slots):
    row_vertex0: jnp.ndarray | None = None  # [P, R_pad_0] int32 bucketed rows
    fused_rv0: jnp.ndarray | None = None    # [P, S_0 * tile_r] int32 fused rows
    stream_rv0: jnp.ndarray | None = None   # [P, n_win_0 * tile_r] int32 slots
    # round-0 row -> chunk-rank maps matching the rv0 maps above (0 on pad
    # rows; the rescan merge reduces each row's exact partial at its static
    # (vertex, rank) coordinate — sketch.merge_rescan_partials):
    bucket_rank0: jnp.ndarray | None = None  # [P, R_pad_0] int32 bucketed rows
    fused_rank0: jnp.ndarray | None = None   # [P, S_0 * tile_r] int32 fused rows
    stream_rank0: jnp.ndarray | None = None  # [P, n_win_0 * tile_r] int32 slots
    # static: max round-0 chunk rows any vertex owns (across shards) — the
    # rescan merge's rank-table depth
    max_rows0: int = 1
    # [P, M_pad] int32 — owning LOCAL vertex of each edge slot (-1 pads);
    # the gated step segment-maxes neighbor changed flags over it to mark
    # next iteration's per-shard frontier (dist_lpa_step(frontier_gate=))
    entry_vertex: jnp.ndarray | None = None
    # window-aligned round-0 entries (build_dist_workspace(aligned=True)):
    # label-table position / edge weight per round-0 window slot, the
    # shard-local analogue of StreamedFoldPlan.aligned_entry_vertex — the
    # streamed shard mover gathers labels straight into window order and
    # skips the per-iteration windowed re-layout gather on round 0. Built
    # AFTER the halo remap, so the positions index whichever label table
    # (padded-global or local+halo) the exchange mode produces.
    stream_aligned_pos: jnp.ndarray | None = None  # [P, n_win_0 * W] int32 (-1 pads)
    stream_aligned_w: jnp.ndarray | None = None    # [P, n_win_0 * W] float32 (0.0 pads)

    def tree_flatten(self):
        children = (self.nbr_pos, self.weights, self.round_gathers,
                    self.final_row_vertex, self.init_labels, self.send_idx,
                    self.hub_idx, self.fused_starts, self.fused_counts,
                    self.fused_dmax, self.stream_gathers, self.stream_starts,
                    self.stream_counts, self.stream_dmax,
                    self.stream_final_rv, self.row_vertex0, self.fused_rv0,
                    self.stream_rv0, self.entry_vertex,
                    self.stream_aligned_pos, self.stream_aligned_w,
                    self.bucket_rank0, self.fused_rank0, self.stream_rank0)
        return children, (self.n_nodes, self.v_pad, self.k, self.chunk,
                          self.h_pad, self.hub_pad, self.fused_entries,
                          self.max_rows0)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:5], *aux[:4], send_idx=children[5],
                   h_pad=aux[4], hub_idx=children[6], hub_pad=aux[5],
                   fused_starts=children[7], fused_counts=children[8],
                   fused_dmax=children[9], fused_entries=aux[6],
                   stream_gathers=children[10], stream_starts=children[11],
                   stream_counts=children[12], stream_dmax=children[13],
                   stream_final_rv=children[14], row_vertex0=children[15],
                   fused_rv0=children[16], stream_rv0=children[17],
                   entry_vertex=children[18],
                   stream_aligned_pos=children[19],
                   stream_aligned_w=children[20],
                   bucket_rank0=children[21], fused_rank0=children[22],
                   stream_rank0=children[23], max_rows0=aux[7])

    @property
    def n_shards(self) -> int:
        return self.nbr_pos.shape[0]


def _edge_balanced_ranges(degrees: np.ndarray, p: int) -> np.ndarray:
    """[P+1] vertex range boundaries with roughly equal edge counts."""
    cum = np.concatenate([[0], np.cumsum(degrees)])
    targets = np.linspace(0, cum[-1], p + 1)
    bounds = np.searchsorted(cum, targets[1:-1])
    return np.concatenate([[0], bounds, [len(degrees)]]).astype(np.int64)


def shard_entry_pad(degrees: np.ndarray, n_shards: int) -> int:
    """Directed edge slots of the widest of the ``n_shards`` edge-balanced
    shards: the padded entry length every shard's plans index."""
    if not len(degrees):
        return 1
    cum = np.concatenate([[0], np.cumsum(degrees)])
    return int(np.diff(cum[_edge_balanced_ranges(degrees, n_shards)]).max())


def build_dist_workspace(graph, n_shards: int, k: int = 8, chunk: int = 128,
                         order: np.ndarray | None = None,
                         halo: bool = False, fused: bool = False,
                         tile_r: int = 128, stream: bool = False,
                         window_entries: int = 8192,
                         aligned: bool = False,
                         sharding=None) -> DistLPAWorkspace:
    """Host-side construction of the stacked distributed workspace.

    ``order`` optionally renumbers vertices first (e.g. the LPA-community
    locality order from repro.graphs.partition) — new_id = order[old_id].
    ``halo=True`` builds the halo-exchange tables (see DistLPAWorkspace).
    ``fused=True`` additionally builds the (start, count) range metadata the
    ``pallas_fused`` engine folds from (dist_lpa_step(engine=...)).
    ``stream=True`` builds the per-shard windowed metadata for
    ``engine="pallas_stream"`` — each shard folds through entry windows of
    at most ``window_entries`` entries (padded uniformly across shards, so
    the stacked [P, ...] pytree keeps static shapes).
    ``aligned=True`` (requires ``stream=True``) additionally stores each
    shard's round-0 entry metadata window-aligned
    (``stream_aligned_pos``/``stream_aligned_w``): the streamed shard mover
    then gathers labels straight into window order and skips the
    per-iteration round-0 re-layout gather, bit-identically — the
    distributed analogue of ``LPAConfig(aligned_layout=True)``.
    ``sharding`` places every stacked array straight from the host (shard
    p of each [P, ...] array on mesh device p), so no device ever holds
    all shards; None puts them on the default device.
    """
    if aligned and not stream:
        raise ValueError("aligned=True requires stream=True (the aligned "
                         "layout is a property of the windowed plan)")
    offsets = np.asarray(graph.offsets, dtype=np.int64)
    indices = np.asarray(graph.indices, dtype=np.int64)
    weights = np.asarray(graph.weights, dtype=np.float32)
    n = graph.n_nodes
    if order is not None:
        inv = np.empty(n, dtype=np.int64)
        inv[order] = np.arange(n)
        # rebuild CSR under the new numbering
        degrees_old = offsets[1:] - offsets[:-1]
        new_deg = degrees_old[inv]
        new_off = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(new_deg, out=new_off[1:])
        new_idx = np.empty_like(indices)
        new_wgt = np.empty_like(weights)
        for v_new in range(n):  # pragma: no cover - exercised via partition tests
            v_old = inv[v_new]
            s, e = offsets[v_old], offsets[v_old + 1]
            ns = new_off[v_new]
            new_idx[ns:ns + e - s] = order[indices[s:e]]
            new_wgt[ns:ns + e - s] = weights[s:e]
        offsets, indices, weights = new_off, new_idx, new_wgt

    degrees = offsets[1:] - offsets[:-1]
    bounds = _edge_balanced_ranges(degrees, n_shards)
    v_pad = int(np.max(bounds[1:] - bounds[:-1])) if n else 1
    # map global vertex id -> padded-global position p * v_pad + local slot
    shard_of = np.repeat(np.arange(n_shards), bounds[1:] - bounds[:-1])
    local_slot = np.arange(n) - bounds[shard_of]
    padded_pos = shard_of * v_pad + local_slot

    m_pad = shard_entry_pad(degrees, n_shards)

    # ONE declarative plan build per shard (DESIGN.md §15): the spec names
    # the fold backend the caller's requests will run on, and every
    # stacked per-engine plan array comes out of stack_shard_bundles —
    # nothing is hand-assembled here anymore.
    if fused and stream:
        raise ValueError("fused=True and stream=True are mutually "
                         "exclusive (one fold backend per workspace)")
    backend = ("pallas_stream" if stream
               else "pallas_fused" if fused else "jnp")
    spec = PlanSpec(backend=backend, k=k, chunk=chunk, tile_r=tile_r,
                    aligned=aligned, stream_window=window_entries)
    shard_counts = [degrees[bounds[p]:bounds[p + 1]]
                    for p in range(n_shards)]
    n_rounds = uniform_round_count(shard_counts, k=k, chunk=chunk)
    bundles = [build_plan_bundle(
        ShardSlice(counts=c, n_entries=m_pad, n_rounds=n_rounds), spec)
        for c in shard_counts]
    plans = stack_shard_bundles(bundles)

    nbr_pos = np.full((n_shards, m_pad), PAD, dtype=np.int32)
    wgts = np.zeros((n_shards, m_pad), dtype=np.float32)
    entry_vertex = np.full((n_shards, m_pad), PAD, dtype=np.int32)
    init_labels = np.full((n_shards, v_pad), PAD, dtype=np.int32)
    for p in range(n_shards):
        lo, hi = bounds[p], bounds[p + 1]
        e0, e1 = offsets[lo], offsets[hi]
        nbr_pos[p, :e1 - e0] = padded_pos[indices[e0:e1]]
        wgts[p, :e1 - e0] = weights[e0:e1]
        entry_vertex[p, :e1 - e0] = np.repeat(
            np.arange(hi - lo, dtype=np.int64), degrees[lo:hi])
        init_labels[p, :hi - lo] = np.arange(lo, hi)

    send_idx = hub_idx_arr = None
    h_pad = hub_pad = 0
    if halo:
        # reference count: how many shards' edge lists touch each vertex
        ref = np.zeros(n, dtype=np.int32)
        needs = []
        for p in range(n_shards):
            lo, hi = bounds[p], bounds[p + 1]
            idx_p = indices[offsets[lo]:offsets[hi]]
            owners = shard_of[idx_p]
            remote = np.unique(idx_p[owners != p])
            ref[remote] += 1
            needs.append(remote)
        # hubs (referenced by >= P/4 shards) go through a small all-gather;
        # per-pair a2a padding would otherwise be dominated by them
        hub_min = max(3, n_shards // 2)
        is_hub = ref >= hub_min
        hub_pad = max(int(np.bincount(shard_of[is_hub],
                                      minlength=n_shards).max())
                      if is_hub.any() else 0, 1)
        hub_idx_arr = np.full((n_shards, hub_pad), PAD, dtype=np.int32)
        hub_rank = np.full(n, -1, dtype=np.int64)
        for p in range(n_shards):
            hubs_p = np.nonzero(is_hub & (shard_of == p))[0]
            hub_idx_arr[p, :len(hubs_p)] = local_slot[hubs_p]
            hub_rank[hubs_p] = np.arange(len(hubs_p))
        # need[p][q] = sorted q-local slots (non-hub) shard p references
        need = [[np.zeros(0, np.int64)] * n_shards for _ in range(n_shards)]
        for p in range(n_shards):
            remote = needs[p]
            remote = remote[~is_hub[remote]]
            owners = shard_of[remote]
            for q in np.unique(owners):
                need[p][q] = np.sort(local_slot[remote[owners == q]])
        h_pad = max((len(need[p][q]) for p in range(n_shards)
                     for q in range(n_shards)), default=0)
        h_pad = max(int(h_pad), 1)
        send_idx = np.full((n_shards, n_shards, h_pad), PAD, dtype=np.int32)
        for p in range(n_shards):
            for q in range(n_shards):
                if len(need[p][q]):
                    send_idx[q, p, :len(need[p][q])] = need[p][q]
        # remap nbr_pos to the local table
        # [v_pad own ++ P*hub_pad hubs ++ P*h_pad halo]
        hub_base = v_pad
        halo_base = v_pad + n_shards * hub_pad
        for p in range(n_shards):
            lo, hi = bounds[p], bounds[p + 1]
            e0, e1 = offsets[lo], offsets[hi]
            idx_p = indices[e0:e1]
            owners = shard_of[idx_p]
            pos = np.empty(e1 - e0, dtype=np.int32)
            own = owners == p
            pos[own] = local_slot[idx_p[own]]
            hub_sel = is_hub[idx_p] & ~own
            pos[hub_sel] = (hub_base + owners[hub_sel] * hub_pad
                            + hub_rank[idx_p[hub_sel]])
            for q in range(n_shards):
                if q == p or not len(need[p][q]):
                    continue
                sel = (owners == q) & ~is_hub[idx_p] & ~own
                rank = np.searchsorted(need[p][q], local_slot[idx_p[sel]])
                pos[sel] = halo_base + q * h_pad + rank
            nbr_pos[p, :e1 - e0] = pos

    stream_apos = stream_aw = None
    if stream and aligned:
        # Each shard bundle's remap_labels transform, applied AFTER the
        # halo remap above so the stored positions index the exchange
        # mode's actual label table (padded-global or local+halo).
        stream_apos, stream_aw = stack_aligned_windows(bundles, nbr_pos,
                                                       wgts)

    round_gathers = plans.round_gathers
    final_row_vertex = plans.final_row_vertex
    row_vertex0, bucket_rank0 = plans.row_vertex0, plans.bucket_rank0
    if fused or stream:
        # the fused and streamed folds never read the bucketed round
        # gathers, and the streamed one none of the bucketed row maps
        # either: empty [P, 0, ...] stand-ins keep the step's operands
        # without placing what no fold reads (at Graph500 scale 21 they
        # were about 0.7 GB a chip)
        round_gathers = tuple(g[:, :0] for g in round_gathers)
        if stream:
            final_row_vertex, row_vertex0, bucket_rank0 = (
                a[:, :0] for a in (final_row_vertex, row_vertex0,
                                   bucket_rank0))
    ws = DistLPAWorkspace(
        nbr_pos=nbr_pos, weights=wgts,
        round_gathers=round_gathers,
        final_row_vertex=final_row_vertex,
        init_labels=init_labels,
        n_nodes=int(n), v_pad=int(v_pad), k=int(k), chunk=int(chunk),
        send_idx=send_idx, h_pad=int(h_pad), hub_idx=hub_idx_arr,
        hub_pad=int(hub_pad),
        fused_starts=plans.fused_starts, fused_counts=plans.fused_counts,
        fused_dmax=plans.fused_dmax, fused_entries=plans.fused_entries,
        stream_gathers=plans.stream_gathers,
        stream_starts=plans.stream_starts,
        stream_counts=plans.stream_counts, stream_dmax=plans.stream_dmax,
        stream_final_rv=plans.stream_final_rv,
        row_vertex0=row_vertex0, fused_rv0=plans.fused_rv0,
        stream_rv0=plans.stream_rv0, entry_vertex=entry_vertex,
        stream_aligned_pos=stream_apos, stream_aligned_w=stream_aw,
        bucket_rank0=bucket_rank0, fused_rank0=plans.fused_rank0,
        stream_rank0=plans.stream_rank0, max_rows0=plans.max_rows0)
    if sharding is None:
        return jax.tree_util.tree_map(jnp.asarray, ws)
    return jax.device_put(ws, sharding)


def _shard_move(nbr_pos, edge_w, round_gathers, final_row_vertex, labels,
                pick_less, seed, *, k, v_pad, axis_names, fold_tile,
                request, send_idx=None, hub_idx=None, fused_meta=None,
                fused_entries=(), chunk=0, stream_meta=None,
                stream_frv=None, rv0=None, rank0=None, max_rows0=1,
                frontier=None, entry_vertex=None, stream_apos=None,
                stream_aw=None):
    """Per-shard body of one distributed LPA iteration (runs inside shard_map).

    Shapes here are the *local* block shapes (leading P axis stripped).
    ``fused_meta`` (per round (starts, counts, dmax) blocks) switches the
    fold to the fused single-dispatch kernel — engine="pallas_fused".
    ``stream_meta`` (per round (gather, starts, counts, dmax) windowed
    blocks) + ``stream_frv`` (final row slot -> local vertex) switch it to
    the HBM-streaming windowed kernel — engine="pallas_stream".

    ``request`` (a static :class:`FoldRequest`, closed over by the step —
    never a shard_map operand) routes the sketch uniformly with
    ``FoldEngine.run``: ``family="bm"`` runs the Boyer-Moore sketch
    instead of MG — only round 0 is folded (one fused/streamed dispatch,
    or the bucketed tile fold), per-row partial states merge shard-locally
    with the max-reduce of ``sketch.bm_merge_rows`` — and ``rescan=True``
    re-scores the MG candidates exactly against round 0 (paper §4.4)
    before selecting. Both need ``rv0`` (the engine's round-0 row -> local
    vertex map); the rescan additionally reduces its partials at the
    static (vertex, ``rank0``) coordinates through a ``max_rows0``-deep
    rank table — every vertex's rows live on its own shard, so neither
    costs an extra collective.

    ``frontier`` ([1, V_pad] bool, with ``entry_vertex`` [1, M_pad]) turns
    on dense frontier gating (the distributed analogue of
    ``LPAConfig.frontier_gate``): off-frontier moves are masked and the
    step emits a third output — next iteration's marked frontier, built by
    exchanging this iteration's changed flags through the SAME halo/gather
    machinery as the labels and segment-maxing them over each shard's own
    edge slots. One extra collective per gated iteration.

    ``stream_apos``/``stream_aw`` ([1, n_win_0 * W] window-aligned label
    positions / weights) switch the streamed round-0 fold to the aligned
    layout: labels gather straight into window order and the round-0
    ``StreamedRound`` carries ``aligned=True``, so the kernel skips the
    windowed re-layout gather (later rounds are unchanged — they consume
    the previous round's padded window-slot outputs).
    """
    nbr_pos = nbr_pos[0]          # [M_pad]
    edge_w = edge_w[0]
    round_gathers = [g[0] for g in round_gathers]
    final_row_vertex = final_row_vertex[0]
    labels = labels[0]            # [V_pad]

    @jax.named_scope("lpa.exchange")
    def exchange(vec, fill):
        """Local [V_pad] vector -> table the nbr_pos positions index."""
        if send_idx is None:
            # THE collective: one all-gather per exchanged vector.
            return jax.lax.all_gather(vec, axis_names, tiled=True)
        # hub values: small all-gather (vertices referenced by many shards)
        hidx = hub_idx[0]         # [HUB_pad]
        hub_buf = jnp.where(hidx >= 0, vec[jnp.maximum(hidx, 0)], fill)
        hub_all = jax.lax.all_gather(hub_buf, axis_names,
                                     tiled=False).reshape(-1)
        # halo exchange: send each peer exactly the values it references.
        sidx = send_idx[0]        # [P, H_pad]
        buf = jnp.where(sidx >= 0, vec[jnp.maximum(sidx, 0)], fill)
        recv = jax.lax.all_to_all(buf, axis_names, split_axis=0,
                                  concat_axis=0, tiled=True)  # [P, H_pad]
        return jnp.concatenate([vec, hub_all, recv.reshape(-1)])

    label_table = exchange(labels, -1)
    safe = jnp.maximum(nbr_pos, 0)
    with jax.named_scope("lpa.gather"):
        entry_labels = jnp.where(nbr_pos >= 0, label_table[safe], -1)
        entry_weights = jnp.where(nbr_pos >= 0, edge_w, 0.0)
    # the fold loops below consume these in place round by round; the
    # rescan second pass re-reads round 0, so keep the originals
    entry_labels0, entry_weights0 = entry_labels, entry_weights

    def aligned_window_labels():
        """Aligned round-0 entries: gather the label table straight into
        window-slot order (pad slots -> label -1, weight 0.0 — exactly what
        the unaligned path's re-layout gather would produce)."""
        sap = stream_apos[0]
        wl = jnp.where(sap >= 0, label_table[jnp.maximum(sap, 0)], -1)
        return wl, stream_aw[0]

    def finish(want):
        fr = None if frontier is None else frontier[0]
        new_labels, changed, delta = _move_epilogue(want, labels, pick_less,
                                                    axis_names, frontier=fr)
        if fr is None:
            return new_labels[None], delta
        # mark next iteration's frontier: a vertex is queued iff any of its
        # neighbors changed — the shard-local segment-max over its own edge
        # slots, fed by one changed-flag exchange (paper Alg. 1 l. 31)
        changed_table = exchange(changed.astype(jnp.int32), 0)
        ent = jnp.where(nbr_pos >= 0, changed_table[safe], 0)
        ev = entry_vertex[0]
        tgt = jnp.where(ev >= 0, ev, v_pad)
        marked = jnp.zeros((v_pad + 1,),
                           jnp.int32).at[tgt].max(ent)[:v_pad] > 0
        return new_labels[None], delta, marked[None]

    if request.family == "bm":
        rv0_l = rv0[0]
        # init + merge go through the same sketch helpers as the
        # single-host engines (fused.run_bm_plan_generic) — only the
        # engine-specific fold call differs per branch below
        init = sketch_lib.bm_init_rows(rv0_l, labels)
        if stream_meta is not None:
            from repro.graphs.csr import StreamedRound
            from repro.kernels.mg_sketch.streaming import bm_fold_round_stream
            g, rs, rc, dm = stream_meta[0]
            el0, ew0 = entry_labels, entry_weights
            if stream_apos is not None:  # window-aligned round 0
                el0, ew0 = aligned_window_labels()
            rnd = StreamedRound(entry_gather=g[0].reshape(-1),
                                row_start=rs[0], row_count=rc[0],
                                step_dmax=dm[0], n_entries_in=0,
                                window_entries=g.shape[-1],
                                aligned=stream_apos is not None)
            ck, wk = bm_fold_round_stream(rnd, el0, ew0, init, chunk=chunk)
        elif fused_meta is not None:
            from repro.graphs.csr import FusedRound
            from repro.kernels.mg_sketch.fused import bm_fold_round_fused
            rs, rc, dm = fused_meta[0]
            rnd = FusedRound(row_start=rs[0], row_count=rc[0],
                             step_dmax=dm[0],
                             n_entries_in=fused_entries[0])
            ck, wk = bm_fold_round_fused(rnd, entry_labels, entry_weights,
                                         init, chunk=chunk)
        else:
            gl, gw = sketch_lib._gather_entries(round_gathers[0],
                                                entry_labels, entry_weights)
            ck, wk = fold_tile(gl, gw, init)
        best_c, _ = sketch_lib.bm_merge_rows(v_pad, labels, rv0_l, ck, wk)
        want = jnp.where(best_c >= 0, best_c, labels)
        return finish(want)

    if stream_meta is not None:
        # streaming engine: one dispatch per round, one window of entries
        # resident per grid step (the shard-local analogue of
        # kernels.mg_sketch.streaming.run_mg_plan_stream)
        from repro.graphs.csr import StreamedRound
        from repro.kernels.mg_sketch.streaming import stream_fold_round
        for r, (g, rs, rc, dm) in enumerate(stream_meta):
            el, ew = entry_labels, entry_weights
            is_aligned = r == 0 and stream_apos is not None
            if is_aligned:  # window-aligned round 0: skip the re-layout
                el, ew = aligned_window_labels()
            rnd = StreamedRound(entry_gather=g[0].reshape(-1),
                                row_start=rs[0], row_count=rc[0],
                                step_dmax=dm[0], n_entries_in=0,
                                window_entries=g.shape[-1],
                                aligned=is_aligned)
            s_k, s_v = stream_fold_round(rnd, el, ew, k=k, chunk=chunk,
                                         index=r)
            entry_labels, entry_weights = s_k.reshape(-1), s_v.reshape(-1)
        # window-slot row order: scatter below via the streaming slot map
        final_row_vertex = stream_frv[0]
    elif fused_meta is not None:
        # fused engine: one dispatch per round, gather inside the kernel
        from repro.graphs.csr import FusedRound
        from repro.kernels.mg_sketch.fused import fused_fold_round
        for r, (rs, rc, dm) in enumerate(fused_meta):
            rnd = FusedRound(row_start=rs[0], row_count=rc[0],
                             step_dmax=dm[0],
                             n_entries_in=fused_entries[r])
            s_k, s_v = fused_fold_round(rnd, entry_labels, entry_weights,
                                        k=k, chunk=chunk, index=r)
            entry_labels, entry_weights = s_k.reshape(-1), s_v.reshape(-1)
        s_k = s_k[:final_row_vertex.shape[0]]  # drop tile-padding rows
        s_v = s_v[:final_row_vertex.shape[0]]
    else:
        for gather in round_gathers:
            gl, gw = sketch_lib._gather_entries(gather, entry_labels,
                                                entry_weights)
            s_k, s_v = fold_tile(gl, gw, k)
            entry_labels, entry_weights = s_k.reshape(-1), s_v.reshape(-1)

    # scatter final sketches to local vertices (+1 dump slot for pad rows)
    dump = v_pad
    row_v = jnp.where(final_row_vertex >= 0, final_row_vertex, dump)
    cand_c = jnp.full((v_pad + 1, k), -1, jnp.int32).at[row_v].set(s_k)[:v_pad]

    if request.rescan:
        # double-scan second pass (paper §4.4): re-score the consolidated
        # candidates *exactly* against round 0 — one in-engine dispatch on
        # the fused/streamed engines, the shared sequential partials on
        # the bucketed tile path. Candidates stay UNMASKED here (a
        # decimated zero-weight slot can win on its exact weight; same
        # convention as fused.rescan_select_generic), and the merge +
        # selection reduce through the same sketch helpers in the same
        # order, so the per-vertex result is bit-identical to the
        # single-host rescan.
        rv0_l, rank0_l = rv0[0], rank0[0]
        cand_ext = jnp.concatenate([cand_c,
                                    jnp.full((1, k), -1, jnp.int32)])
        cand_rows = cand_ext[jnp.where(rv0_l >= 0, rv0_l, v_pad)]
        if stream_meta is not None:
            from repro.graphs.csr import StreamedRound
            from repro.kernels.mg_sketch.streaming import rescan_round_stream
            g, rs, rc, dm = stream_meta[0]
            el0, ew0 = entry_labels0, entry_weights0
            is_aligned = stream_apos is not None
            if is_aligned:  # window-aligned round 0: skip the re-layout
                el0, ew0 = aligned_window_labels()
            rnd0 = StreamedRound(entry_gather=g[0].reshape(-1),
                                 row_start=rs[0], row_count=rc[0],
                                 step_dmax=dm[0], n_entries_in=0,
                                 window_entries=g.shape[-1],
                                 aligned=is_aligned)
            parts = rescan_round_stream(rnd0, el0, ew0, cand_rows, k=k,
                                        chunk=chunk)
        elif fused_meta is not None:
            from repro.graphs.csr import FusedRound
            from repro.kernels.mg_sketch.fused import rescan_round_fused
            rs, rc, dm = fused_meta[0]
            rnd0 = FusedRound(row_start=rs[0], row_count=rc[0],
                              step_dmax=dm[0],
                              n_entries_in=fused_entries[0])
            parts = rescan_round_fused(rnd0, entry_labels0, entry_weights0,
                                       cand_rows, k=k, chunk=chunk)
        else:
            gl0, gw0 = sketch_lib._gather_entries(round_gathers[0],
                                                  entry_labels0,
                                                  entry_weights0)
            parts = sketch_lib.rescan_row_partials(gl0, gw0, cand_rows)
        acc = sketch_lib.merge_rescan_partials(v_pad, k, max_rows0, rv0_l,
                                               rank0_l, parts)
        want = sketch_lib.choose_from_candidates(
            jnp.where(acc > 0, cand_c, -1), acc, labels, seed)
        return finish(want)

    cand_w = jnp.zeros((v_pad + 1, k), jnp.float32).at[row_v].set(s_v)[:v_pad]
    cand_c = jnp.where(cand_w > 0, cand_c, -1)

    want = sketch_lib.choose_from_candidates(cand_c, cand_w, labels, seed)
    return finish(want)


def _move_epilogue(want, labels, pick_less, axis_names, frontier=None):
    """Shared per-shard move rule: apply the Pick-Less/changed gating to
    the wanted labels (pad slots excluded) and psum the global ΔN. One
    copy for every method — the MG and BM paths must never drift.
    ``frontier`` ([V_pad] bool) additionally masks off-frontier moves."""
    with jax.named_scope("lpa.apply"):
        allowed = jnp.where(pick_less, want < labels, want != labels)
        if frontier is not None:
            allowed = allowed & frontier
        is_real = labels >= 0
        new_labels = jnp.where(allowed & is_real, want, labels)
        changed = (new_labels != labels) & is_real
        moved = jnp.sum(changed.astype(jnp.int32))
    with jax.named_scope("lpa.exchange"):
        delta = jax.lax.psum(moved, axis_names)
    return new_labels, changed, delta


def dist_lpa_step(mesh, ws: DistLPAWorkspace, *, axis_names=None,
                  fold_tile=None, engine: str | None = None,
                  method: str = "mg", rescan: bool = False,
                  frontier_gate: bool = False):
    """Build the shard_map'd single-iteration function for ``mesh``.

    Returns step(nbr_pos, weights, round_gathers, final_row_vertex,
    labels [P, V_pad], pick_less, seed, *, ws_arrays=None) -> (labels,
    delta_n). The caller jits it (dryrun lowers it). ``ws_arrays`` is the
    workspace to read every other array from (default: the closed-over
    ``ws``); a jitted caller passes it so the arrays stay arguments.

    ``engine`` selects the fold backend uniformly with the single-host
    driver ("jnp" | "pallas" | "pallas_fused" | "pallas_stream" — see
    repro.core.fold_engine); "pallas_fused" needs a workspace built with
    ``fused=True``, "pallas_stream" one built with ``stream=True``. An
    explicit ``fold_tile`` overrides the engine's tile fold.

    ``method``/``rescan`` select the sketch family uniformly with the
    single-host driver — they build the same static ``FoldRequest``
    routing key ``lpa_move`` does (``family`` "mg" | "bm", ``rescan``
    the MG double-scan ablation, DESIGN.md §14), and ``_shard_move``
    routes by it; every combo runs on every engine (halo or full-gather
    label exchange is orthogonal).

    ``frontier_gate=True`` builds the dense-gated step: it takes an extra
    trailing ``frontier`` argument ([P, V_pad] bool) and returns
    (labels, delta_n, marked) — ``marked`` is next iteration's per-shard
    frontier (``dist_lpa`` keeps Pick-Less iterations' deferred vertices
    queued by unioning, mirroring the single-host §8.5 rule).
    """
    axis_names = tuple(mesh.axis_names) if axis_names is None else axis_names
    if method not in ("mg", "bm"):
        raise ValueError(f"unknown method {method!r}; expected 'mg' | 'bm'")
    # the request is pure static routing state here (seed/frontier stay
    # ordinary shard_map operands); construction validates the combo
    request = FoldRequest(family=method, rescan=rescan)
    if frontier_gate and ws.entry_vertex is None:
        raise ValueError("frontier_gate=True requires a workspace with "
                         "entry_vertex (rebuild via build_dist_workspace)")
    fused = engine == "pallas_fused"
    stream = engine == "pallas_stream"
    if engine is not None and not (fused or stream) and fold_tile is None:
        from repro.core.fold_engine import get_engine
        # checked=False: the tile folds run inside the shard_mapped step,
        # where the checkify contract proxy's eager throw cannot trace
        eng = get_engine(engine, checked=False)
        fold_tile = eng.bm_fold_tile if method == "bm" else eng.mg_fold_tile
    fold_tile = fold_tile or (sketch_lib.bm_fold_tile if method == "bm"
                              else sketch_lib.mg_fold_tile)
    if fused and ws.fused_starts is None:
        raise ValueError("engine='pallas_fused' requires "
                         "build_dist_workspace(..., fused=True)")
    if stream and ws.stream_gathers is None:
        raise ValueError("engine='pallas_stream' requires "
                         "build_dist_workspace(..., stream=True)")
    if rescan and (ws.stream_rank0 is None if stream else
                   ws.fused_rank0 is None if fused else
                   ws.bucket_rank0 is None):
        raise ValueError("rescan=True needs the workspace's round-0 rank "
                         "metadata (rebuild via build_dist_workspace)")
    spec = P(axis_names)
    n_rounds = len(ws.round_gathers)
    halo = ws.send_idx is not None

    def engine_operands(w):
        """(``_shard_move`` keyword, stacked array, in_spec) of every
        engine-specific workspace array the step reads from ``w``."""
        ops = []
        if fused:
            ops.append(("fused_meta",
                        tuple(zip(w.fused_starts, w.fused_counts,
                                  w.fused_dmax)),
                        tuple([(spec, spec, spec)] * n_rounds)))
        if stream:
            ops.append(("stream_meta",
                        tuple(zip(w.stream_gathers, w.stream_starts,
                                  w.stream_counts, w.stream_dmax)),
                        tuple([(spec, spec, spec, spec)] * n_rounds)))
            ops.append(("stream_frv", w.stream_final_rv, spec))
            if w.stream_aligned_pos is not None:
                ops += [("stream_apos", w.stream_aligned_pos, spec),
                        ("stream_aw", w.stream_aligned_w, spec)]
        if method == "bm" or rescan:
            ops.append(("rv0", w.stream_rv0 if stream
                        else w.fused_rv0 if fused else w.row_vertex0, spec))
        if rescan:
            ops.append(("rank0", w.stream_rank0 if stream
                        else w.fused_rank0 if fused else w.bucket_rank0,
                        spec))
        if frontier_gate:
            ops.append(("entry_vertex", w.entry_vertex, spec))
        return ops

    def step(nbr_pos, edge_w, round_gathers, final_row_vertex, labels,
             pick_less, seed, send_idx=None, hub_idx=None, frontier=None,
             ws_arrays=None):
        in_specs = [spec, spec, tuple([spec] * n_rounds), spec, spec,
                    P(), P()]
        args = [nbr_pos, edge_w, round_gathers, final_row_vertex, labels,
                pick_less, seed]
        kw = {"k": ws.k, "v_pad": ws.v_pad, "axis_names": axis_names,
              "fold_tile": fold_tile, "request": request}
        if fused:
            kw.update(fused_entries=ws.fused_entries, chunk=ws.chunk)
        if stream:
            kw.update(chunk=ws.chunk)
        if rescan:
            kw["max_rows0"] = ws.max_rows0
        extra_names = []
        if send_idx is not None:
            in_specs += [spec, spec]
            args += [send_idx, hub_idx]
            extra_names += ["send_idx", "hub_idx"]
        if frontier_gate:
            in_specs.append(spec)
            args.append(frontier)
            extra_names.append("frontier")
        # the engine's arrays come from ``ws_arrays`` when the caller hands
        # the workspace in as a jit argument, else from the closed-over
        # ``ws`` (jit would then embed them in the program as constants)
        for name, value, sp in engine_operands(
                ws if ws_arrays is None else ws_arrays):
            in_specs.append(sp)
            args.append(value)
            extra_names.append(name)

        def body(*a):
            return _shard_move(*a[:7], **dict(zip(extra_names, a[7:])),
                               **kw)

        out_specs = (spec, P(), spec) if frontier_gate else (spec, P())
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=out_specs,
            check_vma=False,
        )(*args)

    if halo:
        def halo_step(*a, frontier=None, ws_arrays=None):
            w = ws if ws_arrays is None else ws_arrays
            return step(*a[:7],
                        send_idx=a[7] if len(a) > 7 else w.send_idx,
                        hub_idx=a[8] if len(a) > 8 else w.hub_idx,
                        frontier=frontier, ws_arrays=ws_arrays)
        return halo_step
    return step


def dist_lpa(mesh, ws: DistLPAWorkspace, rho: int = 8, tau: float = 0.05,
             max_iters: int = 20, engine: str | None = None,
             method: str = "mg", rescan: bool = False,
             frontier_gate: bool = False):
    """Run distributed LPA to convergence. Returns (labels [N], iterations).

    ``method`` selects the sketch ("mg" | "bm"), ``rescan`` the MG
    double-scan ablation (§4.4), ``engine`` the fold backend — all
    uniform with the single-host driver (they key the same
    ``FoldRequest``, DESIGN.md §14).
    ``frontier_gate`` turns on per-shard dense frontier gating (the
    distributed analogue of ``LPAConfig.frontier_gate``): settled vertices
    keep their label, and Pick-Less iterations union the previous frontier
    into the marks so deferred vertices stay queued (§8.5).

    ``lpa(graph, LPAConfig(shards=P))`` runs the same loop on a workspace
    ``build_workspace`` placed once; this entry point places ``ws`` on
    ``mesh`` on every call."""
    ws = jax.device_put(ws, NamedSharding(mesh, P(tuple(mesh.axis_names))))
    label_pos = jax.device_put(label_positions(ws.init_labels),
                               NamedSharding(mesh, P()))
    res = dist_solve(mesh, ws, label_pos, rho=rho, tau=tau,
                     max_iters=max_iters, engine=engine, method=method,
                     rescan=rescan, frontier_gate=frontier_gate)
    return res.labels, res.iterations


def label_positions(init_labels) -> np.ndarray:
    """[N] int32: where vertex v's label lies in the flattened [P * V_pad]
    stacked labels (``init_labels`` holds each slot's vertex id, -1 on
    pads)."""
    slots = np.asarray(init_labels).reshape(-1)
    real = slots >= 0
    pos = np.empty(int(real.sum()), dtype=np.int32)
    pos[slots[real]] = np.nonzero(real)[0]
    return pos


@jax.jit
def _labels_by_vertex(labels, label_pos):
    """Stacked [P, V_pad] labels -> [N] labels in vertex order."""
    return labels.reshape(-1)[label_pos]


@functools.lru_cache(maxsize=32)
def _jitted_step(mesh, treedef, engine, method, rescan, frontier_gate):
    """One jitted step per mesh, workspace structure and routing key, so
    repeated solves re-trace nothing. The step reads only the statics of
    the workspace it is built from (its arrays come in as ``ws_arrays``),
    so it is built from the structure alone and holds no array."""
    skeleton = jax.tree_util.tree_unflatten(treedef,
                                            [True] * treedef.num_leaves)
    return jax.jit(dist_lpa_step(mesh, skeleton, engine=engine,
                                 method=method, rescan=rescan,
                                 frontier_gate=frontier_gate))


def dist_solve(mesh, ws: DistLPAWorkspace, label_pos, *, rho: int = 8,
               tau: float = 0.05, max_iters: int = 20,
               engine: str | None = None, method: str = "mg",
               rescan: bool = False,
               frontier_gate: bool = False) -> LPAResult:
    """The distributed host loop over a workspace already placed on
    ``mesh``; ``label_pos`` is ``label_positions(ws.init_labels)`` on the
    mesh. Options as ``dist_lpa``'s. The result's labels are in vertex
    order; it records no frontier or work-row history.

    A Pick-Less iteration never converges, so the loop does not read its
    moved count: the count stays on the device and is read with the next
    iteration's, one host read per non-Pick-Less iteration (one more if
    ``max_iters`` ends on a Pick-Less iteration)."""
    step = _jitted_step(mesh, jax.tree_util.tree_structure(ws), engine,
                        method, rescan, frontier_gate)
    n = ws.n_nodes
    count = obs.SyncCount()
    history, pending = [], []
    converged = False
    it = 0
    with obs.span("lpa.solve"):
        labels = ws.init_labels
        if frontier_gate:
            frontier = jax.device_put(
                jnp.ones(labels.shape, dtype=jnp.bool_),
                NamedSharding(mesh, P(tuple(mesh.axis_names))))
        for it in range(max_iters):
            with obs.span("lpa.iteration", it=it):
                pl_on = (it % rho) == 0
                with obs.span("lpa.dispatch.move"):
                    args = (ws.nbr_pos, ws.weights, ws.round_gathers,
                            ws.final_row_vertex, labels, jnp.asarray(pl_on),
                            jnp.int32(it + 1))
                    if frontier_gate:
                        labels, delta, marked = step(*args,
                                                     frontier=frontier,
                                                     ws_arrays=ws)
                        frontier = (frontier | marked) if pl_on else marked
                    else:
                        labels, delta = step(*args, ws_arrays=ws)
                pending.append(delta)
                if pl_on:
                    continue
                with obs.sync("delta", count):
                    history += [int(d) for d in jax.device_get(pending)]
                pending = []
                if history[-1] / max(n, 1) < tau:
                    converged = True
                    break
        if pending:
            with obs.sync("delta", count):
                history += [int(d) for d in jax.device_get(pending)]
        out = _labels_by_vertex(labels, label_pos)
    return LPAResult(labels=out, iterations=it + 1, changed_history=history,
                     converged=converged, host_syncs=count.host_syncs)


@dataclasses.dataclass(frozen=True)
class ShardBundle:
    """The plan-level view a one-chip workspace's ``bundle`` gives: the
    resolved per-shard spec (the stacked plans live in the
    ``DistLPAWorkspace``)."""

    spec: PlanSpec  # "auto" resolved against the widest shard


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedWorkspace:
    """``build_workspace``'s workspace for ``LPAConfig(shards > 1)``: the
    stacked shard workspace placed on a ``(shards,)`` mesh once, and where
    each vertex's label lies in it. Answers ``bundle.spec`` and
    ``stream_plan`` (None: the windowed plans are per shard) as a one-chip
    ``LPAWorkspace`` does."""

    dist: DistLPAWorkspace  # placed: shard p of each array on device p
    label_pos: jnp.ndarray  # [N] int32 (label_positions), replicated
    mesh: Mesh              # the (shards,) mesh ``dist`` lives on
    bundle: ShardBundle     # the resolved per-shard spec

    def tree_flatten(self):
        return (self.dist, self.label_pos), (self.mesh, self.bundle)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def stream_plan(self):
        return None


def build_sharded_workspace(graph, config) -> ShardedWorkspace:
    """The ``LPAConfig(shards > 1)`` build (duck-typed on the config, as
    ``spec_for``): edge-balanced shards, their plans and the halo
    exchange tables (``build_dist_workspace(halo=True)``), placed on the
    first ``shards`` devices. ``fold_backend="auto"`` resolves against the widest shard's
    entry count by the one-chip rule (``resolve_auto``)."""
    backend = config.fold_backend
    if backend == "auto":
        backend = resolve_auto(
            shard_entry_pad(np.asarray(graph.degrees), config.shards),
            config.vmem_budget_bytes)
    stream = backend == "pallas_stream"
    mesh = make_mesh((config.shards,), ("shard",))
    dist = build_dist_workspace(
        graph, config.shards, k=config.k, chunk=config.chunk,
        halo=True, fused=backend == "pallas_fused", stream=stream,
        window_entries=config.stream_window,
        aligned=stream and config.aligned_layout,
        sharding=NamedSharding(mesh, P("shard")))
    label_pos = jax.device_put(label_positions(dist.init_labels),
                               NamedSharding(mesh, P()))
    spec = dataclasses.replace(spec_for(config), backend=backend)
    return ShardedWorkspace(dist=dist, label_pos=label_pos, mesh=mesh,
                            bundle=ShardBundle(spec=spec))
