"""Label Propagation driver — the paper's Algorithms 1/3/4 on TPU-native JAX.

Methods:
  * ``exact`` — sort+segment exact aggregation (ν-LPA / GVE-LPA analogue,
    O(|E|) working set).
  * ``mg``    — weighted Misra-Gries k-slot sketches (νMG-LPA, O(k|V|)).
  * ``bm``    — weighted Boyer-Moore majority vote (νBM-LPA, O(|V|)).

Shared machinery (paper Alg. 1): unique initial labels; per-iteration move
step; Pick-Less (PL) symmetry breaking every ``rho`` iterations starting at
iteration 0 (a vertex may only adopt a *smaller* label while PL is active);
convergence when the changed fraction drops below ``tau`` in a non-PL
iteration; hard cap ``max_iters``.

The sketch fold backend is a config string resolved through
``repro.core.fold_engine`` ("jnp" | "pallas" | "pallas_fused" |
"pallas_stream" | "auto") and applies uniformly to every sketch: the MG
fold (one fused dispatch per round, the last fused with move selection,
DESIGN.md §9), the BM fold and the rescan second pass (one dispatch
each on the fused/streamed engines, DESIGN.md §11). The streaming
engine keeps the fused dispatch structure while bounding VMEM residency
to fixed entry windows (DESIGN.md §10); "auto" picks between fused and
streamed from the round-0 entry volume vs ``vmem_budget_bytes``.

Plan construction is one declarative call (DESIGN.md §15):
``build_workspace`` derives a :class:`repro.core.plan_bundle.PlanSpec`
from the config and hands it to ``build_plan_bundle``, which builds
exactly the plans the config's FoldRequests need; the host-side sizing
policy (dense row counts, sparse-overflow checks, the default row cap)
lives on the bundle so this driver and ``dist_lpa`` share one copy.

Deviation from the paper (documented in DESIGN.md §8): iterations are
synchronous (pure-functional JAX) rather than asynchronous in-place. The
unprocessed-frontier of paper Alg. 1 l. 31 is tracked every iteration
(``LPAResult.frontier_history`` diagnostics) and — with the opt-in
``frontier_gate`` config, after Traag & Šubelj's fast label propagation —
gates the move step so settled vertices (no changed neighbor) keep their
label. ``frontier_sparse`` additionally *executes* the gate: each
iteration the host checks the concrete frontier against a static row
capacity and, when it fits, swaps the mover's static ``FoldRequest`` to
``mode="sparse"`` so the engine compacts the active fold rows and grids
only over them — the skipped-row savings the gate alone never bought
(DESIGN.md §8.5/§14; ``LPAResult.work_rows_history`` records the rows
each iteration folded).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import TYPE_CHECKING, Callable, Literal, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import obs
from repro.core.exact import exact_choose
from repro.core.fold_engine import get_engine
from repro.core.fold_program import FoldRequest
from repro.core.plan_bundle import PlanBundle, build_plan_bundle, spec_for
from repro.graphs.csr import CSRGraph
from repro.kernels.mg_sketch.fused import pack_marks

# core.distributed imports LPAResult from here, so the sharded path
# imports it where it runs
if TYPE_CHECKING:
    from repro.core.distributed import ShardedWorkspace

Method = Literal["exact", "mg", "bm"]


@dataclasses.dataclass(frozen=True)
class LPAConfig:
    method: Method = "mg"      # "exact" | "mg" | "bm" (paper §4)
    k: int = 8                 # MG sketch slots (paper: 8)
    chunk: int = 128           # virtual-vertex chunk width (paper D_H: 128)
    rho: int = 8               # Pick-Less cadence (paper: 8)
    tau: float = 0.05          # convergence tolerance (paper: 0.05)
    max_iters: int = 20        # paper: 20
    rescan: bool = False       # double-scan mode (paper Fig. 5 ablation)
    # "jnp" | "pallas" | "pallas_fused" | "pallas_stream" | "auto"
    fold_backend: str = "jnp"
    mg_variant: str = "paper"  # "paper" | "exact_weighted" (DESIGN.md §8.4)
    # pallas_stream: cap on the entries of a streamed window (bytes resident
    # per step <= ~2 * window * 8); each fold round packs its windows at the
    # cap under it that its rows make cheapest (csr.build_streamed_rounds).
    # Also the "auto" policy's stream granularity
    stream_window: int = 8192
    # pallas_stream: materialize the round-0 CSR entries window-aligned at
    # plan build time (DESIGN.md §13). The driver then gathers neighbor
    # labels straight into window slots and the engine skips its
    # per-iteration O(|E|) windowed re-layout gather — bit-identical to the
    # unaligned layout. Applies whenever the (possibly auto-resolved)
    # backend streams; other backends ignore it.
    aligned_layout: bool = False
    # "auto" picks pallas_fused while 8 * |E| <= this budget, else
    # pallas_stream (None = fold_engine.DEFAULT_VMEM_BUDGET_BYTES)
    vmem_budget_bytes: Optional[int] = None
    frontier_gate: bool = False  # Traag & Šubelj frontier gating (opt-in)
    # Sparse execution of the gate (DESIGN.md §8.5): per iteration the host
    # checks the concrete frontier against the row capacity and, when it
    # fits, folds ONLY the active rows through the engine's compacted
    # sparse path; otherwise it falls back to the dense gated mover (both
    # movers are statically shaped jit artifacts). Requires frontier_gate;
    # the bucketed jnp/pallas backends accept it but fold densely (only
    # pallas_fused/pallas_stream actually skip rows).
    frontier_sparse: bool = False
    # Static per-round active-row capacity of the sparse path (None: half
    # the largest round's real rows — the break-even neighborhood). Larger
    # caps keep the sparse mover in play on bigger frontiers at the price
    # of more padded compute per sparse iteration.
    frontier_cap_rows: Optional[int] = None
    # frontier_history diagnostics (the per-iteration frontier fraction).
    # Deliberately decoupled from gating: frontier_gate computes the marks
    # it needs whether or not this is set, and track_frontier=False then
    # only skips recording the history — it does NOT silently re-enable
    # anything. The marks come from the mover's round-0 pass on an aligned
    # streamed MG plan (marks_in_mover), else from one O(|E|) segment_max
    # an iteration (mark_frontier). With both frontier_gate and
    # track_frontier off nothing marks the frontier (asserted in
    # tests/test_sparse_frontier).
    track_frontier: bool = True
    # > 1: split the vertices into this many edge-balanced shards, one a
    # device (core.distributed): build_workspace places them on a
    # (shards,) mesh of the first `shards` devices and lpa() runs the
    # distributed loop, bit-identical to shards=1, exchanging only the
    # labels a shard's edges reference (hub all-gather + per-pair
    # all_to_all). It carries neither the frontier history nor the sparse
    # fold (track_frontier and frontier_sparse must be off), nor
    # method="exact", nor mg_variant.
    shards: int = 1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LPAWorkspace:
    """Graph + its plan bundle + CSR-expanded edge sources.

    The bundle holds the static fold plans the config's requests need
    (``build_plan_bundle``): the bucketed plan always, plus exactly one
    aux plan when the resolved backend is fused/streamed — the aux plan
    serves every sketch (MG, BM and the rescan ablation all fold through
    it). The legacy ``plan``/``fused_plan``/``stream_plan`` reads stay
    available as properties delegating to the bundle.
    """

    graph: CSRGraph        # the CSR graph the plans were built from
    bundle: PlanBundle     # static fold plans + resolved PlanSpec
    edge_src: jnp.ndarray  # [M] int32 CSR-expanded edge source vertices

    def tree_flatten(self):
        return (self.graph, self.bundle, self.edge_src), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def plan(self):
        return self.bundle.plan

    @property
    def fused_plan(self):
        return self.bundle.fused_plan

    @property
    def stream_plan(self):
        return self.bundle.stream_plan


def build_workspace(graph: CSRGraph, config: LPAConfig
                    ) -> LPAWorkspace | ShardedWorkspace:
    """Thin wrapper over the bundle layer: spec the config, build the
    bundle, attach the edge-source expansion ``lpa()`` reads. With
    ``config.shards > 1``: the sharded workspace, placed on its mesh
    (``core.distributed.build_sharded_workspace``)."""
    if config.shards > 1:
        from repro.core.distributed import build_sharded_workspace
        _check_sharded(config)
        return build_sharded_workspace(graph, config)
    return LPAWorkspace(graph=graph,
                        bundle=build_plan_bundle(graph, spec_for(config)),
                        edge_src=graph.sources())


def _check_sharded(config: LPAConfig) -> None:
    """Refuses what the distributed loop does not carry."""
    refused = [
        ("frontier_sparse", config.frontier_sparse),
        ("track_frontier (no frontier history)", config.track_frontier),
        ('method="exact"', config.method == "exact"),
        (f"mg_variant={config.mg_variant!r}", config.mg_variant != "paper"),
    ]
    for what, on in refused:
        if on:
            raise ValueError(f"LPAConfig(shards={config.shards}) does not "
                             f"carry {what}")
    if config.shards > len(jax.devices()):
        raise ValueError(f"LPAConfig(shards={config.shards}) needs as many "
                         f"devices; JAX finds {len(jax.devices())}")


def marks_in_mover(ws: LPAWorkspace, config: LPAConfig) -> bool:
    """Whether the mover marks the frontier itself (``lpa_move(marks=)``,
    DESIGN.md §8.5): the dense MG fold of an aligned streamed plan, whose
    round-0 kernel reads every vertex's whole row of gathered neighbour
    labels. Elsewhere ``lpa()`` runs :func:`mark_frontier`; the sparse
    fold needs the frontier on the host before it dispatches."""
    engine = get_engine(ws.bundle.spec.backend, checked=False)
    aux = ws.bundle.aux_for(engine)
    return (config.method == "mg" and not config.rescan
            and not config.frontier_sparse and engine.uses_stream_plan
            and aux is not None and aux.aligned)


@dataclasses.dataclass
class MoverMarks:
    """The frontier the mover marks itself (:func:`marks_in_mover`). In:
    the previous move's ``changed`` mask, frontier and Pick-Less flag.
    Out: ``entering``, the frontier entering this move, which
    :func:`lpa_move` sets."""

    changed: jnp.ndarray  # [N] bool: the previous move's changed mask
    frontier: jnp.ndarray  # [N] bool: the frontier entering that move
    pick_less: jnp.ndarray  # bool scalar: that move was a Pick-Less one
    #: [N] bool: the frontier entering this move (lpa_move sets it)
    entering: Optional[jnp.ndarray] = None

    def enter(self, marked: jnp.ndarray) -> jnp.ndarray:
        """The frontier entering this move from the neighbours of the
        vertices that changed: after a Pick-Less move, which defers legal
        moves, the previous frontier stays queued (§8.5)."""
        return jnp.where(self.pick_less, self.frontier | marked, marked)


def lpa_move(ws: LPAWorkspace, labels: jnp.ndarray, pick_less: jnp.ndarray,
             seed: jnp.ndarray, config: LPAConfig,
             frontier: Optional[jnp.ndarray] = None, sparse: bool = False,
             cap_rows: int = 0, marks: Optional[MoverMarks] = None
             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One LPA iteration: returns (new_labels, changed_mask).

    ``pick_less`` and ``seed`` are traced so the jitted step is reused
    across PL-on/off iterations; ``seed`` varies per iteration and drives
    the hash tie-breaking (DESIGN.md §8 — the synchronous stand-in for the
    async/hashtable-order tie randomness of the GPU implementation).
    ``frontier`` (optional bool [N]) gates moves to unprocessed vertices
    (config.frontier_gate). ``sparse``/``cap_rows`` are static: they put
    ``mode="sparse"`` on the FoldRequest so the engine compacts the fold
    to active rows — the caller must have verified on the host that the
    frontier fits ``cap_rows`` (``lpa``'s loop swaps the request back to
    dense on overflow). Sparse wanted labels are bit-identical to dense
    ones on frontier vertices and the gate masks the rest, so the two
    request modes commute.

    ``marks`` makes the mover mark the frontier itself (where
    :func:`marks_in_mover` holds, in place of ``frontier``): the round-0
    gather reads each neighbour's ``changed`` bit with its label, the
    round-0 kernel ORs it over each row, and scope ``lpa.marks`` ORs each
    vertex's rows into ``marks.entering``, which gates the move under
    ``config.frontier_gate``.
    """
    graph, bundle = ws.graph, ws.bundle
    if sparse and frontier is None:
        raise ValueError("sparse=True needs a frontier (the compacted fold "
                         "is defined by the active vertex set)")
    # the bundle's spec carries the RESOLVED backend ("auto" was decided
    # at plan-build time), so the engine always finds its plan.
    # checked=False: lpa_move is traced/jitted and the checkify contract
    # proxy throws eagerly (REPRO_CHECKED must not leak into the jit path)
    engine = get_engine(bundle.spec.backend, mg_variant=config.mg_variant,
                        checked=False)

    aux = bundle.aux_for(engine)
    with jax.named_scope("lpa.gather"):
        if engine.uses_stream_plan and aux is not None and aux.aligned:
            # window-aligned layout (DESIGN.md §13): ONE O(window slots)
            # gather straight into window-slot order replaces
            # labels[graph.indices] AND the engine's per-iteration windowed
            # re-layout gather; the appended -1 slot absorbs the plan's
            # n_nodes pad sentinel.
            labels_ext = jnp.concatenate([labels,
                                          jnp.full((1,), -1, labels.dtype)])
            if marks is not None:
                # each neighbour's changed bit rides with its label; packed
                # after the pad slot is appended, so XLA makes one n + 1
                # buffer of both (packing first cost the 2^20-vertex k-mer
                # mover 17 MB more HBM on v5e, compiled ahead of time)
                labels_ext = pack_marks(labels_ext, jnp.concatenate(
                    [marks.changed, jnp.zeros((1,), jnp.bool_)]))
            nbr_labels = labels_ext[aux.aligned_entry_vertex]
            nbr_weights = aux.aligned_entry_weights
        else:
            nbr_labels = labels[graph.indices]
            nbr_weights = graph.weights
    if config.method == "exact":
        want = exact_choose(ws.edge_src, labels[graph.indices],
                            graph.weights, graph.n_nodes, labels, seed)
    elif config.method in ("mg", "bm"):
        # One declarative request routes every sketch combo — family
        # (incl. the rescan ablation's in-engine second pass, paper
        # Fig. 5) and mode (the sparse request compacts the fold to the
        # frontier) — through FoldEngine.run (DESIGN.md §14). Built under
        # trace: the routing fields are Python statics, seed/frontier are
        # the traced operands.
        request = FoldRequest(
            family=config.method,
            mode="sparse" if sparse else "dense",
            rescan=config.method == "mg" and config.rescan,
            aligned=bool(engine.uses_stream_plan and aux is not None
                         and aux.aligned),
            seed=seed,
            frontier=frontier if sparse else None,
            cap_rows=cap_rows if sparse else 0,
            marks=marks is not None)
        outcome = engine.run(bundle, request, nbr_labels, nbr_weights,
                             labels)
        want = outcome.want
    else:
        raise ValueError(f"unknown method {config.method!r}")

    if marks is not None:
        with jax.named_scope("lpa.marks"):
            marks.entering = marks.enter(outcome.marked)
        if config.frontier_gate:
            frontier = marks.entering

    with jax.named_scope("lpa.apply"):
        allowed = jnp.where(pick_less, want < labels, want != labels)
        if frontier is not None:
            allowed = allowed & frontier
        new_labels = jnp.where(allowed, want, labels)
        changed = new_labels != labels
    return new_labels, changed


def mover(config: LPAConfig, cap_rows: int, marks: bool = False
          ) -> Callable:
    """``lpa_move`` bound to ``config`` and ``cap_rows`` as ``lpa()`` calls
    it; jitted (``sparse`` static), its XLA module is ``jit_lpa_move``.

    ``marks`` (where :func:`marks_in_mover` holds): the mover takes the
    previous move's ``last`` = (changed, frontier) and ``last_pick_less``
    in place of a frontier, and returns (new_labels, changed, frontier
    entering the move); ``lpa()`` jits it with ``last`` donated. A
    ``lpa_move`` that leaves the marks unset (one wrapped or replaced,
    keeping only the (new_labels, changed) contract) gets
    :func:`mark_frontier`'s marks in the same program.
    """
    if not marks:
        move = functools.partial(lpa_move, config=config, cap_rows=cap_rows)
    else:
        def move(ws, labels, pick_less, seed, last, last_pick_less):
            carry = MoverMarks(*last, last_pick_less)
            new_labels, changed = lpa_move(ws, labels, pick_less, seed,
                                           config, cap_rows=cap_rows,
                                           marks=carry)
            if carry.entering is None:
                carry.entering = carry.enter(mark_frontier(ws,
                                                           carry.changed))
            return new_labels, changed, carry.entering
    move.__name__ = "lpa_move"
    return move


def mark_frontier(ws: LPAWorkspace, changed: jnp.ndarray) -> jnp.ndarray:
    """Mark neighbors of changed vertices as unprocessed (paper Alg. 1 l. 31).

    This is the synchronous analogue of Traag & Šubelj's FLPA queue: after
    an iteration, exactly the neighbors of vertices that changed label are
    'in the queue' for the next one.
    """
    n = ws.graph.n_nodes
    src_changed = changed[ws.edge_src].astype(jnp.int32)
    marked = jax.ops.segment_max(src_changed, ws.graph.indices, num_segments=n)
    return marked > 0


@dataclasses.dataclass
class LPAResult:
    labels: jnp.ndarray    # [N] int32 final label per vertex
    iterations: int        # iterations actually run (<= config.max_iters)
    changed_history: list  # per-iteration count of vertices that moved
    converged: bool        # changed fraction fell below tau (non-PL iter)
    #: unprocessed-frontier fraction entering each iteration (diagnostics;
    #: the gate only acts on it when config.frontier_gate is set)
    frontier_history: list = dataclasses.field(default_factory=list)
    #: rows the fold actually computed each iteration. Dense iterations
    #: record the full plan row count; sparse ones record the compacted
    #: active rows (fused) or rows in active windows (streamed) — the
    #: skipped-row savings are visible as the gap to the dense entries.
    work_rows_history: list = dataclasses.field(default_factory=list)
    #: device-to-host reads the host loop made (``obs.sync`` blocks): one
    #: per iteration for the convergence delta, one more each for the
    #: frontier fraction (track_frontier) and the sparse fit check
    host_syncs: int = 0
    #: iterations whose frontier the mover marked from its round-0 gather
    #: (``marks_in_mover``; every one after the first, whose frontier is
    #: all ones); 0 where ``mark_frontier`` marks it or nothing does
    mover_marks: int = 0


def lpa(graph: CSRGraph, config: Optional[LPAConfig] = None,
        ws: Optional[LPAWorkspace] = None, jit: bool = True) -> LPAResult:
    """Run LPA to convergence (host loop; jitted move step).

    The loop's host spans and the mover's device scopes are named in
    :mod:`repro.core.obs`; they record only under ``jax.profiler.trace``.
    ``config.shards > 1`` runs the distributed loop
    (``core.distributed.dist_solve``, always jitted) on a workspace
    ``build_workspace`` placed on its mesh.
    """
    config = config if config is not None else LPAConfig()
    if config.shards > 1:
        return _lpa_sharded(graph, config, ws)
    if config.frontier_sparse:
        if not config.frontier_gate:
            raise ValueError("frontier_sparse requires frontier_gate: the "
                             "sparse fold is only correct when off-frontier "
                             "moves are masked")
        if config.method == "exact":
            raise ValueError("frontier_sparse does not apply to the exact "
                             "method (no fold plan to compact)")
    ws = ws if ws is not None else build_workspace(graph, config)
    cap_rows = ws.bundle.cap_rows()
    need_marks = config.frontier_gate or config.track_frontier
    in_mover = need_marks and marks_in_mover(ws, config)
    move = mover(config, cap_rows, marks=in_mover)
    frontier_fn = mark_frontier
    if jit:
        # ONE mover; the dense/sparse choice is a static argument decided
        # per iteration on the host (the frontier is concrete between
        # iterations), never a traced branch — the overflow fallback is a
        # request swap between two cached specializations of the same
        # artifact.
        # A mover that marks takes the previous changed mask and frontier
        # (last=) and donates them: its new ones take their buffers.
        move = (jax.jit(move, donate_argnames=("last",)) if in_mover
                else jax.jit(move, static_argnames=("sparse",)))
        frontier_fn = jax.jit(mark_frontier)
    n = graph.n_nodes
    marks_by = ("mover" if in_mover else
                "segment_max" if need_marks else "none")
    mover_marks = 0
    history = []
    frontier_history = []
    work_rows_history = []
    dense_rows = ws.bundle.dense_work_rows()
    count = obs.SyncCount()
    converged = False
    it = 0
    with obs.span("lpa.solve"):
        labels = jnp.arange(n, dtype=jnp.int32)
        frontier = jnp.ones((n,), dtype=jnp.bool_)  # every vertex queued
        # the mover marks iteration 0's frontier from no vertex changed and
        # the all-ones frontier kept, as after a Pick-Less pass
        changed = jnp.zeros((n,), dtype=jnp.bool_) if in_mover else None
        pl = True
        for it in range(config.max_iters):
            with obs.span("lpa.iteration", it=it, marks=marks_by):
                last_pl, pl = pl, (it % config.rho) == 0
                gate = frontier if config.frontier_gate else None
                sparse = False
                work = dense_rows
                if config.frontier_sparse:
                    with obs.sync("sparse_fit", count):
                        active = np.asarray(frontier)
                    fits, sparse_work = ws.bundle.sparse_fit(active,
                                                             cap_rows)
                    if fits:
                        sparse, work = True, sparse_work
                with obs.span("lpa.dispatch.move"):
                    seed = jnp.int32(it + 1)
                    if in_mover:
                        labels, changed, frontier = move(
                            ws, labels, jnp.asarray(pl), seed,
                            last=(changed, frontier),
                            last_pick_less=jnp.asarray(last_pl))
                        mover_marks += it > 0
                    else:
                        labels, changed = move(ws, labels, jnp.asarray(pl),
                                               seed, frontier=gate,
                                               sparse=sparse)
                work_rows_history.append(work)
                if config.track_frontier:
                    with obs.sync("frontier_mean", count):
                        frontier_history.append(float(jnp.mean(frontier)))
                if need_marks and not in_mover:
                    with obs.span("lpa.dispatch.frontier"):
                        marked = frontier_fn(ws, changed)
                        # A Pick-Less round blocks legal moves (want >
                        # label), so its unchanged vertices are deferred,
                        # not settled — keep them queued instead of
                        # letting the gate freeze them (§8.5).
                        frontier = (frontier | marked) if pl else marked
                with obs.sync("delta", count):
                    delta = int(jnp.sum(changed))
                history.append(delta)
                if not pl and delta / max(n, 1) < config.tau:
                    converged = True
                    break
    return LPAResult(labels=labels, iterations=it + 1,
                     changed_history=history, converged=converged,
                     frontier_history=frontier_history,
                     work_rows_history=work_rows_history,
                     host_syncs=count.host_syncs, mover_marks=mover_marks)


def _lpa_sharded(graph: CSRGraph, config: LPAConfig,
                 ws: Optional[ShardedWorkspace]) -> LPAResult:
    from repro.core.distributed import ShardedWorkspace, dist_solve
    _check_sharded(config)
    ws = ws if ws is not None else build_workspace(graph, config)
    if not isinstance(ws, ShardedWorkspace):
        raise ValueError(f"LPAConfig(shards={config.shards}) needs the "
                         "workspace build_workspace makes for it")
    return dist_solve(ws.mesh, ws.dist, ws.label_pos, rho=config.rho,
                      tau=config.tau, max_iters=config.max_iters,
                      engine=ws.bundle.spec.backend, method=config.method,
                      rescan=config.method == "mg" and config.rescan,
                      frontier_gate=config.frontier_gate)


def lpa_step_fn(config: LPAConfig) -> Callable:
    """A (ws, labels, iteration) -> (labels, delta_n) single-step function —
    the unit the dry-run lowers and the roofline analyses."""

    def step(ws: LPAWorkspace, labels: jnp.ndarray, iteration: jnp.ndarray):
        pick_less = (iteration % config.rho) == 0
        seed = iteration.astype(jnp.int32) + 1
        new_labels, changed = lpa_move(ws, labels, pick_less, seed, config)
        return new_labels, jnp.sum(changed.astype(jnp.int32))

    return step
