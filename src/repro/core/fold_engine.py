"""FoldEngine: uniform backend selection for the sketch folds.

Every engine computes BOTH of the paper's sketches through ONE entry
point: consumers build a :class:`repro.core.fold_program.FoldRequest`
(family + mode + rescan + traced payload) and call :meth:`FoldEngine.run`,
which routes it to the backend's family executor and returns a
:class:`FoldOutcome` (DESIGN.md §14). The family executors are

  * **MG** (``mg_select``, plus ``mg_candidates`` for raw candidate
    sets): fold the neighbor entries into per-vertex k-slot Misra-Gries
    sketches, then pick each vertex's winning label;
  * **MG + rescan** (``mg_rescan``): the double-scan ablation — re-score
    the k candidates exactly against the round-0 neighborhood before
    selecting (paper §4.4);
  * **BM** (``bm_fold_plan``): fold round 0 into per-row weighted
    Boyer-Moore majority states and max-reduce-merge them per vertex
    (paper Alg. 3 / §4.7).

Sparse (frontier-compacted) execution is not a separate method family:
``run`` lowers ``mode="sparse"`` to a ``RoundSelection`` threaded into
the same executors, and the fused/streamed kernel drivers compact their
row/window grids from it (DESIGN.md §8.5). Four interchangeable backends
compute the executors:

  * ``jnp``           — dense reference (repro.core.sketch); also hosts the
                        ``exact_weighted`` MG variant (DESIGN.md §8.4).
  * ``pallas``        — per-width-bucket Pallas tile kernels; XLA gathers a
                        padded [R, D] tile per bucket per round (HBM
                        round-trip), one dispatch each. Kept as the
                        pre-fusion baseline.
  * ``pallas_fused``  — whole-round fused kernels with an in-kernel gather
                        and the final round fused with move selection:
                        ``n_rounds`` dispatches per MG iteration instead of
                        ``O(rounds x buckets)``, ONE dispatch for the BM
                        fold and ONE for the rescan second pass
                        (kernels.mg_sketch.fused). Keeps the flat entry
                        arrays VMEM-resident, so a single core is bounded
                        by the VMEM budget (round 0 = |E| entries at 8
                        bytes each).
  * ``pallas_stream`` — the fused dataflow with every round streamed
                        through fixed-size double-buffered HBM->VMEM entry
                        windows (kernels.mg_sketch.streaming): same
                        dispatch counts, O(window) residency — for graphs
                        past the fused VMEM budget (DESIGN.md §10/§11).

Dispatch accounting is request-keyed the same way: ONE
``dispatches_per_iter(plan, aux_plan, request)`` per engine, verified
symbolically per request by kernelcheck R3, with routing closure over the
request space enforced by R7 (DESIGN.md §12).

``"auto"`` resolves to ``pallas_fused`` or ``pallas_stream`` per graph by
checking the round-0 entry volume against a configurable VMEM budget
(:func:`resolve_auto`).

``repro.core.lpa``, ``repro.core.distributed`` and the benchmarks all
resolve engines through :func:`get_engine`, so backend choice is a config
string everywhere. All engines are bit-identical on the paper's MG, BM
and double-scan rules (validated in tests/test_fused_engine.py,
tests/test_stream_engine.py, tests/test_bm_engines.py,
tests/test_rescan_engines.py and tests/test_kernels.py).
"""
from __future__ import annotations

import os
from typing import TYPE_CHECKING, Optional, Tuple

import jax.numpy as jnp

if TYPE_CHECKING:  # import-time cycle guard: plan_bundle imports this module
    from repro.core.plan_bundle import PlanBundle

from repro.core import sketch as sketch_lib
from repro.core.fold_program import FoldOutcome, FoldRequest, RoundSelection
from repro.graphs.csr import (FoldPlan, fused_dispatches, plan_dispatches,
                              plan_round0_dispatches, streamed_dispatches)

#: Default VMEM budget (bytes) the ``auto`` policy allows the fused engine's
#: resident round-0 entry arrays (labels int32 + weights float32 = 8
#: bytes/entry; one whole-array VMEM copy each, not double-buffered).
#: 8 MiB = 1M entries. The v5e compiler accepts the fused kernels with up
#: to 96 MiB of entries and refuses 128 MiB, the core's whole VMEM, so
#: the budget is a speed policy, not a capacity limit: raise it once a chip
#: measurement shows the fused engine winning past 1M entries
#: (tests/test_tpu_compile.py compiles the fused kernels at the budget).
DEFAULT_VMEM_BUDGET_BYTES = 8 * 2**20

#: HBM bytes per round-0 entry held resident by the fused engine
#: (int32 label + float32 weight).
_BYTES_PER_ENTRY = 8



def _require_plan(aux_plan, engine: str, plan_name: str):
    """Guard for the plan-consuming engines: the aux plan is built by
    build_workspace exactly when the config selects the engine."""
    if aux_plan is None:
        raise ValueError(f"{engine} engine needs a {plan_name} "
                         f"(build_workspace constructs one when "
                         f"fold_backend={engine!r})")
    return aux_plan


class FoldEngine:
    """Backend-neutral interface; subclasses wire the actual kernels.

    Consumers go through :meth:`run` with a :class:`FoldRequest`; the
    family executors below are the per-backend implementation surface
    (and stay callable directly where a consumer wants one family with
    no routing, e.g. the distributed per-shard folds).
    """

    name: str = "base"
    #: does mg_select consume the FusedFoldPlan (vs the bucketed FoldPlan)?
    uses_fused_plan: bool = False
    #: does mg_select consume the StreamedFoldPlan?
    uses_stream_plan: bool = False

    # -- the routed entry point (DESIGN.md §14/§15) -----------------------
    def run(self, bundle: "PlanBundle", request: FoldRequest,
            entry_labels, entry_weights, labels) -> FoldOutcome:
        """Execute one fold iteration described by ``request``.

        Plans are keyed off the :class:`~repro.core.plan_bundle.PlanBundle`
        (the bucketed plan plus whichever aux plan this engine consumes,
        via :meth:`PlanBundle.aux_for`) — consumers stopped threading
        loose (plan, aux_plan) pairs in the PlanBundle refactor.

        Routing is total over the request space (kernelcheck R7):
        ``family="bm"`` -> :meth:`bm_fold_plan` (with the -1 sentinel
        resolved into per-vertex wants here, once), ``rescan=True`` ->
        :meth:`mg_rescan`, otherwise :meth:`mg_select`. ``mode="sparse"``
        lowers the request's frontier/cap into a :class:`RoundSelection`
        threaded to the executor; the caller (core.lpa's host loop)
        guarantees the concrete frontier fits ``cap_rows`` and swaps the
        request back to dense on overflow, so the engine never sees an
        overflowing frontier. Contract on every engine: ``want`` is
        bit-identical to the dense request's on frontier vertices —
        lpa_move masks off-frontier moves either way.
        """
        plan = bundle.plan
        aux_plan = bundle.aux_for(self)
        selection = None
        if request.mode == "sparse":
            selection = RoundSelection(frontier=request.frontier,
                                       cap_rows=request.cap_rows)
        if request.family == "bm":
            best, weight = self.bm_fold_plan(plan, aux_plan, entry_labels,
                                             entry_weights, labels,
                                             selection=selection)
            want = jnp.where(best >= 0, best, labels)
            return FoldOutcome(want=want, bm_label=best, bm_weight=weight)
        if request.rescan:
            want = self.mg_rescan(plan, aux_plan, entry_labels,
                                  entry_weights, labels, request.seed,
                                  selection=selection)
        else:
            want = self.mg_select(plan, aux_plan, entry_labels,
                                  entry_weights, labels, request.seed,
                                  selection=selection)
        return FoldOutcome(want=want)

    # -- tile-level folds (the distributed path and run_bm_plan plug in
    #    here; signatures match repro.core.sketch.{mg,bm}_fold_tile) -------
    def mg_fold_tile(self, labels, weights, k):
        raise NotImplementedError

    def bm_fold_tile(self, labels, weights, init_label=None):
        raise NotImplementedError

    # -- family executors --------------------------------------------------
    # ``aux_plan`` is the engine's auxiliary plan: a FusedFoldPlan for
    # pallas_fused, a StreamedFoldPlan for pallas_stream, ignored (None ok)
    # by the bucketed jnp/pallas engines. The driver picks the right one
    # from the workspace via uses_fused_plan/uses_stream_plan.
    # ``selection=None`` means dense (fold every plan row); a
    # RoundSelection compacts the fused/streamed grids to the frontier
    # (the bucketed jnp/pallas layouts have no row compaction and compute
    # the dense fold either way — correct, zero FLOP savings).
    def mg_candidates(self, plan: FoldPlan, aux_plan,
                      entry_labels, entry_weights
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Per-vertex candidate sets ([N, k] labels, [N, k] weights)."""
        raise NotImplementedError

    def mg_select(self, plan: FoldPlan, aux_plan,
                  entry_labels, entry_weights, labels, seed, *,
                  selection: Optional[RoundSelection] = None) -> jnp.ndarray:
        """Full iteration: fold + move selection -> wanted label per vertex
        ([N] int32)."""
        raise NotImplementedError

    def mg_rescan(self, plan: FoldPlan, aux_plan,
                  entry_labels, entry_weights, labels, seed, *,
                  selection: Optional[RoundSelection] = None) -> jnp.ndarray:
        """Full double-scan iteration (paper §4.4): MG fold, then re-read
        the round-0 neighborhood to score the k candidates *exactly*, then
        select -> wanted label per vertex ([N] int32). Bit-identical to
        ``sketch.run_mg_plan`` + ``sketch.rescan_candidates`` on every
        engine."""
        raise NotImplementedError

    def bm_fold_plan(self, plan: FoldPlan, aux_plan,
                     entry_labels, entry_weights, labels, *,
                     selection: Optional[RoundSelection] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """νBM iteration core: fold round 0 into per-row weighted
        Boyer-Moore partial states (incumbent-initialized, paper Alg. 3
        l. 13) and max-reduce-merge them per vertex. Returns per-vertex
        ([N] majority label, -1 when the vertex has no entries; [N] vote
        weight). Bit-identical to ``sketch.run_bm_plan`` on every
        engine."""
        raise NotImplementedError

    def dispatches_per_iter(self, plan: FoldPlan, aux_plan,
                            request: FoldRequest) -> int:
        """Pallas kernel dispatches one ``request`` iteration costs on
        this engine. Request-keyed like :meth:`run`; ``mode`` never
        changes the count (sparse compacts grids inside the same
        dispatches). Verified symbolically per request by kernelcheck
        R3."""
        raise NotImplementedError


class JnpEngine(FoldEngine):
    """Dense pure-XLA reference (repro.core.sketch); the bit-exactness
    oracle for every Pallas engine, and the only host of the
    ``exact_weighted`` MG variant (DESIGN.md §8.4)."""

    name = "jnp"

    def __init__(self, mg_variant: str = "paper"):
        self.mg_variant = mg_variant

    def mg_fold_tile(self, labels, weights, k):
        if self.mg_variant == "exact_weighted":
            return sketch_lib.mg_fold_tile_exact_weighted(labels, weights, k)
        return sketch_lib.mg_fold_tile(labels, weights, k)

    def bm_fold_tile(self, labels, weights, init_label=None):
        return sketch_lib.bm_fold_tile(labels, weights, init_label)

    def mg_candidates(self, plan, fused_plan, entry_labels, entry_weights):
        s_k, s_v = sketch_lib.run_mg_plan(plan, entry_labels, entry_weights,
                                          fold_tile=self.mg_fold_tile)
        return sketch_lib.scatter_rows(plan, s_k, s_v)

    def mg_select(self, plan, fused_plan, entry_labels, entry_weights,
                  labels, seed, *, selection=None):
        # selection ignored: dense bucketed fold, gate-masked in lpa_move
        s_k, s_v = sketch_lib.run_mg_plan(plan, entry_labels, entry_weights,
                                          fold_tile=self.mg_fold_tile)
        return sketch_lib.select_best(plan, s_k, s_v, labels, seed)

    def mg_rescan(self, plan, fused_plan, entry_labels, entry_weights,
                  labels, seed, *, selection=None):
        s_k, _ = sketch_lib.run_mg_plan(plan, entry_labels, entry_weights,
                                        fold_tile=self.mg_fold_tile)
        return sketch_lib.rescan_candidates(plan, s_k, entry_labels,
                                            entry_weights, labels, seed)

    def bm_fold_plan(self, plan, fused_plan, entry_labels, entry_weights,
                     labels, *, selection=None):
        return sketch_lib.run_bm_plan(plan, entry_labels, entry_weights,
                                      labels, fold_tile=self.bm_fold_tile)

    def dispatches_per_iter(self, plan, fused_plan, request):
        return 0  # pure XLA — no pallas dispatches, whatever the request


class PallasEngine(FoldEngine):
    """Per-bucket tile kernels (the pre-fusion Pallas baseline; for
    bounded-VMEM large graphs use ``pallas_stream`` instead). Runs in
    interpret mode only: its kernels slice columns out of in-register
    values, which the TPU compiler refuses (``dynamic_slice``)."""

    name = "pallas"

    def mg_fold_tile(self, labels, weights, k):
        from repro.kernels.mg_sketch import ops as kops
        return kops.mg_fold_tile_pallas(labels, weights, k)

    def bm_fold_tile(self, labels, weights, init_label=None):
        from repro.kernels.mg_sketch import ops as kops
        return kops.bm_fold_tile_pallas(labels, weights, init_label)

    def mg_candidates(self, plan, fused_plan, entry_labels, entry_weights):
        s_k, s_v = sketch_lib.run_mg_plan(plan, entry_labels, entry_weights,
                                          fold_tile=self.mg_fold_tile)
        return sketch_lib.scatter_rows(plan, s_k, s_v)

    def mg_select(self, plan, fused_plan, entry_labels, entry_weights,
                  labels, seed, *, selection=None):
        # selection ignored: dense bucketed fold, gate-masked in lpa_move
        s_k, s_v = sketch_lib.run_mg_plan(plan, entry_labels, entry_weights,
                                          fold_tile=self.mg_fold_tile)
        return sketch_lib.select_best(plan, s_k, s_v, labels, seed)

    def mg_rescan(self, plan, fused_plan, entry_labels, entry_weights,
                  labels, seed, *, selection=None):
        # the second (re-scoring) scan is an XLA pass over the bucketed
        # round-0 tiles; only the MG fold itself dispatches kernels here
        s_k, _ = sketch_lib.run_mg_plan(plan, entry_labels, entry_weights,
                                        fold_tile=self.mg_fold_tile)
        return sketch_lib.rescan_candidates(plan, s_k, entry_labels,
                                            entry_weights, labels, seed)

    def bm_fold_plan(self, plan, fused_plan, entry_labels, entry_weights,
                     labels, *, selection=None):
        return sketch_lib.run_bm_plan(plan, entry_labels, entry_weights,
                                      labels, fold_tile=self.bm_fold_tile)

    def dispatches_per_iter(self, plan, fused_plan, request):
        if request.family == "bm":
            return plan_round0_dispatches(plan)  # one per round-0 bucket
        # mg, with or without rescan: one per bucket per round (the
        # rescan's second scan is XLA, not a kernel dispatch)
        return plan_dispatches(plan)


class PallasFusedEngine(FoldEngine):
    """Whole-round fused kernels — see kernels.mg_sketch.fused. MG, BM and
    the rescan second pass all run plan-level fused dispatches; the tile
    folds below are kept for ad-hoc tile-level callers only."""

    name = "pallas_fused"
    uses_fused_plan = True

    def mg_fold_tile(self, labels, weights, k):
        from repro.kernels.mg_sketch import ops as kops
        return kops.mg_fold_tile_pallas(labels, weights, k)

    def bm_fold_tile(self, labels, weights, init_label=None):
        from repro.kernels.mg_sketch import ops as kops
        return kops.bm_fold_tile_pallas(labels, weights, init_label)

    def mg_candidates(self, plan, fused_plan, entry_labels, entry_weights):
        from repro.kernels.mg_sketch.fused import run_mg_plan_fused
        _require_plan(fused_plan, 'pallas_fused', 'FusedFoldPlan')
        s_k, s_v = run_mg_plan_fused(fused_plan, entry_labels, entry_weights)
        return _scatter_padded_rows(fused_plan.n_nodes, fused_plan.k,
                                    fused_plan.row_to_vertex, s_k, s_v)

    def mg_select(self, plan, fused_plan, entry_labels, entry_weights,
                  labels, seed, *, selection=None):
        from repro.kernels.mg_sketch.fused import select_best_fused
        _require_plan(fused_plan, 'pallas_fused', 'FusedFoldPlan')
        return select_best_fused(fused_plan, entry_labels, entry_weights,
                                 labels, seed, selection=selection)

    def mg_rescan(self, plan, fused_plan, entry_labels, entry_weights,
                  labels, seed, *, selection=None):
        from repro.kernels.mg_sketch.fused import rescan_select_fused
        _require_plan(fused_plan, 'pallas_fused', 'FusedFoldPlan')
        return rescan_select_fused(fused_plan, entry_labels, entry_weights,
                                   labels, seed, selection=selection)

    def bm_fold_plan(self, plan, fused_plan, entry_labels, entry_weights,
                     labels, *, selection=None):
        from repro.kernels.mg_sketch.fused import run_bm_plan_fused
        _require_plan(fused_plan, 'pallas_fused', 'FusedFoldPlan')
        return run_bm_plan_fused(fused_plan, entry_labels, entry_weights,
                                 labels, selection=selection)

    def dispatches_per_iter(self, plan, fused_plan, request):
        if request.family == "bm":
            return 1  # the BM fold only ever walks round 0
        if request.rescan:
            # all fold rounds + one in-kernel rescan of round 0
            return fused_dispatches(fused_plan) + 1
        return fused_dispatches(fused_plan)  # n_rounds (last one selects)


def _scatter_padded_rows(n: int, k: int, row_to_vertex, s_k, s_v
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter padded per-row sketches to per-vertex candidate sets.

    ``row_to_vertex`` [rows] int32 (-1 on pad rows) maps each padded row of
    ``s_k``/``s_v`` [rows, k] to its owning vertex; pad rows land in a dump
    slot. Returns ([N, k] int32 candidate labels with -1 empties, [N, k]
    float32 weights). Shared by the fused and streaming engines.
    """
    safe = jnp.where(row_to_vertex >= 0, row_to_vertex, n)
    cand_c = jnp.full((n + 1, k), -1, jnp.int32).at[safe].set(s_k)[:n]
    cand_w = jnp.zeros((n + 1, k), jnp.float32).at[safe].set(s_v)[:n]
    return cand_c, cand_w


class PallasStreamEngine(FoldEngine):
    """HBM-streaming windowed kernels — see kernels.mg_sketch.streaming.

    Same dispatch structure as ``pallas_fused`` (one per round, the last
    fused with move selection) but each round's entries are streamed
    through fixed-size double-buffered VMEM windows, so per-step residency
    is O(window_entries) instead of O(|E|).
    """

    name = "pallas_stream"
    uses_stream_plan = True

    def mg_fold_tile(self, labels, weights, k):
        # tile-level callers share the per-bucket kernel; MG, BM and the
        # rescan second pass all stream plan-level windowed dispatches.
        from repro.kernels.mg_sketch import ops as kops
        return kops.mg_fold_tile_pallas(labels, weights, k)

    def bm_fold_tile(self, labels, weights, init_label=None):
        from repro.kernels.mg_sketch import ops as kops
        return kops.bm_fold_tile_pallas(labels, weights, init_label)

    def mg_candidates(self, plan, stream_plan, entry_labels, entry_weights):
        from repro.kernels.mg_sketch.streaming import run_mg_plan_stream
        _require_plan(stream_plan, 'pallas_stream', 'StreamedFoldPlan')
        s_k, s_v = run_mg_plan_stream(stream_plan, entry_labels,
                                      entry_weights)
        return _scatter_padded_rows(stream_plan.n_nodes, stream_plan.k,
                                    stream_plan.row_to_vertex, s_k, s_v)

    def run(self, bundle, request, entry_labels, entry_weights, labels):
        """:meth:`FoldEngine.run`; a ``marks`` request (packed round-0
        labels of an aligned plan, DESIGN.md §8.5) also returns each
        vertex's round-0 mark (``FoldOutcome.marked``)."""
        if not request.marks:
            return super().run(bundle, request, entry_labels, entry_weights,
                               labels)
        want, marked = self.mg_select(bundle.plan, bundle.aux_for(self),
                                      entry_labels, entry_weights, labels,
                                      request.seed, marks=True)
        return FoldOutcome(want=want, marked=marked)

    def mg_select(self, plan, stream_plan, entry_labels, entry_weights,
                  labels, seed, *, selection=None, marks=False):
        from repro.kernels.mg_sketch.streaming import select_best_stream
        _require_plan(stream_plan, 'pallas_stream', 'StreamedFoldPlan')
        return select_best_stream(stream_plan, entry_labels, entry_weights,
                                  labels, seed, selection=selection,
                                  marks=marks)

    def mg_rescan(self, plan, stream_plan, entry_labels, entry_weights,
                  labels, seed, *, selection=None):
        from repro.kernels.mg_sketch.streaming import rescan_select_stream
        _require_plan(stream_plan, 'pallas_stream', 'StreamedFoldPlan')
        return rescan_select_stream(stream_plan, entry_labels,
                                    entry_weights, labels, seed,
                                    selection=selection)

    def bm_fold_plan(self, plan, stream_plan, entry_labels, entry_weights,
                     labels, *, selection=None):
        from repro.kernels.mg_sketch.streaming import run_bm_plan_stream
        _require_plan(stream_plan, 'pallas_stream', 'StreamedFoldPlan')
        return run_bm_plan_stream(stream_plan, entry_labels, entry_weights,
                                  labels, selection=selection)

    def dispatches_per_iter(self, plan, stream_plan, request):
        if request.family == "bm":
            return 1  # one dispatch; the round-0 window grid lives inside
        if request.rescan:
            # all fold rounds + one windowed in-kernel rescan of round 0
            return streamed_dispatches(stream_plan) + 1
        return streamed_dispatches(stream_plan)  # n_rounds (last selects)


#: Concrete fold backends, resolvable by name. ``"auto"`` additionally
#: resolves to one of the last two per graph (see :func:`resolve_auto`).
ENGINES = ("jnp", "pallas", "pallas_fused", "pallas_stream")


def resolve_auto(n_entries: int,
                 vmem_budget_bytes: Optional[int] = None) -> str:
    """Pick ``pallas_fused`` vs ``pallas_stream`` for a graph.

    ``n_entries`` is the round-0 entry volume (= |E| directed CSR slots,
    units: entries); the fused engine keeps ``8 * n_entries`` bytes of flat
    entry arrays VMEM-resident, so it is selected only while that fits
    ``vmem_budget_bytes`` (default :data:`DEFAULT_VMEM_BUDGET_BYTES`).
    """
    budget = (DEFAULT_VMEM_BUDGET_BYTES if vmem_budget_bytes is None
              else vmem_budget_bytes)
    return ("pallas_fused" if n_entries * _BYTES_PER_ENTRY <= budget
            else "pallas_stream")


def _maybe_checked(engine: FoldEngine, checked: Optional[bool]) -> FoldEngine:
    """Wrap an engine in the checkify contract proxy when asked.

    ``checked=None`` defers to the ``REPRO_CHECKED`` env hook (how the
    parity suites opt every ``get_engine`` call in at once); the wrapper
    throws eagerly, so jitted drivers must pass ``checked=False``.
    """
    if checked is None:
        checked = os.environ.get("REPRO_CHECKED", "0").lower() \
            not in ("", "0", "false")
    if not checked:
        return engine
    from repro.core.checked import CheckedEngine
    return CheckedEngine(engine)


def get_engine(name: str, mg_variant: str = "paper", *,
               n_entries: Optional[int] = None,
               vmem_budget_bytes: Optional[int] = None,
               checked: Optional[bool] = None) -> FoldEngine:
    """Resolve a fold backend by config name.

    ``mg_variant='exact_weighted'`` is implemented on the jnp engine only;
    the Pallas engines always compute the paper's Alg. 2 rule.

    ``name="auto"`` picks ``pallas_fused`` vs ``pallas_stream`` from the
    round-0 entry volume ``n_entries`` against ``vmem_budget_bytes``
    (:func:`resolve_auto`); both the driver and ``build_workspace`` resolve
    with the same inputs, so the chosen engine always finds its plan.

    ``checked=True`` (or ``REPRO_CHECKED=1`` with ``checked=None``) wraps
    the engine in :class:`repro.core.checked.CheckedEngine`, which asserts
    the OOB/NaN contracts via jax.experimental.checkify on every fold —
    eager validation only; jitted callers pass ``checked=False``.
    """
    if name == "auto":
        if n_entries is None:
            raise ValueError("get_engine('auto') needs n_entries (the "
                             "round-0 entry volume) to resolve the policy")
        name = resolve_auto(n_entries, vmem_budget_bytes)
    if name == "jnp":
        return _maybe_checked(JnpEngine(mg_variant=mg_variant), checked)
    if name == "pallas":
        return _maybe_checked(PallasEngine(), checked)
    if name == "pallas_fused":
        return _maybe_checked(PallasFusedEngine(), checked)
    if name == "pallas_stream":
        return _maybe_checked(PallasStreamEngine(), checked)
    raise ValueError(f"unknown fold backend {name!r}; expected one of "
                     f"{ENGINES + ('auto',)}")
