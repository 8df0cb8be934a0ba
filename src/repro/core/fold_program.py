"""The FoldRequest IR: one declarative description of a fold iteration.

The reproduction executes one algorithmic dataflow — fold neighbor votes
into a bounded sketch, then select — specialized per sketch family
(MG Alg. 2 / BM Alg. 3 / the double-scan rescan ablation) and per memory
regime (dense vs frontier-compacted sparse). Instead of one top-level
function per (family x mode x backend) cell, every consumer builds a
:class:`FoldRequest` and hands it to ``FoldEngine.run`` (DESIGN.md §14):

    request = FoldRequest(family="mg", mode="sparse", rescan=True,
                          frontier=marks, seed=seed, cap_rows=cap)
    outcome = engine.run(bundle, request, entry_labels,
                         entry_weights, labels)

where ``bundle`` is the :class:`repro.core.plan_bundle.PlanBundle` the
spec's plans were built into (DESIGN.md §15).

``run`` routes the request to the backend's family executor, threading a
:class:`RoundSelection` (the runtime half of the request: which rows or
windows to fold) into the kernel drivers, and returns a
:class:`FoldOutcome` whose ``want`` is always the per-vertex selection.

The request is built INSIDE the jitted mover — its static fields are
Python constants under trace, its traced fields (``seed``, ``frontier``)
are ordinary operands — so it never crosses a jit boundary and costs
nothing at runtime.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

__all__ = ["FAMILIES", "MODES", "FoldRequest", "RoundSelection",
           "FoldOutcome"]

#: sketch families a request can name (the rescan ablation is a flag on
#: the mg family, not a family of its own — it reuses the MG fold)
FAMILIES = ("mg", "bm")

#: execution modes: dense folds every plan row, sparse folds only the
#: frontier-compacted rows/windows
MODES = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class FoldRequest:
    """One fold iteration, declaratively: family + mode + traced payload.

    ``family``/``mode``/``rescan`` are static routing keys — ``run`` and
    ``dispatches_per_iter`` branch on them in Python. ``seed`` and
    ``frontier`` are the traced operands the selected executor consumes.
    """

    family: str = "mg"  # sketch family: "mg" | "bm" (FAMILIES)
    mode: str = "dense"  # "dense" | "sparse" (MODES): fold all rows or
    # only the frontier-compacted subset
    rescan: bool = False  # run the double-scan second pass (mg only)
    aligned: bool = False  # round-0 entries are pre-materialized
    # window-aligned (informational: the plan itself carries the layout)
    # tie-break seed for this iteration — scalar int32 (traced), or None
    # for families that never hash (bm)
    seed: Optional[Any] = None
    # active-vertex mask — [N] bool (traced); required in sparse mode,
    # ignored in dense mode
    frontier: Optional[Any] = None
    cap_rows: int = 0  # sparse compaction capacity (static): max active
    # rows/windows the compacted fold may touch
    marks: bool = False  # round-0 entry labels carry each neighbor's
    # "changed" bit (fused.pack_marks): the round-0 kernel strips it and
    # ORs it per row (FoldOutcome.marked; DESIGN.md §8.5)

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(
                f"unknown fold family {self.family!r}; expected one of "
                f"{FAMILIES}")
        if self.mode not in MODES:
            raise ValueError(
                f"unknown fold mode {self.mode!r}; expected one of {MODES}")
        if self.rescan and self.family != "mg":
            raise ValueError(
                "rescan=True is an MG-family ablation (the double scan "
                "re-scores the MG sketch); it does not compose with "
                f"family={self.family!r}")
        if self.mode == "sparse" and self.frontier is None:
            raise ValueError(
                "sparse mode needs a frontier (the compacted fold is "
                "defined by the active vertex set)")
        if self.marks and (self.family != "mg" or self.rescan
                           or self.mode != "dense" or not self.aligned):
            raise ValueError(
                "marks=True is the dense MG fold of an aligned round 0 "
                "(the only kernel that reads every row's packed labels)")


@dataclasses.dataclass(frozen=True)
class RoundSelection:
    """Which rows/windows a kernel driver folds this iteration.

    ``None`` in driver signatures means dense (all rows/windows); a
    selection carries the sparse half: the frontier mask the driver
    compacts into row/window indices, bounded by ``cap_rows``.
    """

    # active-vertex mask — [N] bool (traced); the driver compacts it into
    # row (fused) or window (stream) indices
    frontier: Any = None
    cap_rows: int = 0  # static compaction capacity (rows for the fused
    # driver, windows are derived from it by the stream driver)


@dataclasses.dataclass
class FoldOutcome:
    """What a routed fold iteration produced.

    ``want`` is always populated — for the BM family ``run`` resolves the
    (candidate, weight) carry into per-vertex wants itself, so consumers
    never re-implement the sentinel handling.
    """

    # per-vertex selected label — [N] int32
    want: Any = None
    # BM only: raw candidate per vertex (-1 empty sentinel) — [N] int32
    bm_label: Optional[Any] = None
    # BM only: surviving candidate weight — [N] float32
    bm_weight: Optional[Any] = None
    # marks requests only: some neighbor's packed "changed" bit was set
    # — [N] bool
    marked: Optional[Any] = None
