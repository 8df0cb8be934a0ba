"""Shared benchmark utilities: timing, the paper-family graph suite, and
the working-set model used for the Fig. 7(d) memory comparison."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from typing import Callable, Dict

import numpy as np
import jax

from repro.core.lpa import LPAConfig, build_workspace
from repro.graphs.csr import CSRGraph, plan_padded_entries


_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_cpu_child(bench: str, code: str, devices: int,
                  timeout: int = 560):
    """Run ``code`` in a child process on ``devices`` virtual CPU devices
    and return the JSON its last stdout line carries.

    Benchmarks that simulate shards on host devices need a fresh process
    (the device count is fixed at JAX start-up). On a TPU host that child
    could only run pinned to the CPU — the parent already holds the chip —
    so there it refuses instead, and a failed child raises: ``run.py``
    turns either into an error row and a non-zero exit.
    """
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{bench} runs its shards as virtual CPU devices in a child "
            "process, which cannot share this host's TPU with the parent; "
            "run it on a CPU host, and the four-chip path with "
            "`python chip_smoke.py --four-chips`")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    if res.returncode != 0:
        raise RuntimeError(f"{bench} child failed: {res.stderr[-400:]}")
    return json.loads(res.stdout.strip().splitlines()[-1])


def time_fn(fn: Callable, *args, repeats: int = 3, warmup: int = 1) -> float:
    """Median wall-clock seconds of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def lpa_working_set_bytes(method: str, graph: CSRGraph,
                          config: LPAConfig) -> Dict[str, float]:
    """Analytic working set BEYOND the input graph (paper Fig. 7d
    accounting: 'memory used by the algorithm itself, including community
    labels', graph storage excluded).

      exact  : sort+segment intermediates — 6 M-sized arrays
               (sorted src/label/weight, group ids, group sums, rep labels)
               + labels/frontier O(V).                       ~ O(|E|)
      mg     : k-slot sketch label+weight arrays per final row + candidate
               scatter + labels/frontier.                    ~ O(k|V|)
      bm     : one (candidate, weight) carry per row + labels/frontier.
                                                             ~ O(|V|)
    These mirror ν-LPA's O(|E|) hashtables vs νMG8/νBM's O(|V|) sketches.
    """
    n, m = graph.n_nodes, graph.n_edges
    labels = 4 * n
    frontier = 1 * n
    if method == "exact":
        algo = m * (4 + 4 + 4) * 2  # sorted triples + segment intermediates
    elif method == "mg":
        k = config.k
        rows = n * 1.15  # final rows ~ vertices (chunk rows merge away)
        algo = rows * k * (4 + 4) * 2  # sketch (k,v) + candidate scatter
    elif method == "bm":
        algo = n * (4 + 4) * 2
    else:
        raise ValueError(method)
    return {"algo_bytes": float(algo + labels + frontier),
            "labels_bytes": float(labels)}


def measured_step_temp_bytes(graph: CSRGraph, config: LPAConfig) -> float:
    """Compiled temp-buffer bytes of one jitted LPA move step (XLA's own
    accounting of the working set — complements the analytic model)."""
    from repro.core.lpa import lpa_move
    import functools
    import jax.numpy as jnp
    ws = build_workspace(graph, config)
    step = jax.jit(functools.partial(lpa_move, config=config))
    labels = jnp.arange(graph.n_nodes, dtype=jnp.int32)
    lowered = step.lower(ws, labels, jnp.asarray(True), jnp.int32(1))
    mem = lowered.compile().memory_analysis()
    return float(mem.temp_size_in_bytes)


def fold_work_volume(graph: CSRGraph, config: LPAConfig) -> int:
    """Padded-entry count of the sketch fold — the hardware-independent
    work metric used where CPU wall-clock would mislead about TPU."""
    ws = build_workspace(graph, config)
    return plan_padded_entries(ws.plan)


def plan_build_seconds(graph: CSRGraph, config: LPAConfig) -> float:
    """Host wall-clock of one ``build_plan_bundle(graph, spec_for(config))``
    call — the one-time plan-construction cost a consumer pays before the
    first fold (DESIGN.md §15). Reported per benchmark row so plan-build
    regressions are visible next to the fold runtimes they amortize into."""
    from repro.core.plan_bundle import build_plan_bundle, spec_for
    t0 = time.perf_counter()
    build_plan_bundle(graph, spec_for(config))
    return time.perf_counter() - t0


def engine_list(spec: str | None = None) -> tuple:
    """Parse an ``--engines`` spec against the fold-engine registry.

    ``spec`` is ``None``/``"all"`` (every registered engine plus the
    ``auto`` policy) or a comma-separated subset (e.g.
    ``"jnp,pallas_stream"``). New backends registered in
    ``repro.core.fold_engine.ENGINES`` become benchable with no edits here.
    """
    from repro.core.fold_engine import ENGINES
    names = ENGINES + ("auto",)
    if spec in (None, "", "all"):
        return names
    chosen = tuple(s.strip() for s in spec.split(",") if s.strip())
    bad = [c for c in chosen if c not in names]
    if bad:
        raise ValueError(f"unknown engines {bad}; registered: {names}")
    return chosen


SKETCHES = ("mg", "bm", "rescan")


def sketch_list(spec: str | None = None) -> tuple:
    """Parse a ``--sketch`` spec: ``"all"`` / ``None`` (every sketch
    family) or a comma-separated subset of ``mg``/``bm``/``rescan``
    (``rescan`` is the MG double-scan ablation — it times
    ``FoldRequest(family="mg", rescan=True)`` routing, single-host and
    distributed). Selected sketches get the full ``--engines`` backend
    sweep; unselected ones are timed on the ``jnp`` reference only."""
    if spec in (None, "", "all"):
        return SKETCHES
    chosen = tuple(s.strip() for s in spec.split(",") if s.strip())
    bad = [c for c in chosen if c not in SKETCHES]
    if bad:
        raise ValueError(f"unknown sketches {bad}; expected {SKETCHES}")
    return chosen


LAYOUTS = ("unaligned", "aligned")


def layout_list(spec: str | None = None) -> tuple:
    """Parse a ``--layout`` spec: ``"all"`` / ``None`` (both CSR entry
    layouts) or a comma subset of ``unaligned``/``aligned``. The aligned
    layout (``LPAConfig(aligned_layout=True)``, DESIGN.md §13) only
    changes the streaming engine, so the sweep re-times the
    stream-capable backends (``pallas_stream`` and ``auto``) with the
    round-0 entries pre-materialized window-aligned — other backends get
    one row regardless of the spec."""
    if spec in (None, "", "all"):
        return LAYOUTS
    chosen = tuple(s.strip() for s in spec.split(",") if s.strip())
    bad = [c for c in chosen if c not in LAYOUTS]
    if bad:
        raise ValueError(f"unknown layouts {bad}; expected {LAYOUTS}")
    return chosen


def fold_engine_stats(graph: CSRGraph, config: LPAConfig) -> dict:
    """Static dispatch/traffic accounting of the MG fold engines.

    Dispatch counts and entry volumes are properties of the (static) fold
    plans, so they are exact without timing kernels:

      dispatches_per_iter_pallas : one pallas_call per width bucket per
        round — the ``O(rounds x buckets)`` the fused engine removes.
      dispatches_per_iter_fused  : one per round; the final dispatch also
        performs move selection, so a full MG iteration is <= n_rounds + 1
        device computations (folds + the [N] label scatter).
      dispatches_per_iter_stream : one per round, same as fused — the
        window grid lives inside each dispatch.
      padded_entries      : entry slots the bucketed engines materialize as
        HBM [R, D] tiles (pad lanes included) — plan_padded_entries.
      fused_hbm_entries   : entries the fused engine actually reads (pad
        lanes are masked in-register from (start, count) metadata).
      fused_resident_entry_bytes : flat entry bytes the fused engine keeps
        VMEM-resident on round 0 (8 bytes/entry) — the quantity the auto
        policy checks against the VMEM budget.
      stream_windows             : total window grid steps per iteration.
      stream_window_entries      : the widest round's window stride W.
      stream_window_slots        : windowed entry slots materialized per
        iteration (pads included) — the streamed re-layout's HBM cost.
      stream_gather_slots        : re-layout gather slots the default
        (unaligned) streamed plan materializes per iteration — every
        window slot is written once by the gather, O(|E|) of it on
        round 0 (graphs.csr.streamed_gather_slots).
      stream_gather_slots_aligned : the same count for the window-aligned
        plan (``aligned_layout=True``): round 0 is pre-materialized at
        build time, so only the tiny chunk-merge rounds still gather.
      stream_gather_bytes_saved_per_iter : HBM gather traffic the aligned
        layout removes each iteration — 8 bytes (int32 label + float32
        weight) per slot no longer re-laid out. This is the O(|E|)
        per-iteration round-trip the layout eliminates.
      stream_peak_resident_bytes : peak per-step entry residency of the
        streamed kernels (double-buffered label+weight window) — bounded
        by the config's ``stream_window``, independent of |E|.
      auto_engine                : what ``fold_backend="auto"`` resolves to
        for this graph under the config's VMEM budget.
      bm_dispatches_per_iter_*   : dispatch economics of the BM fold (one
        round-0-only pass): per round-0 width bucket on ``pallas``, ONE on
        ``pallas_fused``/``pallas_stream``.
      rescan_dispatches_per_iter_* : dispatch economics of the double-scan
        MG iteration (fold + in-engine second pass).

    All dispatch columns come from each engine's single request-keyed
    ``dispatches_per_iter(plan, aux_plan, request)`` (verified against
    the drivers by kernelcheck R3); the request ``mode`` never changes a
    count, so sparse rows share their dense column.
    """
    import dataclasses

    import numpy as np
    from repro.core.fold_engine import get_engine, resolve_auto
    from repro.core.fold_program import FoldRequest
    from repro.core.plan_bundle import build_plan_bundle, spec_for
    from repro.graphs.csr import (fused_hbm_entries,
                                  streamed_gather_slots,
                                  streamed_peak_window_bytes,
                                  streamed_window_slots)
    degrees = np.asarray(graph.degrees)
    # every engine's plan comes from the same build layer the drivers use
    # (DESIGN.md §15): one bundle per backend the stats compare
    base = spec_for(config)
    fused_b = build_plan_bundle(graph, dataclasses.replace(
        base, backend="pallas_fused", aligned=False))
    stream_b = build_plan_bundle(graph, dataclasses.replace(
        base, backend="pallas_stream", aligned=False))
    aligned_b = build_plan_bundle(graph, dataclasses.replace(
        base, backend="pallas_stream", aligned=True))
    plan = fused_b.plan
    fused_plan = fused_b.fused_plan
    stream_plan = stream_b.stream_plan
    aligned_plan = aligned_b.stream_plan
    gather_slots = streamed_gather_slots(stream_plan)
    gather_slots_aligned = streamed_gather_slots(aligned_plan)
    pallas = get_engine("pallas")
    fused = get_engine("pallas_fused")
    stream = get_engine("pallas_stream")
    mg_req = FoldRequest(family="mg")
    bm_req = FoldRequest(family="bm")
    rescan_req = FoldRequest(family="mg", rescan=True)
    return {
        "fold_rounds": plan.n_rounds,
        "dispatches_per_iter_pallas":
            pallas.dispatches_per_iter(plan, None, mg_req),
        "dispatches_per_iter_fused":
            fused.dispatches_per_iter(plan, fused_plan, mg_req),
        "dispatches_per_iter_stream":
            stream.dispatches_per_iter(plan, stream_plan, mg_req),
        "bm_dispatches_per_iter_pallas":
            pallas.dispatches_per_iter(plan, None, bm_req),
        "bm_dispatches_per_iter_fused":
            fused.dispatches_per_iter(plan, fused_plan, bm_req),
        "bm_dispatches_per_iter_stream":
            stream.dispatches_per_iter(plan, stream_plan, bm_req),
        "rescan_dispatches_per_iter_pallas":
            pallas.dispatches_per_iter(plan, None, rescan_req),
        "rescan_dispatches_per_iter_fused":
            fused.dispatches_per_iter(plan, fused_plan, rescan_req),
        "rescan_dispatches_per_iter_stream":
            stream.dispatches_per_iter(plan, stream_plan, rescan_req),
        "padded_entries": plan_padded_entries(plan),
        "fused_hbm_entries": fused_hbm_entries(fused_plan),
        "fused_resident_entry_bytes": 8 * int(degrees.sum()),
        "stream_windows": sum(r.n_windows for r in stream_plan.rounds),
        "stream_window_entries": max(
            (r.window_entries for r in stream_plan.rounds), default=0),
        "stream_window_slots": streamed_window_slots(stream_plan),
        "stream_gather_slots": gather_slots,
        "stream_gather_slots_aligned": gather_slots_aligned,
        "stream_gather_bytes_saved_per_iter":
            8 * (gather_slots - gather_slots_aligned),
        "stream_peak_resident_bytes":
            streamed_peak_window_bytes(stream_plan),
        "auto_engine": resolve_auto(int(degrees.sum()),
                                    config.vmem_budget_bytes),
    }


def suite(scale: str = "small"):
    from repro.graphs.generators import paper_suite
    return paper_suite(scale)
