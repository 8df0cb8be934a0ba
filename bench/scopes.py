"""Records one traced solve of a cell and splits it by the program's names.

    python3 bench/scopes.py --workload graph500.mg8 --seed 7 \
        [--save events.json.gz]

Set-up as ``bench/run.py`` does it (the cell's graph, plan and one warm
solve), then two untraced solves and one solve under the profiler. Prints
one JSON object: the mover's device time per iteration by scope
(``lpa.gather``, ``lpa.relayout``, ``lpa.fold.r<i>``, ``lpa.apply``, and
what no scope covers, and the share the scopes cover), the device-idle
time by innermost host span (in the traced window and inside
``lpa.solve``), the host reads, and the traced solve's wall time against
the untraced ones.
``--save`` writes the events, each op of the mover with its ``op_name``
(``benchlib.spans.save``). Needs the cell's TPU chips.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

MOVER = "jit_lpa_move"


def mover_op_names(ws, cfg, labels) -> dict:
    """Instruction name -> ``op_name`` of the mover as ``lpa()`` compiles
    it (loaded from the persistent cache, which holds it by then): the
    TPU's op events carry no ``op_name`` of their own."""
    import jax
    import jax.numpy as jnp
    from benchlib import spans
    from repro.core.lpa import mover
    move = mover(cfg, ws.bundle.cap_rows())
    compiled = jax.jit(move, static_argnames=("sparse",)).lower(
        ws, labels, jnp.asarray(True), jnp.int32(1), frontier=None,
        sparse=False).compile()
    return spans.op_names_of(compiled.as_text())


def report(events, iterations: int) -> dict:
    """The split of one traced solve (events of ``spans.with_scopes``),
    in ms per iteration."""
    from benchlib import spans
    from benchlib import trace as tr
    per = 1e3 / iterations
    span = [e for e in events if e.name == "bench/solve"][0]
    s = tr.Summary(events, span.start_ns, span.end_ns)
    mods = [m for m in s.modules if tr.module_name(m.name) == MOVER]
    ops = [o for o in s.ops if any(m.plane == o.plane
                                   and m.start_ns <= o.start_ns < m.end_ns
                                   for m in mods)]
    by_scope = collections.Counter()
    unscoped = collections.Counter()
    for o in ops:
        names = spans.scopes_of(o.scope)
        if names:
            by_scope[names[0]] += o.dur_ns * 1e-9 * per
        else:
            unscoped[spans.instruction(o.name)] += o.dur_ns * 1e-9 * per
    ops_ms = sum(o.dur_ns for o in ops) * 1e-9 * per
    solve = [h for h in s.host if h.name == "lpa.solve"][0]
    in_solve = spans.idle_by_span(tr.Summary(events, solve.start_ns,
                                             solve.end_ns))
    return {
        "iterations": iterations,
        "move_ms": s.module_s(MOVER) * per,
        "move_ops_ms": ops_ms,
        "scope_ms": dict(sorted(by_scope.items())),
        "scoped_share_of_move_ops": (sum(by_scope.values()) / ops_ms
                                     if ops_ms else None),
        "unscoped_ms": dict(unscoped.most_common(12)),
        "idle_ms_by_span": {k or "(none)": v * per for k, v in
                            sorted(spans.idle_by_span(s).items())},
        "idle_in_solve_ms_by_span": {k: v * per for k, v in
                                     sorted(in_solve.items())},
        "host_syncs_per_iteration": spans.sync_count(s) / iterations,
        "busy_s": s.busy_s, "window_s": s.window_s,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--save", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchlib import graphs, harness, spans
    from benchlib import trace as tr
    from repro.core import build_workspace, lpa
    from repro.graphs.csr import CSRGraph
    from repro.launch.compile_cache import use_compile_cache
    bench = harness.Bench()
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = bench.workload(args.workload)
    harness.require_chips(cell["chips"])
    config, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    g, _ = graphs.load_or_generate(config, args.seed, bench.dir)
    graph = CSRGraph(offsets=jnp.asarray(g.offsets),
                     indices=jnp.asarray(g.indices),
                     weights=jnp.asarray(g.weights),
                     n_nodes=g.n_nodes, n_edges=g.n_edges)
    cfg = harness.lpa_config(mix)
    ws = jax.block_until_ready(build_workspace(graph, cfg))

    def solve():
        t0 = time.perf_counter()
        res = lpa(graph, cfg, ws=ws)
        labels = np.asarray(res.labels)
        return res, labels, time.perf_counter() - t0

    solve()  # warm
    plain = [solve() for _ in range(2)]
    with tempfile.TemporaryDirectory() as tdir:
        def traced():
            with jax.profiler.TraceAnnotation("bench/solve"):
                return solve()
        res, labels, traced_s = tr.record(traced, tdir)
        events = tr.extract(tdir)
    labels0 = jnp.arange(g.n_nodes, dtype=jnp.int32)
    events = spans.with_scopes(events,
                               {MOVER: mover_op_names(ws, cfg, labels0)})
    if args.save:
        spans.save(events, args.save)
    if not all((p[1] == labels).all() for p in plain):
        raise RuntimeError("a traced solve's labels differ from untraced")
    out = report(events, res.iterations)
    out.update(host_syncs=res.host_syncs,
               untraced_solve_s=[p[2] for p in plain],
               traced_solve_s=traced_s, workload=args.workload,
               seed=args.seed)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
