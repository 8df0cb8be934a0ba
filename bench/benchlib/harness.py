"""One run of one cell: set-up, a timed window of solves, then the checks.

Everything is found by name. The cell (``workloads`` of ``BENCHMARK.json``)
names a configuration, ``bench/configs/<config>.json`` (a graph family and
its parameters), and a traffic mix, ``bench/mixes/<traffic>.json`` (the
``LPAConfig`` of one solve). Each metric the cell reports is read by
``bench/metrics/<metric>.py``, whose ``read(reading)`` returns a number or
None when the run has nothing for it. A later cell, graph or metric is a
new file; nothing here names one.

Steps of a run:

* set-up: the configuration's graph from the cache (generated first if
  the cache lacks it, which ``setup_s`` leaves out: generation is the
  benchmark's own cost, once per checkout), renumbered by the seed and
  placed on the device; ``build_workspace`` timed until its arrays are
  ready; then one whole warm solve, which compiles (or loads from the
  persistent compilation cache) every program the window runs;
* window: ``lpa(graph, config, ws=ws)`` to labels on the host, again and
  again from fresh labels, until a solve ends past ``seconds``; with
  ``trace`` the window is one whole solve under the profiler;
* after it: the peak device memory, then the program's state is freed and
  the plain host reference solves the same graph; every solve of the
  window must agree with it exactly: the labels, the iteration count and
  the number of vertices moved in each iteration.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from benchlib import graphs, reference

ROOT = os.path.dirname(graphs.BENCH_DIR)


class Bench:
    """The benchmark's files under ``root`` (the checkout's root)."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.dir = os.path.join(root, "bench")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _json(self, kind: str, name: str) -> dict:
        path = os.path.join(self.dir, kind, f"{name}.json")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind[:-1]} {name!r} ({path})")
        with open(path) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def mix(self, name: str) -> dict:
        return self._json("mixes", name)

    def metric_reader(self, name: str):
        path = os.path.join(self.dir, "metrics", f"{name}.py")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no reader for metric {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"metric_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def metrics_of(self, workload: str, trace: bool) -> list[dict]:
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec[kind]
                if workload in m.get("workloads", [workload])]

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.dir, "peaks.json")) as f:
            table = json.load(f)["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           "bench/peaks.json")
        return table[device_kind]


@dataclasses.dataclass
class Solve:
    labels: np.ndarray
    iterations: int
    changed_history: list


@dataclasses.dataclass
class Reading:
    """What a run measured; the metric readers take their numbers here."""

    graph: graphs.HostGraph
    setup_s: float
    plan_s: float
    window_s: float
    solves: list
    window_slots: int
    peak_bytes: Optional[int]
    peaks: dict
    trace: object = None          # trace.Summary of the traced solve
    jit_prep_s: float = 0.0       # host time JAX spent tracing/loading


class CompileWatch:
    """Counts what JAX traces, lowers, compiles or loads while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring as mon
        self.on = False
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if self.on and event in self.EVENTS:
            self.seconds += secs
            self.programs += event == self.EVENTS[2]

    def _event(self, event, **_):
        if self.on:
            self.cache_hits += event == "/jax/compilation_cache/cache_hits"
            self.cache_misses += \
                event == "/jax/compilation_cache/cache_misses"


def device_info(n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    peak = None
    stats = [d.memory_stats() for d in devs]
    if all(s and "peak_bytes_in_use" in s for s in stats):
        peak = max(int(s["peak_bytes_in_use"]) for s in stats)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def require_chips(n_chips: int) -> None:
    """Raises SystemExit(3) unless JAX finds ``n_chips`` TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n_chips:
        print(f"bench: JAX finds {len(devs)} {devs[0].platform} device(s); "
              f"this cell needs {n_chips} TPU chip(s)", file=sys.stderr)
        raise SystemExit(3)


def lpa_config(mix: dict):
    from repro.core import LPAConfig
    return LPAConfig(**mix["lpa_config"])


def reference_solve(g: graphs.HostGraph, mix: dict, **overrides):
    c = dict(mix["lpa_config"])
    if c.get("method", "mg") != "mg":
        raise ValueError("the reference covers method='mg' only")
    kw = dict(k=c.get("k", 8), chunk=c.get("chunk", 128), rho=c.get("rho", 8),
              tau=c.get("tau", 0.05), max_iters=c.get("max_iters", 20))
    kw.update(overrides)
    return reference.mg_lpa(g.offsets, g.indices, g.weights, **kw)


def compare(solves: list, ref) -> tuple[dict, int]:
    """Exact agreement of every solve with the reference: returns the
    numbers compared, each with its limit, and how many solves failed."""
    worst = {"labels_differing": 0, "iterations_off": 0,
             "moved_counts_differing": 0}
    failed = 0
    for s in solves:
        a, b = s.changed_history, ref.changed_history
        length = max(len(a), len(b))
        pad = lambda h: list(h) + [-1] * (length - len(h))  # noqa: E731
        got = {"labels_differing": int((s.labels != ref.labels).sum()),
               "iterations_off": abs(s.iterations - ref.iterations),
               "moved_counts_differing": int(sum(
                   x != y for x, y in zip(pad(a), pad(b))))}
        failed += any(got.values())
        worst = {k: max(worst[k], got[k]) for k in worst}
    return {k: {"value": v, "limit": 0} for k, v in worst.items()}, failed


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, bench: Optional[Bench] = None,
        require_tpu: bool = True) -> dict:
    """One run; returns the result object the benchmark prints."""
    bench = bench or Bench()
    cell = bench.workload(workload)
    config, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    if require_tpu:
        require_chips(cell["chips"])
    import jax
    import jax.numpy as jnp
    from repro.core import build_workspace, lpa
    from repro.graphs.csr import CSRGraph, streamed_window_slots

    watch = CompileWatch()
    t_jax = time.perf_counter()  # JAX imported, the chips found
    g, gen_s = graphs.load_or_generate(config, seed, bench.dir)
    graph = CSRGraph(offsets=jnp.asarray(g.offsets),
                     indices=jnp.asarray(g.indices),
                     weights=jnp.asarray(g.weights),
                     n_nodes=g.n_nodes, n_edges=g.n_edges)
    jax.block_until_ready(graph)
    graph_s = time.perf_counter() - t_jax - gen_s
    cfg = lpa_config(mix)

    t0 = time.perf_counter()
    ws = jax.block_until_ready(build_workspace(graph, cfg))
    plan_s = time.perf_counter() - t0
    backend = ws.bundle.spec.backend
    want = mix.get("require_backend")
    if want and backend != want:
        raise RuntimeError(f"fold_backend resolved to {backend!r}, the mix "
                           f"needs {want!r}")
    window_slots = (streamed_window_slots(ws.stream_plan)
                    if ws.stream_plan is not None else 0)

    def solve() -> Solve:
        res = lpa(graph, cfg, ws=ws)
        return Solve(np.asarray(res.labels), res.iterations,
                     list(res.changed_history))

    t_warm = time.perf_counter()
    solve()  # warm: compiles or loads every program the window runs
    warm_s = time.perf_counter() - t_warm
    setup_s = time.perf_counter() - t_start - gen_s

    summary = None
    watch.on = True
    w0 = time.perf_counter()
    if trace:
        from benchlib import trace as tr
        with tempfile.TemporaryDirectory() as tdir:
            def traced():
                with jax.profiler.TraceAnnotation("bench/solve"):
                    return solve()
            solves = [tr.record(traced, tdir)]
            events = tr.extract(tdir)
        span = [e for e in events if e.name == "bench/solve"]
        summary = tr.Summary(events, span[0].start_ns, span[0].end_ns)
        window_s = summary.window_s
    else:
        solves = []
        while True:
            solves.append(solve())
            if time.perf_counter() - w0 >= seconds:
                break
        window_s = time.perf_counter() - w0
    watch.on = False

    dev = device_info(cell["chips"])
    del ws, graph
    gc.collect()

    ref = reference_solve(g, mix)
    checks, failed = compare(solves, ref)
    peaks = bench.peaks(dev["kind"]) if require_tpu else {}
    reading = Reading(graph=g, setup_s=setup_s, plan_s=plan_s,
                      window_s=window_s, solves=solves,
                      window_slots=window_slots,
                      peak_bytes=dev["memory_peak_bytes"], peaks=peaks,
                      trace=summary, jit_prep_s=watch.seconds)
    metrics = {}
    for m in bench.metrics_of(workload, trace):
        value = bench.metric_reader(m["name"])(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": failed == 0, "attempted": len(solves),
           "failed": failed, "metrics": metrics, "device": dev}
    if summary is not None:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(),
                            "idle_gaps": summary.idle_gaps()}
    print(f"bench: graph |V|={g.n_nodes} |E|={g.n_edges} generate_s={gen_s} "
          f"backend={backend} window_slots={window_slots} "
          f"solves={len(solves)} iterations={[s.iterations for s in solves]} "
          f"reference_iterations={ref.iterations} "
          f"window_jit_s={watch.seconds} window_programs={watch.programs} "
          f"window_cache_hits={watch.cache_hits} "
          f"window_cache_misses={watch.cache_misses} "
          f"setup: start_to_jax_s={t_jax - t_start} graph_s={graph_s} "
          f"plan_s={plan_s} warm_solve_s={warm_s}", file=sys.stderr)
    out["checks"] = checks
    return out
