"""Chip smoke test: sketch LPA end to end on a TPU, through the public API.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # one four-chip host: shards=4 only

One chip (the default). Generates LDBC Graphalytics ``graph500-22``
(Graph500 Kronecker scale 22, edge factor 16, A/B/C = 0.57/0.19/0.19) from
``--seed``, runs ``lpa()`` with ``method="mg"``, ``fold_backend="auto"``
(which resolves to ``pallas_stream`` at this size) and the window-aligned
layout to convergence, and checks that

  * the lowered mover holds compiled Pallas kernels (``tpu_custom_call``),
    so a kernel in interpret mode cannot pass;
  * the mover marks the frontier in its round-0 pass (``mover_marks`` is
    ``iterations - 1``);
  * its labels, moved counts and frontier history are bit-identical to
    ``fold_backend="jnp"`` (whose frontier ``mark_frontier`` marks) on the
    same chip, and its modularity is at least 0.9 x the jnp run's.

``--four-chips`` runs only the four-chip path and what it is compared
with: ``lpa()`` with ``LPAConfig(shards=4)`` (the distributed loop over a
(4,) mesh with the halo label exchange, ``fold_backend="auto"`` resolved
to ``pallas_stream`` per shard, window-aligned), bit-identical to
single-chip ``lpa()`` on the same graph in labels, iterations and moved
counts; then ``dist_lpa`` with the all-gather exchange, bit-identical in
labels and iterations (Graph500 scale 20 unless ``--scale`` says
otherwise).

The lines before the last are readings; times are one smoke run each, not
benchmarks. The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check raises (non-zero exit); without a TPU the script exits 2
before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import LPAConfig, build_workspace, lpa  # noqa: E402
from repro.core import modularity  # noqa: E402
from repro.core.lpa import marks_in_mover, mover  # noqa: E402
from repro.graphs.csr import streamed_window_slots  # noqa: E402
from repro.graphs.generators import rmat  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

EDGE_FACTOR = 16  # graph500-22: Graph500 Kronecker, edge factor 16


def reading(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def require_labels_match(got, want, what: str) -> None:
    got, want = np.asarray(got), np.asarray(want)
    n_diff = int((got != want).sum())
    reading(f"{what} differing labels", n_diff)
    if n_diff:
        raise AssertionError(f"{what}: {n_diff} of {len(want)} labels differ")


def generate(scale: int, seed: int):
    t0 = time.perf_counter()
    g = rmat(scale, EDGE_FACTOR, seed=seed)
    reading("graph", f"graph500-{scale} seed={seed} |V|={g.n_nodes} "
            f"|E|={g.n_edges} (directed slots)")
    reading("generate_s", time.perf_counter() - t0)
    return g


def one_chip(scale: int, seed: int) -> None:
    g = generate(scale, seed)
    cfg = LPAConfig(method="mg", fold_backend="auto", aligned_layout=True)

    t0 = time.perf_counter()
    ws = jax.block_until_ready(build_workspace(g, cfg))
    reading("plan_build_s", time.perf_counter() - t0)
    backend = ws.bundle.spec.backend
    reading("auto resolved to", backend)
    if backend != "pallas_stream":
        raise AssertionError(f"auto resolved to {backend}, not pallas_stream")
    reading("streamed_window_slots", streamed_window_slots(ws.stream_plan))

    # the mover exactly as lpa() jits it (it marks the frontier, and takes
    # the previous changed mask and frontier donated); with the persistent
    # cache on, lpa()'s own compile of the same program is a cache read
    if not marks_in_mover(ws, cfg):
        raise AssertionError("the mover does not mark the frontier")
    move = jax.jit(mover(cfg, ws.bundle.cap_rows(), marks=True),
                   donate_argnames=("last",))
    labels0 = jnp.arange(g.n_nodes, dtype=jnp.int32)
    mask = jnp.zeros((g.n_nodes,), jnp.bool_)
    t0 = time.perf_counter()
    lowered = move.lower(ws, labels0, jnp.asarray(True), jnp.int32(1),
                         last=(mask, mask), last_pick_less=jnp.asarray(True))
    lowered.compile()
    reading("compile_s", time.perf_counter() - t0)
    if "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("the mover holds no compiled Pallas kernel")

    t0 = time.perf_counter()
    res = lpa(g, cfg, ws=ws)
    labels = np.asarray(res.labels)
    reading("e2e_s (smoke reading, not a benchmark)",
            time.perf_counter() - t0)
    q = float(modularity(g, res.labels))
    reading("iterations", res.iterations)
    reading("mover_marks", res.mover_marks)
    reading("modularity", q)
    reading("peak_bytes_in_use",
            jax.devices()[0].memory_stats()["peak_bytes_in_use"])
    if res.mover_marks != res.iterations - 1:
        raise AssertionError(f"the mover marked {res.mover_marks} of "
                             f"{res.iterations} iterations' frontiers")
    moved, frontier = res.changed_history, res.frontier_history
    del ws, res

    ref = lpa(g, LPAConfig(method="mg", fold_backend="jnp"))
    q_ref = float(modularity(g, ref.labels))
    reading("jnp iterations", ref.iterations)
    reading("jnp modularity", q_ref)
    require_labels_match(labels, ref.labels, "auto vs jnp")
    if moved != ref.changed_history:
        raise AssertionError(f"moved counts {moved}, jnp "
                             f"{ref.changed_history}")
    if frontier != ref.frontier_history:
        raise AssertionError(f"frontier history {frontier}, jnp "
                             f"{ref.frontier_history}")
    # at least 0.9 x the jnp run's modularity; written as a 10% margin so
    # that it also holds for a (tiny) negative Q of one giant community
    if q < q_ref - 0.1 * abs(q_ref):
        raise AssertionError(f"modularity {q} under the jnp run's {q_ref} "
                             "by more than 10%")


def four_chips(scale: int, seed: int) -> None:
    from repro.core.distributed import (build_dist_workspace, dist_lpa,
                                        dist_lpa_step)
    if len(jax.devices()) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found "
                             f"{len(jax.devices())}")
    g = generate(scale, seed)
    base = dict(method="mg", fold_backend="auto", aligned_layout=True)
    t0 = time.perf_counter()
    ref = lpa(g, LPAConfig(**base))
    reading("single-chip e2e_s (smoke reading, not a benchmark)",
            time.perf_counter() - t0)
    reading("single-chip iterations", ref.iterations)

    cfg = LPAConfig(**base, track_frontier=False, shards=4)
    t0 = time.perf_counter()
    ws = jax.block_until_ready(build_workspace(g, cfg))
    reading("halo plan_build_s", time.perf_counter() - t0)
    backend = ws.bundle.spec.backend
    reading("halo auto resolved to", backend)
    d = ws.dist
    step = jax.jit(dist_lpa_step(ws.mesh, d, engine=backend))
    hlo = step.lower(d.nbr_pos, d.weights, d.round_gathers,
                     d.final_row_vertex, d.init_labels, jnp.asarray(True),
                     jnp.int32(1), ws_arrays=d).as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("halo step holds no compiled kernel")
    t0 = time.perf_counter()
    res = lpa(g, cfg, ws=ws)
    reading("halo e2e_s (smoke reading, not a benchmark)",
            time.perf_counter() - t0)
    reading("halo iterations", res.iterations)
    reading("halo host_syncs", res.host_syncs)
    if res.iterations != ref.iterations:
        raise AssertionError(f"halo: {res.iterations} iterations, "
                             f"single-chip {ref.iterations}")
    if res.changed_history != ref.changed_history:
        raise AssertionError(f"halo: moved counts {res.changed_history}, "
                             f"single-chip {ref.changed_history}")
    require_labels_match(np.asarray(res.labels), ref.labels,
                         "halo vs single-chip")
    reading("halo modularity", float(modularity(g, res.labels)))
    mesh = ws.mesh
    del ws, d, res

    # the all-gather exchange, which only dist_lpa's callers build
    t0 = time.perf_counter()
    stream = backend == "pallas_stream"
    dw = build_dist_workspace(g, 4, halo=False, stream=stream,
                              fused=backend == "pallas_fused", aligned=stream)
    reading("all-gather plan_build_s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    labels, iters = dist_lpa(mesh, dw, engine=backend)
    reading("all-gather e2e_s (smoke reading, not a benchmark)",
            time.perf_counter() - t0)
    reading("all-gather iterations", iters)
    if iters != ref.iterations:
        raise AssertionError(f"all-gather: {iters} iterations, single-chip "
                             f"{ref.iterations}")
    require_labels_match(np.asarray(labels), ref.labels,
                         "all-gather vs single-chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only lpa(shards=4) vs single-chip")
    ap.add_argument("--scale", type=int, default=None,
                    help="Graph500 Kronecker scale (default 22 on one chip, "
                         "20 with --four-chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.scale is None:
        args.scale = 20 if args.four_chips else 22

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    reading("compile cache", f"{cache} ({warm} entries at start)")
    if args.four_chips:
        four_chips(args.scale, args.seed)
    else:
        one_chip(args.scale, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
