"""Distributed LPA shard-count scaling on host devices (subprocess): label
all-gather volume per iteration (THE collective of the design) and
equivalence to the single-device result.

``--sketch`` selects which sketch families run the scaling sweep
(``benchmarks.common.sketch_list``): ``mg`` (default), ``bm``, and
``rescan`` — the MG double-scan ablation, which ``dist_lpa`` routes
through the same static ``FoldRequest`` as the single-host mover
(DESIGN.md §14), so its rows assert bit-equality against single-host
``lpa(rescan=True)`` exactly like the plain MG rows do.
"""
from __future__ import annotations

_CODE = """
import json, time
import numpy as np, jax
from repro.graphs.generators import powerlaw_communities
from repro.core.distributed import build_dist_workspace, dist_lpa
from repro.core.lpa import lpa, LPAConfig
from repro.core.modularity import modularity
from repro.launch.mesh import make_mesh

SKETCHES = {sketches!r}
g, _ = powerlaw_communities(8192, p_in=0.5, mix=0.02, seed=1)
out = []
refs = {{}}
for sketch in SKETCHES:
    family = "mg" if sketch == "rescan" else sketch
    rescan = sketch == "rescan"
    ref = lpa(g, LPAConfig(method=family, rescan=rescan, rho=2))
    refs[sketch] = ref
    for p in (1, 2, 4, 8):
        mesh = make_mesh((p,), ("shard",))
        ws = build_dist_workspace(g, p)
        t0 = time.time()
        labels, iters = dist_lpa(mesh, ws, rho=2, method=family,
                                 rescan=rescan)
        dt = time.time() - t0
        out.append({{
            "shards": p,
            "method": sketch,
            "engine": "jnp",
            "iterations": iters,
            "runtime_s": round(dt, 3),
            "matches_single_device": bool(
                (np.asarray(labels) == np.asarray(ref.labels)).all()),
            "allgather_bytes_per_iter_per_dev": int(4 * ws.v_pad * p),
            "modularity": round(float(modularity(g, labels)), 4),
        }})
# fused engine parity at the max shard count (engines select uniformly;
# interpret-mode kernels make CPU wall-clock meaningless, so report only
# equivalence + dispatch count = one per fold round)
if "mg" in refs:
    ref = refs["mg"]
    p = 4
    mesh = make_mesh((p,), ("shard",))
    ws_f = build_dist_workspace(g, p, fused=True)
    labels_f, iters_f = dist_lpa(mesh, ws_f, rho=2, engine="pallas_fused")
    out.append({{
        "shards": p,
        "method": "mg",
        "engine": "pallas_fused",
        "iterations": iters_f,
        "matches_single_device": bool(
            (np.asarray(labels_f) == np.asarray(ref.labels)).all()),
        "fold_dispatches_per_iter": len(ws_f.round_gathers),
        "allgather_bytes_per_iter_per_dev": int(4 * ws_f.v_pad * p),
        "modularity": round(float(modularity(g, labels_f)), 4),
    }})
print(json.dumps(out))
"""


def run(scale: str = "small", sketches: str | None = None):
    from benchmarks.common import run_cpu_child, sketch_list
    chosen = sketch_list(sketches) if sketches else ("mg",)
    code = _CODE.format(sketches=tuple(chosen))
    rows = run_cpu_child("dist_lpa_scaling", code, devices=8)
    for r in rows:
        r["bench"] = "dist_lpa_scaling"
    return rows
