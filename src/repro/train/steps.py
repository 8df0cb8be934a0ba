"""Train-step builders: loss -> grad -> (optionally compressed-allreduce)
-> AdamW, as a single jitted function."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compression import compressed_psum
from repro.optim.schedule import cosine_schedule


def make_train_step(loss_fn, peak_lr=3e-4, warmup=100, total=10000,
                    opt_cfg: AdamWConfig | None = None):
    """loss_fn(params, batch) -> scalar. Returns (init_fn, step_fn).

    step(params, opt_state, batch) -> (params, opt_state, metrics).
    Under pjit, gradient averaging across data shards is implicit in the
    partitioned autodiff (GSPMD inserts the reduce-scatter/all-reduce).
    """

    def init(params):
        return adamw_init(params)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        lr = cosine_schedule(opt_state["step"], peak_lr, warmup, total)
        params, opt_state, stats = adamw_update(grads, opt_state, params, lr,
                                                opt_cfg)
        return params, opt_state, {"loss": loss, "lr": lr, **stats}

    return init, step


def make_dp_train_step(loss_fn, mesh, axis_name="data", peak_lr=3e-4,
                       warmup=100, total=10000,
                       opt_cfg: AdamWConfig | None = None,
                       compress: bool = True):
    """Explicit data-parallel shard_map step with int8 error-feedback
    gradient all-reduce (the distributed-optimization trick measured in
    benchmarks/bench_compression.py).

    Params/opt state replicated; batch sharded on axis 0.
    step(params, opt_state, err, batch) -> (params, opt_state, err, metrics).
    """
    from jax.sharding import PartitionSpec as P

    def init(params):
        return adamw_init(params), jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def shard_body(params, opt_state, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis_name)
        if compress:
            grads, err = compressed_psum(grads, err, axis_name)
        else:
            grads = jax.lax.pmean(grads, axis_name)
        lr = cosine_schedule(opt_state["step"], peak_lr, warmup, total)
        params, opt_state, stats = adamw_update(grads, opt_state, params, lr,
                                                opt_cfg)
        return params, opt_state, err, {"loss": loss, "lr": lr, **stats}

    rep = P()
    dat = P(axis_name)
    step = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(rep, rep, rep, dat), out_specs=(rep, rep, rep, rep),
        check_vma=False)
    return init, jax.jit(step)
