"""jit'd wrappers for the Pallas sketch-fold kernels.

Pads row counts to the tile size, dispatches to the kernel, and slices the
padding back off. Signatures match ``repro.core.sketch.{mg,bm}_fold_tile``
so either backend plugs into ``run_mg_plan`` / ``run_bm_plan`` unchanged.

``interpret`` defaults to True exactly when no TPU backs JAX
(``fused._interpret_default``).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels.mg_sketch.fused import _interpret_default
from repro.kernels.mg_sketch.mg_sketch import (bm_fold_pallas_call,
                                               mg_fold_pallas_call)

DEFAULT_TILE_R = 512


def _pad_rows(x: jnp.ndarray, tile_r: int, fill) -> jnp.ndarray:
    r = x.shape[0]
    pad = (-r) % tile_r
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((pad,) + x.shape[1:], fill, dtype=x.dtype)], axis=0)


def mg_fold_tile_pallas(labels: jnp.ndarray, weights: jnp.ndarray, k: int,
                        tile_r: int = DEFAULT_TILE_R,
                        interpret: bool | None = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[R, D] padded neighbor tiles -> [R, k] weighted MG sketches."""
    if interpret is None:
        interpret = _interpret_default()
    r = labels.shape[0]
    tile_r = min(tile_r, max(8, r))
    gl = _pad_rows(labels.astype(jnp.int32), tile_r, -1)
    gw = _pad_rows(weights.astype(jnp.float32), tile_r, 0.0)
    s_k, s_v = mg_fold_pallas_call(gl, gw, k, tile_r, interpret)
    return s_k[:r], s_v[:r]


def bm_fold_tile_pallas(labels: jnp.ndarray, weights: jnp.ndarray,
                        init_label: jnp.ndarray | None = None,
                        tile_r: int = DEFAULT_TILE_R,
                        interpret: bool | None = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[R, D] padded neighbor tiles + [R] incumbents -> [R] BM majority states."""
    if interpret is None:
        interpret = _interpret_default()
    r = labels.shape[0]
    tile_r = min(tile_r, max(8, r))
    if init_label is None:
        init_label = jnp.full((r,), -1, jnp.int32)
    gl = _pad_rows(labels.astype(jnp.int32), tile_r, -1)
    gw = _pad_rows(weights.astype(jnp.float32), tile_r, 0.0)
    gi = _pad_rows(init_label.astype(jnp.int32), tile_r, -1)
    ck, wk = bm_fold_pallas_call(gl, gw, gi, tile_r, interpret)
    return ck[:r], wk[:r]
