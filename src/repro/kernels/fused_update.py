"""Pallas TPU fused Generalized-AsyncSGD update.

CS-side update (Algorithm 1, line 6): ``w <- w - (eta / (n p_C)) g`` fused
with the squared-gradient-norm reduction used for staleness/clipping
telemetry — one HBM pass over (w, g) instead of two (update + norm).

Tiling: the flat parameter stream is viewed as ``[rows, 128]`` and walked in
``(block // 128, 128)`` VMEM tiles; each tile's norm contribution is its
column sums, a ``(1, 128)`` row of a per-tile partial-sum output that the
wrapper reduces (deterministic tree reduction).  Every block spans whole
trailing dimensions or whole ``(8, 128)`` tiles, so the kernel also
compiles under ``vmap`` (the trainer's lane axis).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import BLOCK0, default_interpret

LANES = 128


def _update_kernel(scale_ref, w_ref, g_ref, out_ref, norm_ref):
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    out_ref[...] = (w - scale_ref[BLOCK0, BLOCK0] * g).astype(out_ref.dtype)
    norm_ref[...] = jnp.sum(g * g, axis=0, keepdims=True)


def fused_async_update_flat(w: jax.Array, g: jax.Array, scale: jax.Array,
                            *, block: int = 4096,
                            interpret: Optional[bool] = None):
    """w, g: flat [N]. Returns (w_new [N], sum(g^2) scalar f32).

    ``block`` (elements per tile) must be a multiple of ``8 * 128``.
    """
    interpret = default_interpret() if interpret is None else interpret
    N = w.shape[0]
    n_blocks = -(-N // block)
    rows = block // LANES
    pad = n_blocks * block - N
    wp = jnp.pad(w, (0, pad)).reshape(n_blocks * rows, LANES)
    gp = jnp.pad(g, (0, pad)).reshape(n_blocks * rows, LANES)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    tile = pl.BlockSpec((rows, LANES), lambda i: (i, BLOCK0))

    out, norms = pl.pallas_call(
        _update_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (BLOCK0, BLOCK0),
                         memory_space=pltpu.SMEM),
            tile,
            tile,
        ],
        out_specs=[
            tile,
            pl.BlockSpec((None, 1, LANES), lambda i: (i, BLOCK0, BLOCK0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks * rows, LANES), w.dtype),
            jax.ShapeDtypeStruct((n_blocks, 1, LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(scale_arr, wp, gp)
    return out.reshape(-1)[:N], jnp.sum(norms)


def fused_async_update(params, grads, scale, *,
                       interpret: Optional[bool] = None):
    """Pytree version: returns (new_params, grad_norm)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    gleaves = treedef.flatten_up_to(grads)
    new_leaves, total = [], jnp.zeros((), jnp.float32)
    for w, g in zip(leaves, gleaves):
        nw, sq = fused_async_update_flat(w.reshape(-1), g.reshape(-1), scale,
                                         interpret=interpret)
        new_leaves.append(nw.reshape(w.shape))
        total = total + sq
    return treedef.unflatten(new_leaves), jnp.sqrt(total)
