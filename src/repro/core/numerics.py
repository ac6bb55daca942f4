"""Numeric configuration for the queueing core.

The product-form normalization constants ``Z_{n,m}`` span hundreds of orders
of magnitude; the whole queueing core therefore runs in log space, and we
additionally enable float64 so that closed-form identities (e.g.
``sum_i E0[D_i] = m - 1``) hold to ~1e-12 in tests.

Model code is unaffected: all model/kernel modules request explicit dtypes
(bf16/f32), which x64 mode does not override.
"""
from __future__ import annotations
# contract: padded-n — reductions here are on the bitwise padding contract

import jax

jax.config.update("jax_enable_x64", True)

NEG_INF = -1e30  # used instead of -inf to keep gradients NaN-free


def array_module(x):
    """``numpy`` for a host (NumPy) array, else ``jax.numpy``: lets a
    helper hand back the kind of array it was given (the padding helpers,
    ``events.unpad_stats``)."""
    import numpy as np

    if isinstance(x, np.ndarray):
        return np
    import jax.numpy as jnp

    return jnp


def safe_log(x):
    import jax.numpy as jnp

    return jnp.log(jnp.maximum(x, 1e-300))


def seqsum(x, axis: int = -1):
    """Strictly left-to-right float sum along ``axis`` (a ``lax.scan``).

    ``jnp.sum`` lowers to an XLA reduce whose association may change with
    the array *length* (vectorized/unrolled reduction trees), so summing a
    zero-padded array is not guaranteed to reproduce the unpadded sum
    bitwise.  A sequential scan is: appended zeros satisfy ``carry + 0 ==
    carry`` exactly and the real elements keep their left-to-right
    association regardless of padding.  Used for every client-axis
    reduction on the padded traced-``n`` bitwise contract
    (``pad_network`` / ``tests/test_padded_n.py``); differentiable and
    vmap-compatible like any scan.
    """
    import jax.numpy as jnp

    x = jnp.moveaxis(jnp.asarray(x), axis, 0)
    carry, _ = jax.lax.scan(lambda c, v: (c + v, None),
                            jnp.zeros(x.shape[1:], x.dtype), x)
    return carry


def seqcumsum(x, axis: int = -1):
    """Strictly left-to-right inclusive prefix sum along ``axis``.

    The prefix analogue of :func:`seqsum`: ``jnp.cumsum`` may lower to a
    parallel (tree) scan whose association changes with array length, so a
    zero-padded prefix is not guaranteed bitwise equal to the unpadded one
    on every backend.  A sequential scan is — real entries keep their
    left-to-right association and trailing zeros repeat the running total
    exactly (so the last element doubles as a padding-stable ``seqsum``).
    """
    import jax.numpy as jnp

    x = jnp.moveaxis(jnp.asarray(x), axis, 0)

    def step(c, v):
        c = c + v
        return c, c

    _, out = jax.lax.scan(step, jnp.zeros(x.shape[1:], x.dtype), x)
    return jnp.moveaxis(out, 0, axis)
