"""Small pure-JAX classifiers for the FL experiments (Appendix B.1).

The paper's EMNIST/KMNIST network: two 7x7 conv layers (20, 40 channels) with
ReLU, 2x2 max-pool, and a dense softmax head.  Implemented with
``lax.conv_general_dilated`` — no flax dependency.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Model(NamedTuple):
    init: Callable  # (rng) -> params
    apply: Callable  # (params, x) -> logits


def _dense_init(rng, fan_in, fan_out):
    k1, _ = jax.random.split(rng)
    # a Python float is weakly typed: the f32 draw stays f32 under x64,
    # where a NumPy float64 scale would promote the weights to f64
    scale = float(np.sqrt(2.0 / fan_in))
    return {"w": jax.random.normal(k1, (fan_in, fan_out), jnp.float32) * scale,
            "b": jnp.zeros((fan_out,), jnp.float32)}


def mlp_classifier(input_dim: int, num_classes: int,
                   hidden: tuple[int, ...] = (256, 128)) -> Model:
    sizes = (input_dim,) + hidden + (num_classes,)

    def init(rng):
        keys = jax.random.split(rng, len(sizes) - 1)
        return [_dense_init(k, a, b) for k, a, b in zip(keys, sizes[:-1], sizes[1:])]

    def apply(params, x):
        h = x.reshape(x.shape[0], -1)
        for layer in params[:-1]:
            h = jax.nn.relu(h @ layer["w"] + layer["b"])
        out = params[-1]
        return h @ out["w"] + out["b"]

    return Model(init, apply)


def cnn_classifier(image_size: int, num_classes: int,
                   channels: tuple[int, int] = (20, 40),
                   kernel: int = 7) -> Model:
    """The paper's EMNIST CNN (Appendix B.1)."""

    def init(rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        c1, c2 = channels
        w1 = jax.random.normal(k1, (kernel, kernel, 1, c1),
                               jnp.float32) * float(np.sqrt(2.0 / (kernel * kernel)))
        w2 = jax.random.normal(k2, (kernel, kernel, c1, c2),
                               jnp.float32) * float(np.sqrt(
                                   2.0 / (kernel * kernel * c1)))
        # SAME conv twice, then 2x2 pool
        flat = (image_size // 2) * (image_size // 2) * c2
        return {
            "conv1": {"w": w1, "b": jnp.zeros((c1,), jnp.float32)},
            "conv2": {"w": w2, "b": jnp.zeros((c2,), jnp.float32)},
            "head": _dense_init(k3, flat, num_classes),
        }

    def conv(x, w, b):
        out = jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(out + b)

    def apply(params, x):
        # the queueing core enables x64, so host batches arrive as float64;
        # conv (unlike matmul) refuses mixed dtypes — keep the model in f32
        h = conv(x.astype(params["conv1"]["w"].dtype),
                 params["conv1"]["w"], params["conv1"]["b"])
        h = conv(h, params["conv2"]["w"], params["conv2"]["b"])
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        h = h.reshape(h.shape[0], -1)
        return h @ params["head"]["w"] + params["head"]["b"]

    return Model(init, apply)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean cross entropy; labels may be [B] (classification) or [B, S] (LM)."""
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean(jnp.argmax(logits, axis=-1) == labels)
