"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel lives in ``<name>.py`` (pl.pallas_call + explicit BlockSpec VMEM
tiling) with its jitted wrapper in ``ops.py`` and pure-jnp oracle in
``ref.py``.  Every kernel compiles for the TPU when JAX's default backend is
one, and runs in ``interpret=True`` mode on any other backend (the CPU test
runs); :func:`default_interpret` is that one decision.
"""
import jax
import numpy as np

# Block index 0 for index maps.  With x64 on (``repro.core.numerics``) a bare
# Python 0 traces as int64, which Mosaic cannot lower.
BLOCK0 = np.int32(0)


def default_interpret() -> bool:
    """Interpret Pallas kernels unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


from . import ops, ref  # noqa: E402  (after default_interpret: they import it)

__all__ = ["BLOCK0", "default_interpret", "ops", "ref"]
