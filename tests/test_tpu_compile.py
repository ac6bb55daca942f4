"""Compile rehearsals: the main path's Pallas kernels for a described v5e.

The TPU compiler is installed even where no chip is attached, and it
compiles for a topology that is only described.  These tests lower each
kernel of the system's path at the paper's widths (8 lanes, n = 100,
m_max = 132, C = 16 classes, the EMNIST CNN's parameters, megastep E = 8)
with ``interpret=False`` and compile it for one v5e chip, so a block shape
or an op that Mosaic refuses fails here and not on the chip.  Nothing runs.

Each kernel is compiled as called and under one more ``vmap`` (the suite's
scenario lanes, the trainer's lanes), which adds a blocked grid axis.

The topology is described inside a module-scoped fixture only: a test
worker that imports this file never loads the TPU library until one of
these tests starts.
"""
import os

import jax
import jax.numpy as jnp
import pytest

import repro.core.numerics  # noqa: F401  (x64 on, as in every program run)

LANES, N, M_MAX, CLASSES, CHUNK = 8, 100, 132, 16, 8
VMAP = 2  # outer lanes of the vmapped variants


def _maybe_vmap(fn, shapes, vmapped):
    """``fn`` and its argument shapes, or both with a leading vmap axis."""
    if not vmapped:
        return fn, shapes
    return jax.vmap(fn), [((VMAP,) + s, d) for s, d in shapes]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_for_chip(topo):
    """``compile_for_chip(fn, *shapes)``: lower ``fn`` for one v5e chip from
    ``(shape, dtype)`` pairs and compile it; returns the HLO text.  The
    persistent compilation cache is off meanwhile (an entry written for a
    described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("vmapped", (False, True))
def test_buzen_kernel_compiles(compile_for_chip, vmapped):
    from repro.kernels.buzen import buzen_pallas_batched

    fn, shapes = _maybe_vmap(
        lambda lr, lg: buzen_pallas_batched(lr, lg, M_MAX, interpret=False),
        [((LANES, N), jnp.float64), ((LANES,), jnp.float64)], vmapped)
    assert "tpu_custom_call" in compile_for_chip(fn, *shapes)


@pytest.mark.parametrize("vmapped", (False, True))
def test_buzen_classes_kernel_compiles(compile_for_chip, vmapped):
    from repro.kernels.buzen import buzen_classes_pallas_batched

    fn, shapes = _maybe_vmap(
        lambda lr, cnt, lg: buzen_classes_pallas_batched(
            lr, cnt, lg, M_MAX, interpret=False),
        [((LANES, CLASSES), jnp.float64), ((LANES, CLASSES), jnp.int64),
         ((LANES,), jnp.float64)], vmapped)
    assert "tpu_custom_call" in compile_for_chip(fn, *shapes)


@pytest.mark.parametrize("vmapped", (False, True))
def test_fused_update_kernel_compiles(compile_for_chip, vmapped):
    from repro.fl.models import cnn_classifier
    from repro.kernels.fused_update import fused_async_update

    params = jax.eval_shape(cnn_classifier(28, 10).init,
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = [(x.shape, x.dtype) for x in leaves]

    def step(scale, *flat):
        w = treedef.unflatten(flat[:len(leaves)])
        g = treedef.unflatten(flat[len(leaves):])
        return fused_async_update(w, g, scale, interpret=False)

    fn, shapes = _maybe_vmap(step, [((), jnp.float32)] + shapes + shapes,
                             vmapped)
    assert "tpu_custom_call" in compile_for_chip(fn, *shapes)


def _table_shapes(fscal_w, iscal_w):
    return [((LANES, M_MAX), jnp.float32)] + [((LANES, M_MAX), jnp.int32)] * 4 \
        + [((LANES, N), jnp.float32)] * 2 \
        + [((LANES, fscal_w), jnp.float32), ((LANES, iscal_w), jnp.int32)]


@pytest.mark.parametrize("has_cs,vmapped", ((False, False), (True, True)))
def test_event_step_kernel_compiles_f32(compile_for_chip, has_cs, vmapped):
    from repro.kernels.events import event_step_tables

    fn, shapes = _maybe_vmap(
        lambda *a: event_step_tables(*a, has_cs=has_cs, interpret=False),
        _table_shapes(4, 3), vmapped)
    assert "tpu_custom_call" in compile_for_chip(fn, *shapes)


@pytest.mark.parametrize("stop_on_update,vmapped",
                         ((False, False), (True, True)))
def test_megastep_kernel_compiles_f32(compile_for_chip, stop_on_update,
                                      vmapped):
    from repro.kernels.events import megastep_tables

    fn, shapes = _maybe_vmap(
        lambda *a: megastep_tables(*a, has_cs=True, chunk=CHUNK,
                                   stop_on_update=stop_on_update,
                                   interpret=False),
        _table_shapes(4 * CHUNK, 3 + CHUNK), vmapped)
    assert "tpu_custom_call" in compile_for_chip(fn, *shapes)


def test_event_kernels_refuse_f64_clock_by_name():
    """The compiled path names the float64 clock instead of handing Mosaic
    an op it cannot lower (no chip or topology needed: it fails in the
    wrapper, before lowering)."""
    from repro.kernels.events import event_step_tables, megastep_tables

    tbl = jnp.zeros((2, 5), jnp.float64)
    ints = jnp.zeros((2, 5), jnp.int32)
    rates = jnp.ones((2, 3), jnp.float64)
    with pytest.raises(NotImplementedError, match="float64"):
        event_step_tables(tbl, ints, ints, ints, ints, rates, rates,
                          jnp.zeros((2, 4)), jnp.zeros((2, 3), jnp.int32),
                          has_cs=False, interpret=False)
    with pytest.raises(NotImplementedError, match="float64"):
        megastep_tables(tbl, ints, ints, ints, ints, rates, rates,
                        jnp.zeros((2, 8)), jnp.zeros((2, 5), jnp.int32),
                        has_cs=False, chunk=2, interpret=False)
