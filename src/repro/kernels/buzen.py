"""Pallas TPU kernel for Buzen's convolution algorithm (Proposition 15).

This is the paper's algorithmic inner loop: the routing/concurrency
optimizer re-evaluates the normalization constants ``Z_{n, 0..m}`` at every
Adam step, for every candidate concurrency.  The DP is sequential over
stations but fully vectorizable over the population dimension ``m`` (lane
axis) *and* over the batch of routing vectors (grid axis) — a natural TPU
layout:

  * grid ``(B, n_stations)``: batch rows are independent (``parallel``
    semantics), stations run the sequential recursion (``arbitrary``);
  * the running log-constant row ``U[0..m]`` lives in a ``(1, W)`` VMEM
    scratch across the station axis (``W`` = ``m + 1`` rounded up to whole
    128-lane vregs), initialized from the aggregated infinite-server
    Poisson factor at station 0 of each row; the per-row loads sit in SMEM;
  * each station performs the log-space truncated convolution
    ``U'[m] = logsumexp_k (k * log_rho_i + U[m - k])`` as a single
    ``(W, W)`` masked reduction in VMEM (256 KB at m = 132), its shift
    matrix built by one strided lane roll rather than a gather.

Public entry points:

  * :func:`buzen_pallas_batched` — raw float32 kernel, ``[B, S] -> [B, m+1]``;
    compiled on a TPU, interpreted on any other backend.
  * :func:`buzen_log_Z_batched` — differentiable wrapper: float32 Pallas
    forward, VJP through the float64 ``jnp`` reference DP (the kernel itself
    has no autodiff rule), so the batched optimizer can run on this backend.
  * :func:`buzen_pallas` — single-row compatibility wrapper (``B = 1``).

Validated in interpret mode against ``repro.core.buzen`` (itself validated
against brute-force state enumeration) in ``tests/test_kernels.py`` and
``tests/test_batched_optimizer.py``.
"""
from __future__ import annotations
# contract: padded-n — reductions here are on the bitwise padding contract

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import BLOCK0, default_interpret

NEG_INF = -1e30
LANES = 128


def _padded_width(m_max: int) -> int:
    """Lane width of the DP row: ``m_max + 1`` rounded up to whole vregs."""
    return -(-(m_max + 1) // LANES) * LANES


def _log_conv_row(u, coef_col, width: int):
    """``U'[m] = logsumexp_{k <= m} (coef[k] + U[m - k])`` on one row.

    ``u`` is the ``(1, width)`` running row and ``coef_col`` the
    ``(width, 1)`` station series (or anything broadcasting to it).  The
    shift matrix ``toe[k, m] = U[m - k]`` is one strided lane roll of the
    broadcast row (row ``k`` rolled by ``k``), so the convolution is a
    masked column reduction with no in-kernel gather.  Entries past
    ``m_max`` only ever feed columns past ``m_max``, which the wrapper
    slices off.
    """
    kk = jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
    mm = jax.lax.broadcasted_iota(jnp.int32, (width, width), 1)
    toe = pltpu.roll(jnp.broadcast_to(u, (width, width)), BLOCK0, 1,
                     stride=1, stride_axis=0)
    terms = jnp.where(kk <= mm, coef_col + toe, np.float32(NEG_INF))
    col_max = jnp.max(terms, axis=0, keepdims=True)
    # contract: allow(raw-reduction): logsumexp over the m-convolution axis within ONE station — the client/station axis is the kernel's sequential grid loop, and this f32 path is rtol-validated, not bitwise
    sumexp = jnp.sum(jnp.exp(terms - col_max), axis=0, keepdims=True)
    return col_max + jnp.log(sumexp)


def _buzen_kernel(rho_ref, init_ref, out_ref, u_scr, *, n_stations: int,
                  width: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        u_scr[...] = init_ref[...]  # aggregated IS Poisson factor row

    # geometric series of one single-server station: coef[k] = k log_rho
    k_col = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
    u_scr[...] = _log_conv_row(u_scr[...],
                               k_col.astype(jnp.float32) * rho_ref[0, i], width)

    @pl.when(i == n_stations - 1)
    def _finalize():
        out_ref[...] = u_scr[...]


def _init_rows(log_gamma_total, m_max: int, width: int):
    """``[B, 1, width]`` float32 Poisson factor rows of the aggregated IS
    stations (``k log gamma - log k!``), ``NEG_INF`` past ``m_max``.

    Both terms reach hundreds at ``k ~ m_max`` and mostly cancel, so the
    row is formed in the input's precision and rounded once to float32."""
    from ..core.buzen import _log_factorials

    k = jnp.arange(width, dtype=log_gamma_total.dtype)
    rows = (k[None, :] * log_gamma_total[:, None]
            - _log_factorials(width - 1).astype(k.dtype))
    rows = jnp.where(k <= m_max, rows, NEG_INF)
    return rows.astype(jnp.float32)[:, None, :]


def _dp_call(kernel, coef, init_rows, coef_spec, grid, width: int,
             interpret: bool):
    """The shared ``pallas_call`` of both Buzen kernels: one ``(1, width)``
    row per batch entry, stations on the sequential grid axis."""
    B = init_rows.shape[0]
    row = pl.BlockSpec((None, 1, width), lambda b, i: (b, BLOCK0, BLOCK0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[coef_spec, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B, 1, width), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, width), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(coef, init_rows)


@functools.partial(jax.jit, static_argnames=("m_max", "interpret"))
def buzen_pallas_batched(log_rho: jax.Array, log_gamma_total: jax.Array,
                         m_max: int, *,
                         interpret: Optional[bool] = None) -> jax.Array:
    """``log Z_{., 0..m_max}`` for a batch of networks.

    ``log_rho`` is ``[B, S]`` single-server log-loads (S stations per row —
    include the CS station as an extra column if modelled) and
    ``log_gamma_total`` is ``[B]`` aggregated infinite-server log-loads.
    Returns float32 ``[B, m_max + 1]``.
    """
    interp = default_interpret() if interpret is None else interpret
    B, n = log_rho.shape
    width = _padded_width(m_max)
    # load-0 stations (padded clients under the traced-n convention) arrive
    # as log_rho = -inf; clamp to the finite mask value so the kernel's
    # k * log_rho products stay NaN-free — the k >= 1 terms then underflow
    # to exactly 0 in the row logsumexp, making the station a convolution
    # identity, matching the jnp reference's masked geometric series
    rho32 = jnp.maximum(log_rho.astype(jnp.float32), NEG_INF)
    kernel = functools.partial(_buzen_kernel, n_stations=n, width=width)
    out = _dp_call(kernel, rho32[:, None, :],
                   _init_rows(log_gamma_total, m_max, width),
                   pl.BlockSpec((None, 1, n), lambda b, i: (b, BLOCK0, BLOCK0),
                                memory_space=pltpu.SMEM),
                   (B, n), width, interp)
    return out[:, 0, :m_max + 1]


def _reference_log_Z(log_rho: jax.Array, log_gamma_total: jax.Array,
                     m_max: int) -> jax.Array:
    """Float64 ``jnp`` DP on the same ``[B, S]``/``[B]`` layout — VJP donor
    for :func:`buzen_log_Z_batched` (matches ``core.buzen`` "aggregate")."""
    from ..core.buzen import _geometric_series, _log_conv, _poisson_series

    def one(lr_row, lg):
        logZ = _poisson_series(lg, m_max)

        def fold(carry, lr):
            return _log_conv(carry, _geometric_series(lr, m_max)), None

        logZ, _ = jax.lax.scan(fold, logZ, lr_row)
        return logZ

    return jax.vmap(one)(log_rho, log_gamma_total)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def buzen_log_Z_batched(log_rho: jax.Array, log_gamma_total: jax.Array,
                        m_max: int) -> jax.Array:
    """Differentiable batched Buzen DP: Pallas forward, reference VJP.

    Forward runs the float32 TPU kernel (interpret fallback off-TPU) and
    casts back to the input dtype; the backward pass differentiates the
    float64 ``jnp`` recursion at the same primal point, so ``jax.grad``
    through the routing optimizer works on this backend.
    """
    out = buzen_pallas_batched(log_rho, log_gamma_total, m_max)
    return out.astype(log_rho.dtype)


def _buzen_log_Z_fwd(log_rho, log_gamma_total, m_max):
    return (buzen_log_Z_batched(log_rho, log_gamma_total, m_max),
            (log_rho, log_gamma_total))


def _buzen_log_Z_bwd(m_max, residuals, g):
    log_rho, log_gamma_total = residuals
    _, vjp = jax.vjp(
        lambda lr, lg: _reference_log_Z(lr, lg, m_max), log_rho,
        log_gamma_total)
    g_lr, g_lg = vjp(g.astype(log_rho.dtype))
    # padded (load-0) stations enter as log_rho = -inf: the forward value
    # does not depend on them (their geometric factor is the convolution
    # identity), so pin their partials to exactly 0 rather than whatever
    # the -inf arithmetic of the masked series propagated
    return jnp.where(jnp.isfinite(log_rho), g_lr, 0.0), g_lg


buzen_log_Z_batched.defvjp(_buzen_log_Z_fwd, _buzen_log_Z_bwd)


def buzen_pallas(log_rho: jax.Array, log_gamma_total: jax.Array, m_max: int,
                 *, interpret: Optional[bool] = None) -> jax.Array:
    """Single-network compatibility wrapper: ``[n] -> [m_max + 1]``."""
    return buzen_pallas_batched(log_rho[None, :],
                                jnp.asarray(log_gamma_total)[None], m_max,
                                interpret=interpret)[0]


# ---------------------------------------------------------------------------
# class-space kernel: one grid step folds a whole client CLASS
# ---------------------------------------------------------------------------

def _buzen_classes_kernel(series_ref, init_ref, out_ref, u_scr, *,
                          n_stations: int, width: int):
    """Station ``i`` convolves the running row with a PRECOMPUTED series.

    Identical control flow to :func:`_buzen_kernel`, but the station factor
    is the negative-binomial series of a whole class (``count`` identical
    single-server stations folded analytically) instead of the geometric
    series of one client — the grid is ``(B, C)``, not ``(B, n)``, which is
    what makes population size a free variable on this backend.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        u_scr[...] = init_ref[...]  # aggregated IS Poisson factor row

    # the (1, width) series row as a (width, 1) column: a diagonal select
    # and lane reduction (one non-zero term per row, so exact)
    kk = jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
    mm = jax.lax.broadcasted_iota(jnp.int32, (width, width), 1)
    # contract: allow(raw-reduction): one-hot diagonal extraction — exactly one non-zero term per row, bitwise under any association
    series_col = jnp.sum(jnp.where(kk == mm, series_ref[...], np.float32(0)),
                         axis=1, keepdims=True)
    u_scr[...] = _log_conv_row(u_scr[...], series_col, width)

    @pl.when(i == n_stations - 1)
    def _finalize():
        out_ref[...] = u_scr[...]


@functools.partial(jax.jit, static_argnames=("m_max", "interpret"))
def buzen_classes_pallas_batched(log_rho: jax.Array, counts: jax.Array,
                                 log_gamma_total: jax.Array, m_max: int, *,
                                 interpret: Optional[bool] = None
                                 ) -> jax.Array:
    """``log Z_{., 0..m_max}`` for a batch of CLASS-aggregated networks.

    ``log_rho``/``counts`` are ``[B, S]`` per-member single-server
    log-loads and class multiplicities (append the CS station as a count-1
    column if modelled); ``log_gamma_total`` the ``[B]`` aggregated
    infinite-server log-loads.  Each grid step folds a whole class through
    its negative-binomial generating series

        ``coef[j] = j log_rho + log C(j + count - 1, j)``

    precomputed outside the kernel in the input's precision and rounded
    once to float32 (``j = 0`` pinned to ``0``; ``count = 0`` padded
    classes clamp to the mask value, making them exact convolution
    identities as in the ``jnp`` DP).  Returns float32 ``[B, m_max + 1]``.
    Forward-only — differentiate through
    :func:`buzen_classes_log_Z_batched`.
    """
    from ..core.buzen import _log_multisets

    interp = default_interpret() if interpret is None else interpret
    B, S = log_rho.shape
    width = _padded_width(m_max)
    k = jnp.arange(width, dtype=log_rho.dtype)
    lr = jnp.maximum(log_rho, NEG_INF)
    series = (k[None, None, :] * lr[:, :, None]
              + _log_multisets(counts, width - 1, log_rho.dtype))
    series = jnp.where(k[None, None, :] == 0, 0.0,
                       jnp.maximum(series, NEG_INF))
    # terms past m_max only reach columns past m_max: pin them finite
    series = jnp.where(k <= m_max, series, NEG_INF).astype(jnp.float32)
    series = series[:, :, None, :]

    kernel = functools.partial(_buzen_classes_kernel, n_stations=S,
                               width=width)
    out = _dp_call(kernel, series, _init_rows(log_gamma_total, m_max, width),
                   pl.BlockSpec((None, None, 1, width),
                                lambda b, i: (b, i, BLOCK0, BLOCK0)),
                   (B, S), width, interp)
    return out[:, 0, :m_max + 1]


def _reference_class_log_Z(log_rho: jax.Array, counts: jax.Array,
                           log_gamma_total: jax.Array,
                           m_max: int) -> jax.Array:
    """Float64 ``jnp`` class DP on the ``[B, S]``/``[B]`` layout — VJP
    donor for :func:`buzen_classes_log_Z_batched` (matches
    ``core.buzen.class_log_normalizing_constants``)."""
    from ..core.buzen import _log_conv, _negbinom_series, _poisson_series

    def one(lr_row, cnt_row, lg):
        logZ = _poisson_series(lg, m_max)

        def fold(carry, xs):
            lr, cnt = xs
            return _log_conv(carry, _negbinom_series(lr, cnt, m_max)), None

        logZ, _ = jax.lax.scan(fold, logZ, (lr_row, cnt_row))
        return logZ

    return jax.vmap(one)(log_rho, counts, log_gamma_total)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def buzen_classes_log_Z_batched(log_rho: jax.Array, counts: jax.Array,
                                log_gamma_total: jax.Array,
                                m_max: int) -> jax.Array:
    """Differentiable batched class Buzen DP: Pallas forward, reference VJP.

    The class analogue of :func:`buzen_log_Z_batched`: float32 kernel
    forward, float64 ``jnp`` negative-binomial recursion for the backward
    pass.  ``counts`` are structural multiplicities — their partials are
    pinned to exactly 0.
    """
    out = buzen_classes_pallas_batched(log_rho, counts, log_gamma_total,
                                       m_max)
    return out.astype(log_rho.dtype)


def _buzen_classes_log_Z_fwd(log_rho, counts, log_gamma_total, m_max):
    return (buzen_classes_log_Z_batched(log_rho, counts, log_gamma_total,
                                        m_max),
            (log_rho, counts, log_gamma_total))


def _buzen_classes_log_Z_bwd(m_max, residuals, g):
    log_rho, counts, log_gamma_total = residuals
    _, vjp = jax.vjp(
        lambda lr, lg: _reference_class_log_Z(lr, counts, lg, m_max),
        log_rho, log_gamma_total)
    g_lr, g_lg = vjp(g.astype(log_rho.dtype))
    # padded (count-0) classes enter as log_rho = -inf with an identity
    # series: the forward value does not depend on them, so pin their
    # partials to exactly 0 (and counts are structural integers)
    mask = jnp.isfinite(log_rho) & (counts > 0)
    return (jnp.where(mask, g_lr, 0.0), jnp.zeros_like(g_lr), g_lg)


buzen_classes_log_Z_batched.defvjp(_buzen_classes_log_Z_fwd,
                                   _buzen_classes_log_Z_bwd)
