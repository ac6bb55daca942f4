"""Gradient-based optimization of routing and concurrency (Sections 5.3.2,
6.4, Appendices B.2 / J).

The routing vector lives on the simplex via the softmax reparameterization of
Appendix B.2 (``p = softmax(theta)``); objectives are minimized with Adam.
Gradients come from ``jax.grad`` through the log-space Buzen pipeline — tested
to agree with the paper's closed-form expressions (Theorem 2 Eq. 4,
Prop. 4 Eq. 12).

Concurrency ``m`` is discrete.  Two search modes are provided:

  * :func:`sequential_concurrency_search` — the paper's warm-started
    sequential search (Section 5.3.2): iterate m = start, start+1, ...,
    re-optimizing ``p`` from the previous optimum, stopping once the
    objective stops improving (with optional patience).  One jit compile
    *per candidate m* — kept as the reference implementation.
  * :func:`batched_concurrency_sweep` — the batched engine: ONE jitted
    Adam ``lax.scan`` optimizes routing for *all* candidate concurrencies
    (and optionally a batch of objective contexts, e.g. Pareto weights
    ``rho``) simultaneously.  Each scan step evaluates the padded log-space
    Buzen DP for the whole ``[B, n]`` routing batch
    (``repro.core.batched``), so the discrete search reduces to an argmin
    over the precomputed ``(p*, m)`` surface with zero per-``m``
    recompilation.
  * :func:`pruned_concurrency_sweep` — coarse-to-fine wrapper over the
    batched engine for paper-scale grids (n=100 / m_max=132), where the
    full-grid sweep's B-fold arithmetic starts to outweigh its
    zero-recompile win: a strided coarse pass plus a warm-started
    refinement around its winner evaluates ~2 sqrt(B) rows instead of B.

``time_optimal`` / ``joint_optimal`` use the batched engine by default
(``search="pruned"`` selects the coarse-to-fine variant,
``search="sequential"`` restores the legacy path).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import span
from . import numerics  # noqa: F401
from .buzen import ClassParams, NetworkParams, log_normalizing_constants
from .complexity import LearningConstants, round_complexity, wallclock_time
from .energy import PowerProfile, energy_complexity, joint_objective
from .jackson import throughput


@dataclasses.dataclass
class OptResult:
    p: jax.Array
    m: int
    value: float
    history: list


@dataclasses.dataclass
class SweepResult:
    """Full ``(p, m)`` surface from one batched sweep.

    ``p[b]`` is the optimized routing for concurrency ``m_grid[b]`` (and
    context ``ctx[b]`` if given); ``values[b]`` the final objective there.
    ``best`` is the argmin row repackaged as an :class:`OptResult` whose
    ``history`` is the ``(m, value)`` trace across the grid.
    """

    p: jax.Array          # [B, n]
    m_grid: np.ndarray    # [B]
    values: np.ndarray    # [B]
    best: OptResult


def _adam_minimize(loss_fn: Callable, theta0: jax.Array, steps: int, lr: float):
    """Plain Adam on unconstrained logits; jitted scan."""
    b1, b2, eps = 0.9, 0.999, 1e-8

    @jax.jit
    def run(theta0):
        def step(carry, t):
            theta, mu, nu = carry
            val, g = jax.value_and_grad(loss_fn)(theta)
            mu = b1 * mu + (1 - b1) * g
            nu = b2 * nu + (1 - b2) * g * g
            mu_hat = mu / (1 - b1 ** (t + 1.0))
            nu_hat = nu / (1 - b2 ** (t + 1.0))
            theta = theta - lr * mu_hat / (jnp.sqrt(nu_hat) + eps)
            return (theta, mu, nu), val

        init = (theta0, jnp.zeros_like(theta0), jnp.zeros_like(theta0))
        (theta, _, _), vals = jax.lax.scan(step, init, jnp.arange(steps, dtype=jnp.float64))
        return theta, vals

    return run(theta0)


def optimize_routing(
    objective: Callable[[jax.Array, int], jax.Array],
    n: int,
    m: int,
    *,
    steps: int = 400,
    lr: float = 0.05,
    p_init: Optional[jax.Array] = None,
) -> OptResult:
    """Minimize ``objective(p, m)`` over the simplex with softmax-Adam."""
    p0 = jnp.full((n,), 1.0 / n) if p_init is None else p_init
    theta0 = jnp.log(jnp.clip(p0, 1e-12))

    def loss(theta):
        p = jax.nn.softmax(theta)
        return objective(p, m)

    theta, vals = _adam_minimize(loss, theta0, steps, lr)
    p = jax.nn.softmax(theta)
    return OptResult(p=p, m=m, value=float(objective(p, m)), history=list(map(float, vals)))


def _run_staged(fn, *args):
    """``jax.jit(fn)(*args)`` through jit's staged API, one span of the
    current ``repro.obs.metrics`` registry per stage: ``optimize.lower``
    (trace and lower), ``optimize.compile`` (XLA compile or persistent-cache
    load) and ``optimize.run`` (the call, to ``block_until_ready``)."""
    with span("optimize.lower"):
        lowered = jax.jit(fn).lower(*args)
    with span("optimize.compile"):
        compiled = lowered.compile()
    with span("optimize.run"):
        out = compiled(*args)
        # dropped while the device runs, as a plain jit call drops them
        del lowered, compiled
        return jax.block_until_ready(out)


def _sharded_rows(solve, theta0, m_grid, ctx, B: int):
    """Run a row-local solver with its row axis split over local devices.

    Rows pad to a device multiple by repeating the last row (sliced back
    off the result).  ``solve(theta_rows, m_rows, ctx_rows)`` must be
    row-local — no cross-row reductions reach the outputs — so each shard
    computes exactly what it would single-device and the concatenated
    result is **bitwise** equal to the unsharded call.
    """
    from jax.sharding import PartitionSpec

    from ..compat import make_mesh, shard_map

    ndev = len(jax.devices())
    Bp = -(-B // ndev) * ndev

    def pad_rows(x):
        if x is None or Bp == B:
            return x
        reps = jnp.broadcast_to(x[-1:], (Bp - B,) + x.shape[1:])
        return jnp.concatenate([x, reps], axis=0)

    mesh = make_mesh((ndev,), ("lanes",))
    spec = PartitionSpec("lanes")
    fn = shard_map(solve, mesh, in_specs=(spec, spec, spec),
                   out_specs=(spec, spec))
    ps, vals = _run_staged(fn, pad_rows(theta0), pad_rows(m_grid),
                           pad_rows(ctx))
    return ps[:B], vals[:B]


def batched_concurrency_sweep(
    objective: Callable,
    params: NetworkParams,
    *,
    m_grid,
    ctx=None,
    steps: int = 400,
    lr: float = 0.05,
    p_init: Optional[jax.Array] = None,
    m_max: Optional[int] = None,
    backend: Optional[str] = None,
    shard: bool = False,
) -> SweepResult:
    """Optimize routing for every concurrency candidate in ONE jitted sweep.

    ``objective`` follows the padded protocol of ``repro.core.batched``:
    ``obj(p, m, logZ)`` (or ``obj(p, m, logZ, ctx_row)`` when ``ctx`` is
    given) with ``m`` traced and ``logZ`` the precomputed ``[m_max + 1]``
    log-constant row for ``p``.  The engine stacks ``B = len(m_grid)``
    softmax logits, computes the batched Buzen DP once per Adam step (one
    ``[B, m_max+1]`` evaluation, Pallas or jnp backend), and runs a single
    ``lax.scan`` whose summed loss decouples row-wise — elementwise Adam on
    a block-diagonal problem is exactly ``B`` independent Adam runs, minus
    the ``B`` recompiles.

    ``ctx`` optionally batches an extra per-row objective input (e.g. the
    Pareto weight ``rho``), so one sweep can also span strategy variants.

    ``params`` may be a :class:`ClassParams`: rows are then per-member
    routing over classes (the O(C) negative-binomial DP replaces the O(n)
    one), the simplex constraint ``sum_c count_c p_c = 1`` is enforced by a
    softmax over class *masses*, and padded (count-0) classes are masked
    out of the logits.

    ``shard=True`` splits the ``B`` rows across all local devices with
    ``shard_map`` (rows pad to a device multiple by repeating the last
    row).  Rows never interact — the Buzen DP, the objective and Adam are
    all row-local — so the sharded sweep is **bitwise** equal to the
    single-device one, at ``1/num_devices`` the per-device row count.
    """
    from .batched import (batch_class_log_normalizing_constants,
                          batch_log_normalizing_constants)

    m_grid = jnp.asarray(m_grid, dtype=jnp.int64)
    B = int(m_grid.shape[0])
    is_classes = isinstance(params, ClassParams)
    if is_classes:
        n = params.C
        cmask = np.asarray(params.count) > 0
        cnt_safe = jnp.where(jnp.asarray(cmask),
                             params.count.astype(jnp.float64), 1.0)
        n_total = float(np.asarray(params.count).sum())
    else:
        n = params.n
    m_top = int(jnp.max(m_grid))
    m_pad = m_top if m_max is None else m_max
    if m_pad < m_top:
        # jit'd gathers clamp out-of-range indices silently — fail loudly
        # instead of returning plausible-but-truncated objective values
        raise ValueError(
            f"m_max={m_pad} must cover max(m_grid)={m_top}; the padded "
            "objective must be built with the same m_max")
    obj_pad = getattr(objective, "m_max", None)
    if obj_pad is not None and obj_pad != m_pad:
        raise ValueError(
            f"objective was built with m_max={obj_pad} but this sweep pads "
            f"logZ to m_max={m_pad}; the paddings must match")

    if is_classes:
        # logits parameterize class masses q (sum 1); members share
        # p = q / count, and padded classes are pinned to -inf mass
        p0 = (jnp.full((n,), 1.0 / n_total) if p_init is None
              else jnp.asarray(p_init))
        q0 = params.count.astype(jnp.float64) * p0
        theta0 = jnp.log(jnp.clip(q0, 1e-12))
    else:
        p0 = (jnp.full((n,), 1.0 / n) if p_init is None
              else jnp.asarray(p_init))
        theta0 = jnp.log(jnp.clip(p0, 1e-12))
    if theta0.ndim == 1:
        theta0 = jnp.broadcast_to(theta0, (B, n))

    def to_p(thetas):
        if is_classes:
            th = jnp.where(jnp.asarray(cmask)[None, :], thetas, -jnp.inf)
            return jax.nn.softmax(th, axis=-1) / cnt_safe[None, :]
        return jax.nn.softmax(thetas, axis=-1)

    def row_values(thetas, m_rows, ctx_rows):
        ps = to_p(thetas)
        if is_classes:
            logZ = batch_class_log_normalizing_constants(params, ps, m_pad,
                                                         backend=backend)
        else:
            logZ = batch_log_normalizing_constants(params, ps, m_pad,
                                                   backend=backend)
        if ctx_rows is None:
            vals = jax.vmap(objective)(ps, m_rows, logZ)
        else:
            vals = jax.vmap(objective)(ps, m_rows, logZ, ctx_rows)
        return ps, vals

    def concurrency_sweep(theta0_rows, m_rows, ctx_rows):
        def loss(thetas):
            return jnp.sum(row_values(thetas, m_rows, ctx_rows)[1])

        theta, _ = _adam_minimize(loss, theta0_rows, steps, lr)
        return row_values(theta, m_rows, ctx_rows)

    # both paths jit the SAME function (scan + final evaluation as one
    # program, named concurrency_sweep in the profiler trace):
    # jit(f) == jit(shard_map(f)) bitwise, whereas an eager final
    # evaluation fuses differently in the last bit
    ctx = None if ctx is None else jnp.asarray(ctx)
    if shard:
        ps, vals = _sharded_rows(concurrency_sweep, theta0, m_grid, ctx, B)
    else:
        ps, vals = _run_staged(concurrency_sweep, theta0, m_grid, ctx)

    m_np = np.asarray(m_grid)
    vals_np = np.asarray(vals)
    b = int(np.argmin(vals_np))
    best = OptResult(p=ps[b], m=int(m_np[b]), value=float(vals_np[b]),
                     history=[(int(m), float(v))
                              for m, v in zip(m_np, vals_np)])
    return SweepResult(p=ps, m_grid=m_np, values=vals_np, best=best)


def pruned_concurrency_sweep(
    objective: Callable,
    params: NetworkParams,
    *,
    m_grid,
    ctx=None,
    coarse_stride: Optional[int] = None,
    min_full: int = 8,
    **kw,
) -> SweepResult:
    """Coarse-to-fine batched sweep: evaluate a strided subsample of the
    ``m`` grid first, then refine only between the coarse neighbours of the
    winner (warm-started from its routing).

    At paper scale the full-grid sweep trades per-``m`` recompiles for
    ``B``-fold more arithmetic per Adam step; pruning keeps the
    zero-recompile property (two compiles total: one coarse, one refine
    batch shape) while cutting the per-step batch to roughly
    ``2 sqrt(B)`` rows.  It assumes the optimized objective is well-behaved
    over ``m`` (unimodal up to the coarse stride) — the regime of the
    paper's wall-clock/joint objectives (Figs. 2/8) — and is cross-checked
    against the full sweep on small grids in
    ``tests/test_scenario.py``.  Grids of at most ``min_full`` points run
    the full sweep directly.

    ``ctx`` (per-row objective context) is subset alongside ``m_grid``;
    pruning treats the grid as a single monotone ``m`` axis, so product
    grids (e.g. ``pareto_sweep``'s rho-major layout) should use the full
    sweep per context instead.
    """
    m_np = np.asarray(m_grid, dtype=np.int64)
    if m_np.ndim != 1 or m_np.size == 0:
        raise ValueError(f"m_grid must be a non-empty 1-D grid, got shape "
                         f"{m_np.shape}")
    if not (np.diff(m_np) > 0).all():
        raise ValueError("pruned search needs a strictly increasing m_grid")
    B = int(m_np.size)
    # pin the logZ padding for every pass: the refine window's max m is
    # smaller than the full grid's, and an objective built for the full
    # grid would otherwise trip the sweep-side padding guard mid-search
    if kw.get("m_max") is None:
        kw["m_max"] = getattr(objective, "m_max", None) or int(m_np[-1])
    if B <= max(int(min_full), 1):
        return batched_concurrency_sweep(objective, params, m_grid=m_np,
                                         ctx=ctx, **kw)

    ctx_np = None if ctx is None else np.asarray(ctx)
    stride = (max(2, int(round(np.sqrt(B)))) if coarse_stride is None
              else max(2, int(coarse_stride)))
    coarse = np.unique(np.append(np.arange(0, B, stride), B - 1))

    def sub(idx):
        return (m_np[idx],
                None if ctx_np is None else jnp.asarray(ctx_np[idx]))

    mg, cx = sub(coarse)
    first = batched_concurrency_sweep(objective, params, m_grid=mg, ctx=cx,
                                      **kw)
    k = int(np.argmin(first.values))
    lo = int(coarse[max(k - 1, 0)])
    hi = int(coarse[min(k + 1, len(coarse) - 1)])
    refine = np.setdiff1d(np.arange(lo, hi + 1), coarse)

    ms = [first.m_grid]
    vals = [first.values]
    ps = [np.asarray(first.p)]
    if refine.size:
        mg2, cx2 = sub(refine)
        kw2 = dict(kw)
        kw2["p_init"] = first.p[k]  # warm start from the coarse winner
        second = batched_concurrency_sweep(objective, params, m_grid=mg2,
                                           ctx=cx2, **kw2)
        ms.append(second.m_grid)
        vals.append(second.values)
        ps.append(np.asarray(second.p))

    m_all = np.concatenate(ms)
    order = np.argsort(m_all)
    m_all = m_all[order]
    v_all = np.concatenate(vals)[order]
    p_all = np.concatenate(ps, axis=0)[order]
    b = int(np.argmin(v_all))
    best = OptResult(p=jnp.asarray(p_all[b]), m=int(m_all[b]),
                     value=float(v_all[b]),
                     history=[(int(m), float(v))
                              for m, v in zip(m_all, v_all)])
    return SweepResult(p=jnp.asarray(p_all), m_grid=m_all, values=v_all,
                       best=best)


def pareto_sweep(params: NetworkParams, consts, power, rhos, tau_star,
                 e_star, *, m_max: int, **kw
                 ) -> tuple[SweepResult, list[OptResult]]:
    """Trace the Eq.-18 time-energy frontier in ONE batched sweep.

    Optimizes the joint objective over the full ``rhos x (1..m_max)``
    product grid (``rho`` rides the ctx batch) and argmins per rho.
    Returns the raw :class:`SweepResult` (rows ordered rho-major, matching
    ``np.tile(m_cands, len(rhos))``) plus one :class:`OptResult` per rho
    whose ``history`` is that rho's ``(m, value)`` slice.
    """
    from .batched import make_joint_objective_padded

    m_cands = np.arange(1, m_max + 1)
    mm = jnp.asarray(np.tile(m_cands, len(rhos)))
    rr = jnp.asarray(np.repeat(np.asarray(rhos, dtype=np.float64),
                               len(m_cands)))
    sweep = batched_concurrency_sweep(
        make_joint_objective_padded(params, consts, power, tau_star, e_star,
                                    m_max), params,
        m_grid=mm, ctx=rr, m_max=m_max, **kw)
    vals = sweep.values.reshape(len(rhos), len(m_cands))
    per_rho = []
    for r_i in range(len(rhos)):
        b = r_i * len(m_cands) + int(np.argmin(vals[r_i]))
        per_rho.append(OptResult(
            p=sweep.p[b], m=int(sweep.m_grid[b]),
            value=float(sweep.values[b]),
            history=[(int(m), float(v)) for m, v in zip(m_cands, vals[r_i])]))
    return sweep, per_rho


def sequential_concurrency_search(
    objective: Callable[[jax.Array, int], jax.Array],
    n: int,
    *,
    m_start: int = 1,
    m_max: int = 256,
    steps: int = 400,
    lr: float = 0.05,
    patience: int = 2,
    p_init: Optional[jax.Array] = None,
) -> OptResult:
    """Sequential (m, p) optimization with warm starts (Section 5.3.2)."""
    best: Optional[OptResult] = None
    stale = 0
    p_warm = p_init
    trace = []
    for m in range(max(m_start, 1), m_max + 1):
        res = optimize_routing(objective, n, m, steps=steps, lr=lr, p_init=p_warm)
        trace.append((m, res.value))
        p_warm = res.p
        if best is None or res.value < best.value:
            best = res
            stale = 0
        else:
            stale += 1
            if stale >= patience:
                break
    best.history = trace
    return best


# ---------------------------------------------------------------------------
# canned objectives / strategies of Section 5.3
# ---------------------------------------------------------------------------

def _with_p(params: NetworkParams, p: jax.Array) -> NetworkParams:
    return params._replace(p=p)


def make_round_objective(params: NetworkParams, consts: LearningConstants):
    """Minimize K_eps — the 'Round-Optimized' strategy."""
    def obj(p, m):
        return round_complexity(_with_p(params, p), m, consts)
    return obj


def make_throughput_objective(params: NetworkParams):
    """Maximize lambda — the 'Max-Throughput' strategy (negated)."""
    def obj(p, m):
        return -throughput(_with_p(params, p), m)
    return obj


def make_time_objective(params: NetworkParams, consts: LearningConstants):
    """Minimize E0[tau_eps] — the paper's proposed strategy."""
    def obj(p, m):
        return wallclock_time(_with_p(params, p), m, consts)
    return obj


def make_energy_objective(params: NetworkParams, consts: LearningConstants,
                          power: PowerProfile):
    def obj(p, m):
        return energy_complexity(_with_p(params, p), m, consts, power)
    return obj


def make_joint_objective(params: NetworkParams, consts: LearningConstants,
                         power: PowerProfile, rho: float,
                         tau_star: float, e_star: float):
    """Eq. (18) normalized scalarization."""
    def obj(p, m):
        return joint_objective(_with_p(params, p), m, consts, power, rho,
                               tau_star, e_star)
    return obj


def time_optimal(params: NetworkParams, consts: LearningConstants,
                 m_max: Optional[int] = None, *, search: str = "batched",
                 **kw) -> OptResult:
    """(p*_tau, m*_tau): jointly time-optimal routing and concurrency.

    ``search``: ``"batched"`` (full-grid one-compile sweep, default),
    ``"pruned"`` (coarse-to-fine batched sweep — the paper-scale variant),
    or ``"sequential"`` (the paper's warm-started reference loop).
    """
    m_max = m_max or params.n + 32
    if search in ("batched", "pruned"):
        from .batched import make_time_objective_padded

        kw.pop("patience", None)  # full grid — no early stop to tune
        engine = (batched_concurrency_sweep if search == "batched"
                  else pruned_concurrency_sweep)
        res = engine(
            make_time_objective_padded(params, consts, m_max), params,
            m_grid=jnp.arange(2, m_max + 1), m_max=m_max, **kw)
        return res.best
    if search != "sequential":
        raise ValueError(f"unknown search mode: {search!r}; expected "
                         "'batched', 'pruned' or 'sequential'")
    return sequential_concurrency_search(
        make_time_objective(params, consts), params.n, m_start=2, m_max=m_max, **kw)


def time_optimal_classes(classes: ClassParams, consts: LearningConstants,
                         m_max: int, *, search: str = "batched",
                         **kw) -> OptResult:
    """Class-space ``time_optimal``: O(C) per Adam step instead of O(n).

    ``m_max`` is explicit (the per-client default ``n + 32`` would be
    absurd at ``n = 10^6``; concurrency is a deployment budget there).
    Returns per-member routing ``p`` (length ``C``) under the mass
    constraint ``sum_c count_c p_c = 1``.
    """
    from .batched import make_time_objective_classes

    if search not in ("batched", "pruned"):
        raise ValueError(f"unknown search mode: {search!r}; expected "
                         "'batched' or 'pruned'")
    engine = (batched_concurrency_sweep if search == "batched"
              else pruned_concurrency_sweep)
    res = engine(
        make_time_objective_classes(classes, consts, m_max), classes,
        m_grid=jnp.arange(2, m_max + 1), m_max=m_max, **kw)
    return res.best


def round_optimal(params: NetworkParams, consts: LearningConstants, m: int,
                  **kw) -> OptResult:
    return optimize_routing(make_round_objective(params, consts), params.n, m, **kw)


def max_throughput(params: NetworkParams, m: int, **kw) -> OptResult:
    return optimize_routing(make_throughput_objective(params), params.n, m, **kw)


def joint_optimal(params: NetworkParams, consts: LearningConstants,
                  power: PowerProfile, rho: float, tau_star: float,
                  e_star: float, m_max: Optional[int] = None, *,
                  search: str = "batched", **kw) -> OptResult:
    m_max = m_max or params.n + 32
    if search in ("batched", "pruned"):
        from .batched import make_joint_objective_padded

        kw.pop("patience", None)
        engine = (batched_concurrency_sweep if search == "batched"
                  else pruned_concurrency_sweep)
        m_grid = jnp.arange(1, m_max + 1)
        res = engine(
            make_joint_objective_padded(params, consts, power, tau_star,
                                        e_star, m_max), params,
            m_grid=m_grid, ctx=jnp.full(m_grid.shape, rho), m_max=m_max,
            **kw)
        return res.best
    if search != "sequential":
        raise ValueError(f"unknown search mode: {search!r}; expected "
                         "'batched', 'pruned' or 'sequential'")
    return sequential_concurrency_search(
        make_joint_objective(params, consts, power, rho, tau_star, e_star),
        params.n, m_start=1, m_max=m_max, **kw)
