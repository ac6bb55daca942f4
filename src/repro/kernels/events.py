"""Pallas TPU kernel for the closed-network event-engine hot path.

One event of the Fig. 1 / Fig. 6 dynamics = one call of
:func:`repro.core.events.step_event`: an argmin over the ``[m_max]``
finish-clock table, a masked phase/routing transition of the completed
slot, and up to two FIFO promotions (compute queue, CS queue).  All of it
is vectorizable over the table axis and embarrassingly parallel over
simulation *lanes* (seeds x strategy lanes x scenarios), which is exactly
the TPU layout of this kernel:

  * grid ``(K,)`` — one program per lane, ``parallel`` semantics;
  * the lane's five table rows (``finish``/``phase``/``client``/``seq``/
    ``disp_round``, each ``[m_max]``) live in VMEM blocks; the argmin and
    both FIFO picks are first-index reductions over ``broadcasted_iota``
    masks (no sequential scan over slots);
  * the phase promotion / routing / FIFO transition is fused into the same
    kernel as vectorized masked writes (one-hot ``where`` updates).

Randomness stays OUTSIDE the kernel: per-event service variates are drawn
by the registered timing law (``repro.scenario.laws.device_draw``) at unit
rate and the kernel rescales them by the completing client's rate
(``e / mu[c]``) — exact (bitwise) for the scale-family laws whose unit
draw is ``rate``-free (exponential, deterministic) and equal up to one
floating-point rescale otherwise (lognormal, hyperexponential).  The
dispatch-routing draw (``C ~ p``) and the draws whose rate is known before
the argmin (downlink of the re-dispatched task, CS service) are computed
entirely outside, bit-identical to the reference engine.

Like the Buzen kernel, the compiled path targets TPU and everything is
validated in ``interpret=True`` mode on CPU (``tests/test_sim_backends.py``)
against the jnp oracle (``repro.kernels.ref.event_step_oracle``) and the
reference engine; statistics accumulation (occupancy, energy, delay sums)
remains regular jnp around the kernel call (see
``repro.sim.batched_events``).
"""
from __future__ import annotations
# contract: padded-n — reductions here are on the bitwise padding contract

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import BLOCK0, default_interpret
from ..core import events as E
from ..core.buzen import NetworkParams
from ..core.numerics import seqsum
from ..scenario.laws import get_law

_BIG_SEQ = E._BIG_SEQ


# Constants inside the kernels are typed int32 / table-dtype explicitly: with
# x64 on, a bare Python int or float is a weak 64-bit value, which Mosaic
# cannot lower.  Integer reductions are max/min, never a sum (``jnp.sum``
# over int32 accumulates in int64 under x64).
_I32 = {name: np.int32(getattr(E, name))
        for name in ("DOWN", "COMP_WAIT", "COMP_SERV", "UP", "CS_WAIT",
                     "CS_SERV")}


def _first_index_min(values, idx, size: int):
    """First index attaining ``min(values)`` — the TPU-friendly argmin."""
    v_min = jnp.min(values)
    return v_min, jnp.min(jnp.where(values == v_min, idx, np.int32(size)))


def _any(mask):
    """``mask.any()`` as an int32 max reduction."""
    return jnp.max(mask.astype(jnp.int32)) > 0


def _one_event(tbl, mu_c, mu_u, rand, idx, cli, *,
               has_cs: bool, m_max: int, n: int):
    """One table transition at registers — the shared kernel body.

    ``tbl = (finish, phase, client, seq, disp)`` are the lane's loaded
    ``(1, m_max)`` rows, ``mu_c``/``mu_u`` its loaded ``(1, n)`` rate rows
    and ``rand = (e_up, e_comp, svc_down, svc_cs, c_new, seq_ctr, rnd)``
    the event's outside-drawn scalars and counters.  Returns the updated
    table rows plus the transition descriptors; :func:`_event_kernel`
    calls it once per launch, :func:`_megastep_kernel` ``chunk`` times per
    launch with keep-masked selects in between (identical primitives —
    the megastep trajectory is bitwise the single-step one).
    """
    finish, phase, client, seq, disp = tbl
    e_up, e_comp, svc_down, svc_cs, c_new, seq_ctr, rnd = rand
    P = _I32
    inf = np.array(np.inf, finish.dtype)

    def gather_i(table, j):
        # one-hot gather: exactly one slot matches, so the max is its value
        return jnp.max(jnp.where(idx == j, table, np.int32(-_BIG_SEQ - 1)))

    def gather_rate(row, c):
        # one-hot gather: exactly one client matches, so the max is its rate
        return jnp.max(jnp.where(cli == c, row, -inf))

    # -- the completing slot (parallel argmin over the clock table) ---------
    t_new, j = _first_index_min(finish, idx, m_max)
    onej = idx == j
    c = gather_i(client, j)
    ph = gather_i(phase, j)
    delay = rnd - gather_i(disp, j)

    is_down = ph == P["DOWN"]
    is_comp = ph == P["COMP_SERV"]
    is_up = ph == P["UP"]
    is_cs = ph == P["CS_SERV"]
    is_update = is_cs if has_cs else is_up
    new_round = rnd + is_update.astype(jnp.int32)

    svc_up = e_up / gather_rate(mu_u, c)
    svc_c = e_comp / gather_rate(mu_c, c)

    # -- fused phase promotion / routing of slot j --------------------------
    phase_j = jnp.where(
        is_down, P["COMP_WAIT"],
        jnp.where(is_comp, P["UP"],
                  jnp.where(is_update, P["DOWN"], P["CS_WAIT"])))
    finish_j = jnp.where(
        is_comp, t_new + svc_up,
        jnp.where(is_update, t_new + svc_down, inf))
    joins_fifo = (is_down | is_up) if has_cs else is_down
    seq_j = jnp.where(joins_fifo, seq_ctr, gather_i(seq, j))
    new_seq_ctr = seq_ctr + joins_fifo.astype(jnp.int32)
    client_j = jnp.where(is_update, c_new, c)
    disp_j = jnp.where(is_update, new_round, gather_i(disp, j))

    phase = jnp.where(onej, phase_j, phase)
    finish = jnp.where(onej, finish_j, finish)
    seq = jnp.where(onej, seq_j, seq)
    client = jnp.where(onej, client_j, client)
    disp = jnp.where(onej, disp_j, disp)

    # -- FIFO promotion at the compute station of client c ------------------
    promo_comp = is_down | is_comp
    serving_c = _any((phase == P["COMP_SERV"]) & (client == c))
    waiting_c = (phase == P["COMP_WAIT"]) & (client == c)
    vals = jnp.where(waiting_c, seq, np.int32(_BIG_SEQ))
    _, pick = _first_index_min(vals, idx, m_max)
    any_wait = _any(waiting_c)
    do_comp = promo_comp & ~serving_c & any_wait
    onep = (idx == pick) & do_comp
    phase = jnp.where(onep, P["COMP_SERV"], phase)
    finish = jnp.where(onep, t_new + svc_c, finish)

    if has_cs:
        # -- FIFO promotion at the CS single-server queue -------------------
        promo_cs = is_up | is_cs
        cs_waiting = phase == P["CS_WAIT"]
        vals_cs = jnp.where(cs_waiting, seq, np.int32(_BIG_SEQ))
        _, pick_cs = _first_index_min(vals_cs, idx, m_max)
        cs_busy = _any(phase == P["CS_SERV"])
        any_cs_wait = _any(cs_waiting)
        do_cs = promo_cs & ~cs_busy & any_cs_wait
        onec = (idx == pick_cs) & do_cs
        phase = jnp.where(onec, P["CS_SERV"], phase)
        finish = jnp.where(onec, t_new + svc_cs, finish)
    else:
        do_cs = jnp.zeros((), jnp.bool_)

    desc = (t_new, j, c, is_update, delay, new_seq_ctr, new_round, ph,
            do_comp, do_cs)
    return (finish, phase, client, seq, disp), desc


def _check_clock(dtype, interpret: bool) -> None:
    """Refuse a float64 finish-clock table on the compiled path.

    Mosaic has no float64, and the event engine keeps its clock in float64
    (``repro.core.numerics`` turns x64 on).  Until the clock moves to a
    32-bit representation the kernels compile only for a float32 table;
    interpret mode runs either.
    """
    if not interpret and jnp.dtype(dtype) == jnp.float64:
        raise NotImplementedError(
            "the pallas event backend cannot compile for the TPU: the event "
            "engine's finish-clock table is float64 and Mosaic has no "
            "float64.  Use backend='batched' (plain XLA) on the TPU, or "
            "interpret=True.")


def _lane_call(kernel, tables, rates, scalars, out_widths, interpret: bool):
    """One ``pallas_call`` over the ``K`` lanes of stacked task tables.

    Every ``[K, w]`` operand is viewed as ``[K, 1, w]`` so that a lane's
    ``(1, w)`` row block spans the whole of the last two dimensions (the
    TPU's block rule).  Tables ``[K, m_max]`` and rates ``[K, n]`` go to
    VMEM; the per-lane scalars in and the descriptors out live in SMEM.
    ``out_widths`` are the widths of the float time and int32 descriptor
    outputs.  Returns the five tables and the two outputs as ``[K, w]``.
    """
    finish = tables[0]
    K, m_max = finish.shape
    vmem = lambda w: pl.BlockSpec((None, 1, w),  # noqa: E731
                                  lambda k: (k, BLOCK0, BLOCK0))
    smem = lambda w: pl.BlockSpec((None, 1, w),  # noqa: E731
                                  lambda k: (k, BLOCK0, BLOCK0),
                                  memory_space=pltpu.SMEM)
    args = [a[:, None, :] for a in tables + rates + scalars]
    outs = pl.pallas_call(
        kernel,
        grid=(K,),
        in_specs=[vmem(a.shape[-1]) for a in tables + rates]
        + [smem(a.shape[-1]) for a in scalars],
        out_specs=[vmem(m_max)] * 5 + [smem(w) for w in out_widths],
        out_shape=[jax.ShapeDtypeStruct((K, 1, m_max), finish.dtype)]
        + [jax.ShapeDtypeStruct((K, 1, m_max), jnp.int32)] * 4
        + [jax.ShapeDtypeStruct((K, 1, out_widths[0]), finish.dtype),
           jax.ShapeDtypeStruct((K, 1, out_widths[1]), jnp.int32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(*args)
    return [o[:, 0, :] for o in outs]


def _event_kernel(finish_ref, phase_ref, client_ref, seq_ref, disp_ref,
                  mu_c_ref, mu_u_ref, fscal_ref, iscal_ref,
                  o_finish_ref, o_phase_ref, o_client_ref, o_seq_ref,
                  o_disp_ref, o_t_ref, o_int_ref, *,
                  has_cs: bool, m_max: int, n: int):
    tbl = (finish_ref[...],   # (1, m_max) float
           phase_ref[...],    # (1, m_max) int32
           client_ref[...], seq_ref[...], disp_ref[...])
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, m_max), 1)
    cli = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    rand = (fscal_ref[0, 0],   # e_up: unit-rate variate (see module doc)
            fscal_ref[0, 1],   # e_comp
            fscal_ref[0, 2],   # svc_down: drawn outside (rate pre-argmin)
            fscal_ref[0, 3],   # svc_cs
            iscal_ref[0, 0],   # c_new
            iscal_ref[0, 1],   # seq_ctr
            iscal_ref[0, 2])   # round

    tbl, desc = _one_event(tbl, mu_c_ref[...], mu_u_ref[...], rand, idx, cli,
                           has_cs=has_cs, m_max=m_max, n=n)
    finish, phase, client, seq, disp = tbl
    (t_new, j, c, is_update, delay, new_seq_ctr, new_round, ph,
     do_comp, do_cs) = desc

    o_finish_ref[...] = finish
    o_phase_ref[...] = phase
    o_client_ref[...] = client
    o_seq_ref[...] = seq
    o_disp_ref[...] = disp
    o_t_ref[0, 0] = t_new
    o_int_ref[0, 0] = j
    o_int_ref[0, 1] = c
    o_int_ref[0, 2] = is_update.astype(jnp.int32)
    o_int_ref[0, 3] = delay
    o_int_ref[0, 4] = new_seq_ctr
    o_int_ref[0, 5] = new_round
    # transition descriptors for the caller's O(1) occupancy maintenance
    o_int_ref[0, 6] = ph
    o_int_ref[0, 7] = do_comp.astype(jnp.int32)
    o_int_ref[0, 8] = do_cs.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("has_cs", "interpret"))
def event_step_tables(finish, phase, client, seq, disp_round, mu_c, mu_u,
                      fscal, iscal, *, has_cs: bool,
                      interpret: Optional[bool] = None):
    """One event per lane on ``K`` stacked task tables.

    Tables are ``[K, m_max]`` (``finish`` float, the rest int32), rates
    ``[K, n]``; ``fscal = [e_up, e_comp, svc_down, svc_cs]`` float ``[K, 4]``
    and ``iscal = [c_new, seq_ctr, round]`` int32 ``[K, 3]`` carry the
    per-lane outside-drawn randomness and counters.  Returns the five
    updated tables plus ``t_new [K, 1]`` and
    ``[j, c, is_update, delay, seq_ctr', round', ph_pre, do_comp, do_cs]``
    ``[K, 9]``.
    """
    interp = default_interpret() if interpret is None else interpret
    _check_clock(finish.dtype, interp)
    K, m_max = finish.shape
    n = mu_c.shape[1]
    kernel = functools.partial(_event_kernel, has_cs=has_cs, m_max=m_max,
                               n=n)
    return _lane_call(kernel, (finish, phase, client, seq, disp_round),
                      (mu_c, mu_u), (fscal, iscal), (1, 9), interp)


def _megastep_kernel(finish_ref, phase_ref, client_ref, seq_ref, disp_ref,
                     mu_c_ref, mu_u_ref, fscal_ref, iscal_ref,
                     o_finish_ref, o_phase_ref, o_client_ref, o_seq_ref,
                     o_disp_ref, o_t_ref, o_int_ref, *,
                     has_cs: bool, m_max: int, n: int, chunk: int,
                     stop_on_update: bool):
    """Retire up to ``chunk`` events per launch against the resident table.

    The lane's rows load once into VMEM registers and an unrolled
    in-kernel loop applies :func:`_one_event` ``chunk`` times with
    keep-masked selects between iterations — amortizing the launch (and
    the five table round-trips) over ``chunk`` events.  ``keep_i = (i <
    rem) & ~done`` masks the tail of a partial chunk; ``stop_on_update``
    latches ``done`` after the first retired update (the trainer's
    ``next_update`` megastep).  Masked iterations still *compute* a
    transition (values stay in-range: the argmin of an untouched table)
    but select the old rows, so the loop is branch-free; descriptors are
    written unconditionally and the wrapper masks them by the ``keep``
    column.
    """
    tbl = (finish_ref[...], phase_ref[...], client_ref[...], seq_ref[...],
           disp_ref[...])
    mu_c = mu_c_ref[...]
    mu_u = mu_u_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, m_max), 1)
    cli = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    seq_ctr = iscal_ref[0, 0]
    rnd = iscal_ref[0, 1]
    rem = iscal_ref[0, 2]
    done = jnp.zeros((), jnp.bool_)

    for i in range(chunk):
        rand = (fscal_ref[0, 4 * i + 0], fscal_ref[0, 4 * i + 1],
                fscal_ref[0, 4 * i + 2], fscal_ref[0, 4 * i + 3],
                iscal_ref[0, 3 + i], seq_ctr, rnd)
        tbl2, desc = _one_event(tbl, mu_c, mu_u, rand, idx, cli,
                                has_cs=has_cs, m_max=m_max, n=n)
        (t_new, j, c, is_update, delay, new_seq_ctr, new_round, ph,
         do_comp, do_cs) = desc
        keep = np.int32(i) < rem
        if stop_on_update:
            keep = keep & ~done
            done = done | (keep & is_update)
        tbl = tuple(jnp.where(keep, a, b) for a, b in zip(tbl2, tbl))
        seq_ctr = jnp.where(keep, new_seq_ctr, seq_ctr)
        rnd = jnp.where(keep, new_round, rnd)
        o_t_ref[0, i] = t_new
        o_int_ref[0, 10 * i + 0] = j
        o_int_ref[0, 10 * i + 1] = c
        o_int_ref[0, 10 * i + 2] = is_update.astype(jnp.int32)
        o_int_ref[0, 10 * i + 3] = delay
        o_int_ref[0, 10 * i + 4] = new_seq_ctr
        o_int_ref[0, 10 * i + 5] = new_round
        o_int_ref[0, 10 * i + 6] = ph
        o_int_ref[0, 10 * i + 7] = do_comp.astype(jnp.int32)
        o_int_ref[0, 10 * i + 8] = do_cs.astype(jnp.int32)
        o_int_ref[0, 10 * i + 9] = keep.astype(jnp.int32)

    finish, phase, client, seq, disp = tbl
    o_finish_ref[...] = finish
    o_phase_ref[...] = phase
    o_client_ref[...] = client
    o_seq_ref[...] = seq
    o_disp_ref[...] = disp


@functools.partial(jax.jit, static_argnames=("has_cs", "chunk",
                                             "stop_on_update", "interpret"))
def megastep_tables(finish, phase, client, seq, disp_round, mu_c, mu_u,
                    fscal, iscal, *, has_cs: bool, chunk: int,
                    stop_on_update: bool = False,
                    interpret: Optional[bool] = None):
    """Up to ``chunk`` events per lane, one launch per lane.

    The chunked analogue of :func:`event_step_tables`: ``fscal`` is
    ``[K, 4 * chunk]`` (``[e_up, e_comp, svc_down, svc_cs]`` per event)
    and ``iscal`` ``[K, 3 + chunk]`` (``[seq_ctr, round, rem]`` then the
    ``chunk`` routed clients).  Returns the five updated tables plus the
    per-event times ``[K, chunk]`` and descriptors ``[K, 10 * chunk]``
    (the single-step 9 columns plus the ``keep`` mask per event).
    """
    interp = default_interpret() if interpret is None else interpret
    _check_clock(finish.dtype, interp)
    K, m_max = finish.shape
    n = mu_c.shape[1]
    kernel = functools.partial(_megastep_kernel, has_cs=has_cs, m_max=m_max,
                               n=n, chunk=chunk,
                               stop_on_update=stop_on_update)
    return _lane_call(kernel, (finish, phase, client, seq, disp_round),
                      (mu_c, mu_u), (fscal, iscal), (chunk, 10 * chunk),
                      interp)


# ---------------------------------------------------------------------------
# EventState-level wrapper: statistics in jnp around the kernel transition
# ---------------------------------------------------------------------------

def _lane_randomness(params: NetworkParams, state, distribution: str,
                     has_cs: bool):
    """Per-lane key split + outside draws, bit-matching the reference
    engine's stream (same split arity, same key roles — including the
    padding-invariant inverse-CDF routing draw of
    ``repro.core.events._route_client``).

    Routed through the ``chunk=1`` block draw, so the single-step and
    megastep streams share one draw program (see
    :func:`_lane_chunk_randomness`).
    """
    chain, c_new, fscal = _lane_chunk_randomness(params, state, distribution,
                                                 has_cs, 1)
    return chain[:, 0], c_new[:, 0], fscal[:, 0]


def _lane_stats(st, t_new, c, is_update, delay, pw, n: int):
    """One lane's statistics accumulation over the sojourn ending at this
    event — line-for-line the reference engine's block, shared (vmapped)
    by the single-step and megastep wrappers so both run identical ops."""
    measure = (st.round >= st.warmup) & (st.round < st.cap)
    dt_eff = jnp.where(
        measure,
        jnp.clip(jnp.minimum(t_new, st.t_cap)
                 - jnp.minimum(st.t, st.t_cap), 0.0, None),
        0.0)
    occ_int = st.occ_int + dt_eff * st.occ
    energy = st.energy
    if pw is not None:
        p_w = seqsum(pw.P_c * st.serving
                     + pw.P_u * st.occ[2 * n:3 * n]
                     + pw.P_d * st.occ[:n])
        if pw.P_cs is not None:
            p_w = p_w + pw.P_cs * st.cs_busy
        energy = energy + dt_eff * p_w
    upd_measured = is_update & measure
    delay_sum = st.delay_sum.at[c].add(
        jnp.where(upd_measured, delay.astype(st.delay_sum.dtype), 0.0))
    delay_cnt = st.delay_cnt.at[c].add(
        jnp.where(upd_measured, 1, 0).astype(jnp.int32))
    return occ_int, energy, delay_sum, delay_cnt


def step_event_pallas(params: NetworkParams, state, *,
                      distribution: str = "exponential", power=None,
                      interpret: Optional[bool] = None):
    """Batched-lane analogue of :func:`repro.core.events.step_event`.

    ``state`` leaves carry a leading lane axis ``[K, ...]`` and ``params``
    (and ``power``) leaves ``[K, n]``; the statistics window accumulation
    is plain (vmapped) jnp, the table transition runs in the Pallas kernel.
    Returns the batched ``(EventState, EventOut)``.
    """
    n = params.p.shape[-1]
    has_cs = params.mu_cs is not None

    keys, c_new, fscal = _lane_randomness(params, state, distribution,
                                          has_cs)
    iscal = jnp.stack(
        [c_new, state.seq_ctr, state.round], axis=-1).astype(jnp.int32)
    finish, phase, client, seq, disp, t_col, int_col = event_step_tables(
        state.finish, state.phase, state.client, state.seq, state.disp_round,
        params.mu_c, params.mu_u, fscal, iscal, has_cs=has_cs,
        interpret=interpret)
    t_new = t_col[:, 0]
    c = int_col[:, 1]
    is_update = int_col[:, 2] > 0
    delay = int_col[:, 3]
    seq_ctr = int_col[:, 4]
    new_round = int_col[:, 5]
    ph_pre = int_col[:, 6]
    do_comp = int_col[:, 7] > 0
    do_cs = int_col[:, 8] > 0

    # -- statistics over the sojourn ending at this event (pre-event state),
    # line-for-line the reference engine's accumulation, vmapped over lanes
    if power is None:
        occ_int, energy, delay_sum, delay_cnt = jax.vmap(
            lambda st, t, c, u, d: _lane_stats(st, t, c, u, d, None, n))(
                state, t_new, c, is_update, delay)
    else:
        occ_int, energy, delay_sum, delay_cnt = jax.vmap(
            lambda st, t, c, u, d, pw: _lane_stats(st, t, c, u, d, pw, n))(
                state, t_new, c, is_update, delay, power)

    # -- O(1) maintenance of the occupancy carries, mirroring step_event
    # (the kernel reports the slot-j transition; promotions stay within
    # their station and only flip the busy indicators)
    is_comp = ph_pre == E.COMP_SERV
    is_down = ph_pre == E.DOWN
    is_cs = ph_pre == E.CS_SERV
    phase_j = jnp.where(
        is_down, E.COMP_WAIT,
        jnp.where(is_comp, E.UP, jnp.where(is_update, E.DOWN, E.CS_WAIT)))
    client_j = jnp.where(is_update, c_new, c)
    stations = jnp.arange(3 * n + 1)
    occ_new = (state.occ
               + jnp.where(stations[None, :]
                           == E._station_index(phase_j, client_j, n)[:, None],
                           1.0, 0.0)
               - jnp.where(stations[None, :]
                           == E._station_index(ph_pre, c, n)[:, None],
                           1.0, 0.0))
    delta_srv = (jnp.where(do_comp, 1.0, 0.0)
                 - jnp.where(is_comp, 1.0, 0.0))
    serving_new = state.serving + jnp.where(
        jnp.arange(n)[None, :] == c[:, None], delta_srv[:, None], 0.0)
    cs_busy_new = ((state.cs_busy & ~is_cs) | do_cs if has_cs
                   else state.cs_busy)

    t0 = jnp.where(is_update & (new_round == state.warmup), t_new, state.t0)
    t1 = jnp.where(is_update & (new_round == state.cap), t_new, state.t1)

    new_state = E.EventState(
        t=t_new, key=keys, round=new_round, seq_ctr=seq_ctr,
        client=client, phase=phase, finish=finish, seq=seq,
        disp_round=disp,
        warmup=state.warmup, cap=state.cap, t_cap=state.t_cap,
        t0=t0, t1=t1, delay_sum=delay_sum, delay_cnt=delay_cnt,
        energy=energy, occ_int=occ_int,
        occ=occ_new, serving=serving_new, cs_busy=cs_busy_new)
    out = E.EventOut(is_update=is_update, time=t_new,
                     slot=int_col[:, 0], client=c, delay=delay)
    return new_state, out


def step_event_pallas1(params: NetworkParams, state, *,
                       distribution: str = "exponential", power=None,
                       interpret: Optional[bool] = None):
    """Single-lane signature-compatible drop-in for ``events.step_event``
    (adds/strips a K=1 lane axis; batches further via vmap's pallas rule)."""
    up = lambda x: x[None]  # noqa: E731
    st, out = step_event_pallas(
        jax.tree_util.tree_map(up, params),
        jax.tree_util.tree_map(up, state),
        distribution=distribution,
        power=None if power is None else jax.tree_util.tree_map(up, power),
        interpret=interpret)
    down = lambda x: x[0]  # noqa: E731
    return (jax.tree_util.tree_map(down, st),
            jax.tree_util.tree_map(down, out))


# ---------------------------------------------------------------------------
# megastep: up to `chunk` events per kernel launch
# ---------------------------------------------------------------------------

class MegastepAux(NamedTuple):
    """Per-event descriptors of one megastep (leaves ``[K, chunk]`` except
    ``taken [K]``), pre-masked values — consumers gate on ``keep``."""

    time: jax.Array        # event time t_new
    slot: jax.Array        # completing slot j
    client: jax.Array      # completing client c (pre-event)
    delay: jax.Array       # staleness of the retiring round
    update: jax.Array      # bool: the event retired an update
    kind: jax.Array        # pre-event phase of slot j (the ring's kind)
    station: jax.Array     # station of (kind, client) — ring `station`
    station_to: jax.Array  # station slot j moved to — ring `station_to`
    keep: jax.Array        # bool: event really happened (partial chunks)
    taken: jax.Array       # [K] int32: number of kept events this launch


def _lane_chunk_randomness(params: NetworkParams, state, distribution: str,
                           has_cs: bool, chunk: int):
    """Per-lane key chain + outside draws for ``chunk`` events.

    A scan over the chunk replays :func:`_lane_randomness`'s per-event
    6-way split (integer-exact), collecting every event's subkeys; the
    draws then run elementwise, vmapped over ``[K, chunk]``, on those
    subkeys.  Keeping the float draws out of the scan body is what makes
    the megastep stream bitwise the single-step one: a scalar draw inside
    a length-1 scan (which XLA inlines) and inside a length-E scan body
    are different fusion contexts, and the lognormal's erf_inv/exp chain
    can contract differently (by 1 ulp) between them.  Returns ``(chain
    [K, chunk, 2], c_new [K, chunk], fscal [K, chunk, 4])`` with
    ``chain[:, i]`` the carried key after ``i + 1`` events.
    """
    law = get_law(distribution)
    dtype = state.finish.dtype
    K, n = params.p.shape
    n_acts = (params.n_active if params.n_active is not None
              else jnp.full((K,), n))
    mu_cs = params.mu_cs if has_cs else jnp.zeros_like(params.p[..., 0])

    def split6(keys, _):
        ks = jax.vmap(lambda k: jax.random.split(k, 6))(keys)
        return ks[:, 0], ks

    _, subkeys = jax.lax.scan(split6, state.key, None, length=chunk)
    subkeys = jnp.moveaxis(subkeys, 0, 1)  # [K, chunk, 6, 2]

    def draw_one(ks, p_row, mu_d_row, mu_cs_i, n_act):
        _, k_up, k_disp_cli, k_disp_svc, k_comp, k_cs = (
            ks[i] for i in range(6))
        c_new = E._route_client(p_row, k_disp_cli, n_act)
        one_rate = jnp.ones((), dtype)
        e_up = law.device_draw(k_up, one_rate)
        e_comp = law.device_draw(k_comp, one_rate)
        svc_down = law.device_draw(k_disp_svc, mu_d_row[c_new])
        svc_cs = (law.device_draw(k_cs, mu_cs_i) if has_cs
                  else jnp.zeros((), dtype))
        return c_new, jnp.stack([e_up, e_comp, svc_down, svc_cs]).astype(
            dtype)

    per_event = jax.vmap(draw_one, in_axes=(0, None, None, None, None))
    c_new, fscal = jax.vmap(per_event)(subkeys, params.p, params.mu_d, mu_cs,
                                       n_acts)
    return subkeys[:, :, 0], c_new, fscal


def megastep_event_pallas(params: NetworkParams, state, *, chunk: int,
                          rem=None, distribution: str = "exponential",
                          power=None, interpret: Optional[bool] = None,
                          stop_on_update: bool = False):
    """Advance up to ``chunk`` events per lane in ONE kernel launch.

    The megastep analogue of :func:`step_event_pallas`: the randomness
    block draws up front (:func:`_lane_chunk_randomness`), the table
    transitions retire inside :func:`_megastep_kernel`'s unrolled
    in-VMEM loop, and the statistics replay per event around the kernel
    (a ``chunk``-length scan of the shared :func:`_lane_stats` block plus
    the O(1) occupancy maintenance, keep-masked — bitwise ``chunk``
    single :func:`step_event_pallas` calls).  ``rem`` bounds the kept
    events per lane (scalar or ``[K]``; default ``chunk``);
    ``stop_on_update`` stops each lane after its first retired update.
    Returns ``(EventState, MegastepAux)``.
    """
    n = params.p.shape[-1]
    has_cs = params.mu_cs is not None
    K = state.finish.shape[0]

    chain, c_new, fscal = _lane_chunk_randomness(params, state, distribution,
                                                 has_cs, chunk)
    if rem is None:
        rem = jnp.full((K,), chunk, jnp.int32)
    else:
        rem = jnp.broadcast_to(jnp.asarray(rem, jnp.int32), (K,))
    iscal = jnp.concatenate(
        [state.seq_ctr[:, None], state.round[:, None], rem[:, None], c_new],
        axis=1).astype(jnp.int32)
    finish, phase, client, seq, disp, t_mat, int_mat = megastep_tables(
        state.finish, state.phase, state.client, state.seq, state.disp_round,
        params.mu_c, params.mu_u, fscal.reshape(K, 4 * chunk), iscal,
        has_cs=has_cs, chunk=chunk, stop_on_update=stop_on_update,
        interpret=interpret)
    D = int_mat.reshape(K, chunk, 10)
    upd_mat = D[..., 2] > 0
    ph_pre_mat = D[..., 6]
    keep_mat = D[..., 9] > 0

    # -- statistics replay: one keep-masked `_lane_stats` + O(1) occupancy
    # maintenance per event, sequential over the chunk (the delay/occ
    # accumulation order of `chunk` single steps)
    lead = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    xs = (lead(t_mat), lead(D[..., 1]), lead(upd_mat), lead(D[..., 3]),
          lead(D[..., 4]), lead(D[..., 5]), lead(ph_pre_mat),
          lead(D[..., 7] > 0), lead(D[..., 8] > 0), lead(keep_mat),
          lead(c_new))

    def body(st, x):
        (t_new, c, is_update, delay, seq_ctr2, new_round, ph_pre,
         do_comp, do_cs, keep, c_new_i) = x
        if power is None:
            occ_int, energy, delay_sum, delay_cnt = jax.vmap(
                lambda s, t, cc, u, d: _lane_stats(s, t, cc, u, d, None, n))(
                    st, t_new, c, is_update, delay)
        else:
            occ_int, energy, delay_sum, delay_cnt = jax.vmap(
                lambda s, t, cc, u, d, pw: _lane_stats(s, t, cc, u, d, pw,
                                                       n))(
                    st, t_new, c, is_update, delay, power)

        is_comp = ph_pre == E.COMP_SERV
        is_down = ph_pre == E.DOWN
        is_cs = ph_pre == E.CS_SERV
        phase_j = jnp.where(
            is_down, E.COMP_WAIT,
            jnp.where(is_comp, E.UP,
                      jnp.where(is_update, E.DOWN, E.CS_WAIT)))
        client_j = jnp.where(is_update, c_new_i, c)
        stations = jnp.arange(3 * n + 1)
        occ_new = (st.occ
                   + jnp.where(stations[None, :]
                               == E._station_index(phase_j, client_j,
                                                   n)[:, None],
                               1.0, 0.0)
                   - jnp.where(stations[None, :]
                               == E._station_index(ph_pre, c, n)[:, None],
                               1.0, 0.0))
        delta_srv = (jnp.where(do_comp, 1.0, 0.0)
                     - jnp.where(is_comp, 1.0, 0.0))
        serving_new = st.serving + jnp.where(
            jnp.arange(n)[None, :] == c[:, None], delta_srv[:, None], 0.0)
        cs_busy_new = ((st.cs_busy & ~is_cs) | do_cs if has_cs
                       else st.cs_busy)
        t0 = jnp.where(is_update & (new_round == st.warmup), t_new, st.t0)
        t1 = jnp.where(is_update & (new_round == st.cap), t_new, st.t1)

        st2 = st._replace(
            t=t_new, round=new_round, seq_ctr=seq_ctr2, t0=t0, t1=t1,
            delay_sum=delay_sum, delay_cnt=delay_cnt, energy=energy,
            occ_int=occ_int, occ=occ_new, serving=serving_new,
            cs_busy=cs_busy_new)
        sel = lambda a, b: jnp.where(  # noqa: E731
            keep.reshape(keep.shape + (1,) * (a.ndim - 1)), a, b)
        return jax.tree_util.tree_map(sel, st2, st), None

    stf, _ = jax.lax.scan(body, state, xs)

    # -- resume key: the chain entry after the last kept event ------------
    # x64 mode promotes integer sums to int64: pin the count to i32
    # contract: allow(raw-reduction): int32 indicator count over the chunk axis — exact integer arithmetic, never a padded client/class axis
    taken = jnp.sum(keep_mat.astype(jnp.int32), axis=1, dtype=jnp.int32)
    idxk = jnp.clip(taken, 1, chunk) - 1
    k_sel = jnp.take_along_axis(chain, idxk[:, None, None], axis=1)[:, 0]
    keys = jnp.where((taken > 0)[:, None], k_sel, state.key)

    new_state = stf._replace(key=keys, client=client, phase=phase,
                             finish=finish, seq=seq, disp_round=disp)
    is_comp_m = ph_pre_mat == E.COMP_SERV
    is_down_m = ph_pre_mat == E.DOWN
    phase_j_m = jnp.where(
        is_down_m, E.COMP_WAIT,
        jnp.where(is_comp_m, E.UP,
                  jnp.where(upd_mat, E.DOWN, E.CS_WAIT)))
    client_j_m = jnp.where(upd_mat, c_new, D[..., 1])
    aux = MegastepAux(
        time=t_mat, slot=D[..., 0], client=D[..., 1], delay=D[..., 3],
        update=upd_mat, kind=ph_pre_mat,
        station=E._station_index(ph_pre_mat, D[..., 1], n),
        station_to=E._station_index(phase_j_m, client_j_m, n),
        keep=keep_mat, taken=taken)
    return new_state, aux


def megastep_event_pallas1(params: NetworkParams, state, *, chunk: int,
                           rem=None, distribution: str = "exponential",
                           power=None, interpret: Optional[bool] = None,
                           stop_on_update: bool = False):
    """Single-lane megastep (adds/strips a K=1 lane axis): the form
    ``events.next_update`` consumes on the pallas backend."""
    up = lambda x: x[None]  # noqa: E731
    st, aux = megastep_event_pallas(
        jax.tree_util.tree_map(up, params),
        jax.tree_util.tree_map(up, state),
        chunk=chunk,
        rem=None if rem is None else jnp.asarray(rem, jnp.int32)[None],
        distribution=distribution,
        power=None if power is None else jax.tree_util.tree_map(up, power),
        interpret=interpret, stop_on_update=stop_on_update)
    down = lambda x: x[0]  # noqa: E731
    return (jax.tree_util.tree_map(down, st),
            jax.tree_util.tree_map(down, aux))
