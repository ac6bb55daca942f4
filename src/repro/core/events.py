"""Device-resident event engine for the Generalized AsyncSGD closed network.

A fully-jitted JAX re-implementation of the Fig. 1 / Fig. 6 discrete-event
dynamics: the simulation state is a **fixed-size in-flight task table** (one
row per circulating task: station/phase, owning client, dispatch round,
FIFO arrival sequence, absolute service-completion clock) advanced one event
at a time by :func:`step_event` — a pure function suitable for
``lax.scan`` / ``lax.while_loop`` and for ``jax.vmap`` over seeds and over
padded ``(p, m)`` strategy batches (the padding conventions of
``repro.core.batched``: the table is sized by a static ``m_max`` and slots
``>= m`` are inactive).

Exactness: service completions are *raced as absolute clocks* — a task
entering service draws its full service time up front and the next event is
the argmin over the table — which is exactly the semantics of the host
reference simulator for **every** service law registered in
``repro.scenario.laws`` (the Section 5.3.3 built-ins exponential /
deterministic / lognormal plus e.g. the hyperexponential H2 stress law),
not just the memoryless case the old ``jump_chain_throughput`` CTMC sampler
handled (that sampler is now a thin wrapper over this engine).

Contract with ``repro.core.simulator.AsyncNetworkSim``: the host heap
simulator remains the *exact per-task-identity reference*.  The two engines
consume randomness differently (numpy heap order vs. split JAX keys), so
cross-checks are distributional: throughput, per-client mean relative delay,
energy and occupancy statistics agree within Monte-Carlo tolerance on every
service law (``tests/test_events.py``).

State layout (all arrays ``[m_max]`` unless noted):

  * ``client``      — owning client of the task in each slot;
  * ``phase``       — station: DOWN(0) / COMP_WAIT(1) / COMP_SERV(2) /
    UP(3) / CS_WAIT(4) / CS_SERV(5); INACTIVE(-1) marks padded slots;
  * ``finish``      — absolute completion clock (``inf`` unless in service);
  * ``seq``         — FIFO arrival order within the current queue;
  * ``disp_round``  — round counter at dispatch (relative delay =
    ``round - disp_round`` at completion, Section 2.4);
  * statistics      — per-client delay sums/counts, energy integral
    (Eq. 14), time-weighted occupancy ``[3n+1]``, measured over the
    update-count window ``[warmup, cap)`` and time-capped by ``t_cap``.

Model updates (uplink or CS completion) immediately re-dispatch a fresh
task into the freed slot with routing ``p`` (Algorithm 1, lines 7-8) — the
slot index is returned so a caller can attach a payload (the parameter
snapshot ring of ``repro.fl.engine`` is indexed by slot).
"""
from __future__ import annotations
# contract: padded-n — reductions here are on the bitwise padding contract

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import numerics  # noqa: F401  (enables x64)
from ..scenario.laws import get_law
from .buzen import NetworkParams
from .numerics import array_module, seqcumsum, seqsum

# task phases
INACTIVE = -1
DOWN = 0        # downlink in service (infinite-server)
COMP_WAIT = 1   # waiting in the client's compute FIFO
COMP_SERV = 2   # in service at the client's compute queue
UP = 3          # uplink in service (infinite-server)
CS_WAIT = 4     # waiting in the CS FIFO (Section 7)
CS_SERV = 5     # in service at the CS single-server queue

_BIG_SEQ = np.iinfo(np.int32).max
_NO_CAP = np.iinfo(np.int32).max


class EventState(NamedTuple):
    """Carry of the event scan (one trajectory; vmap for batches)."""

    t: jax.Array          # current wall-clock time
    key: jax.Array        # PRNG carry
    round: jax.Array      # updates completed so far (round counter k)
    seq_ctr: jax.Array    # global FIFO arrival counter
    client: jax.Array     # [m_max]
    phase: jax.Array      # [m_max]
    finish: jax.Array     # [m_max]
    seq: jax.Array        # [m_max]
    disp_round: jax.Array  # [m_max]
    # statistics window: update-count window [warmup, cap), time cap t_cap
    warmup: jax.Array
    cap: jax.Array
    t_cap: jax.Array
    t0: jax.Array         # time of update #warmup (stats origin)
    t1: jax.Array         # time of update #cap (stats end)
    delay_sum: jax.Array  # [n]
    delay_cnt: jax.Array  # [n]
    energy: jax.Array     # scalar, Eq. 14 time integral
    occ_int: jax.Array    # [3n+1] time-weighted station occupancy
    # incrementally-maintained occupancy (each event moves exactly one task
    # between stations, so these are O(1)-update carries rather than O(m+n)
    # per-event recounts — the difference between the event scan being
    # bandwidth-bound and scatter-bound, especially under lane vmap):
    occ: jax.Array        # [3n+1] current station occupancy
    serving: jax.Array    # [n] busy indicator of each compute server
    cs_busy: jax.Array    # bool: CS server busy


class EventOut(NamedTuple):
    """Per-event emission of :func:`step_event`."""

    is_update: jax.Array
    time: jax.Array
    slot: jax.Array    # task-table row of the completed task (payload key)
    client: jax.Array  # C_k — client whose gradient would be applied
    delay: jax.Array   # relative delay round - dispatch_round


class UpdateOut(NamedTuple):
    """Result of :func:`next_update` (one model update)."""

    time: jax.Array
    slot: jax.Array
    client: jax.Array
    delay: jax.Array
    steps: jax.Array   # events consumed to reach this update


class EventStats(NamedTuple):
    """Device analogue of ``repro.core.simulator.SimStats``."""

    updates: jax.Array
    time: jax.Array
    throughput: jax.Array
    mean_delay: jax.Array        # [n] unscaled E0[R_i], 0 where no samples
    delay_counts: jax.Array      # [n]
    energy: jax.Array
    mean_queue_counts: jax.Array  # [3n+1]


def _draw(key: jax.Array, rate: jax.Array, distribution: str,
          shape=()) -> jax.Array:
    """Service time with mean ``1/rate``: the device draw of the registered
    timing law (``repro.scenario.laws``; Section 5.3.3 built-ins plus any
    ``@timing_law``-registered extension).  Unknown names raise listing the
    registry — and only at trace time; callers validate eagerly via
    :func:`repro.scenario.laws.get_law`."""
    return get_law(distribution).device_draw(key, rate, shape)


def _route_client(p: jax.Array, key: jax.Array, n_act,
                  prefix: Optional[jax.Array] = None) -> jax.Array:
    """Dispatch-routing draw ``C ~ p/sum(p)`` by inverse-CDF on one uniform.

    Deliberately *not* ``jax.random.categorical``: the Gumbel trick draws
    noise of the logits' shape, so the sampled client would depend on the
    static padded length ``n_max``.  A single scalar uniform against the
    routing prefix sums consumes shape-independent randomness, making
    event trajectories **bitwise invariant** to trailing zero-mass padding
    — the traced-``n`` analogue of the ``m_max`` slot-padding contract.
    The prefix is the strictly-sequential :func:`numerics.seqcumsum`
    (``jnp.cumsum`` may reassociate with length on parallel backends), its
    last element doubles as the padding-stable total mass (no separate
    normalization pass), padded entries repeat that total so
    ``searchsorted`` never lands on them, and the clip covers the
    measure-zero ``u * total >= total`` edge.

    ``prefix`` lets the caller pass ``seqcumsum(p)`` precomputed: the
    routing CDF is loop-invariant across an event scan, so hoisting it
    into the scan constants saves an O(n) sequential cumsum *per event*
    (:func:`_simulate_stats` does this).  The hoisted value is the same
    ``seqcumsum`` of the same ``p`` — trajectories are bitwise identical
    either way.
    """
    if prefix is None:
        prefix = seqcumsum(p)
    u = jax.random.uniform(key, dtype=p.dtype) * prefix[-1]
    idx = jnp.searchsorted(prefix, u, side="right")
    return jnp.minimum(idx, n_act - 1).astype(jnp.int32)


class EventBlocks(NamedTuple):
    """Pre-drawn randomness for a chunk of consecutive events (megastep).

    Every leaf carries a leading ``[chunk]`` axis; one row resolves one
    :func:`step_event_block` call.  The factorization follows what is
    state-independent in the per-event stream: the routing draw, the
    downlink service (its rate is keyed by the routed client, known before
    the argmin) and the CS service resolve fully up front; the uplink and
    computation services depend on the *completing* client's rate, so they
    are stored as the law's unit parts (``TimingLaw.unit_draw``) and
    rate-applied inside the step — or, for laws without a unit
    factorization, as the raw subkeys (``device_draw`` runs in-step,
    bitwise by construction).
    """

    c_new: jax.Array       # routed client (client engine) / class (class)
    member: jax.Array      # routed member within the class; () otherwise
    svc_down: jax.Array    # downlink service of the re-dispatched task
    up: jax.Array          # uplink unit part (or raw subkey)
    comp: jax.Array        # computation unit part (or raw subkey)
    svc_cs: jax.Array      # CS service draw; () when the network has no CS


def _apply_unit(u, rate, distribution: str):
    """Resolve a stored uplink/computation entry against the completing
    client's rate — ``unit_apply`` replays ``device_draw``'s exact op
    order (bitwise), the raw-subkey fallback *is* ``device_draw``."""
    law = get_law(distribution)
    if law.unit_apply is not None:
        return law.unit_apply(u, rate)
    return law.device_draw(u, rate)


def draw_event_blocks(params: NetworkParams, key: jax.Array, chunk: int, *,
                      distribution: str = "exponential",
                      route_prefix: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, EventBlocks]:
    """Draw the randomness of ``chunk`` consecutive events up front.

    A tiny-carry scan (the carry is just the PRNG key) replays
    :func:`step_event`'s 6-way split per event; the draws themselves then
    resolve on the collected subkeys — the exact primitives on the exact
    keys of ``chunk`` single steps.  Laws with a unit factorization draw
    **vmapped** over the chunk axis (PRNG bits are integer-exact per key
    and the uniform→sample conversions compile bitwise elementwise);
    laws without one (e.g. lognormal, whose erf_inv/exp chain is not
    fusion-stable across a materialization boundary) stay on a strictly
    sequential scalar-shape draw scan and store raw subkeys for the
    rate-dependent services.  Returns ``(chain, blocks)``: ``chain[i]``
    is the carried key after ``i + 1`` events (the partial-chunk resume
    point) and ``blocks`` one :class:`EventBlocks` row per event.
    """
    law = get_law(distribution)
    has_cs = params.mu_cs is not None

    if law.unit_draw is None:
        def body(k, _):
            k2, k_up, k_cli, k_svc, k_comp, k_cs = jax.random.split(k, 6)
            c_new = _route_client(params.p, k_cli, params.active_count,
                                  route_prefix)
            svc_down = _draw(k_svc, params.mu_d[c_new], distribution)
            svc_cs = (_draw(k_cs, params.mu_cs, distribution)
                      if has_cs else ())
            blk = EventBlocks(c_new=c_new, member=(), svc_down=svc_down,
                              up=k_up, comp=k_comp, svc_cs=svc_cs)
            return k2, (k2, blk)

        _, (chain, blks) = jax.lax.scan(body, key, None, length=chunk)
        return chain, blks

    def split6(k, _):
        ks = jax.random.split(k, 6)
        return ks[0], (ks[0], ks[1], ks[2], ks[3], ks[4], ks[5])

    _, (chain, k_up, k_cli, k_svc, k_comp, k_cs) = jax.lax.scan(
        split6, key, None, length=chunk)
    c_new = jax.vmap(lambda k: _route_client(
        params.p, k, params.active_count, route_prefix))(k_cli)
    svc_down = jax.vmap(
        lambda k, r: _draw(k, r, distribution))(k_svc, params.mu_d[c_new])
    up = jax.vmap(law.unit_draw)(k_up)
    comp = jax.vmap(law.unit_draw)(k_comp)
    svc_cs = (jax.vmap(lambda k: _draw(k, params.mu_cs, distribution))(k_cs)
              if has_cs else ())
    return chain, EventBlocks(c_new=c_new, member=(), svc_down=svc_down,
                              up=up, comp=comp, svc_cs=svc_cs)


def _tree_select(pred, on_true, on_false):
    """Leaf-wise ``where`` — the masked-step select of the megastep scans
    (a scalar predicate; identical trees selected leaf-by-leaf)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), on_true, on_false)


def init_state(params: NetworkParams, m, key: jax.Array, *,
               m_max: Optional[int] = None,
               distribution: str = "exponential",
               warmup=0, cap=_NO_CAP, t_cap=jnp.inf) -> EventState:
    """Initial out-of-equilibrium state: ``m`` tasks dispatched uniformly at
    random into the downlink servers at ``t = 0`` (Section 5.3.3).

    ``m`` may be a traced scalar; ``m_max`` (static) sizes the task table —
    slots ``>= m`` are inactive, following the padded conventions of
    ``repro.core.batched``.  Under the traced-``n`` convention
    (``params.n_active`` set, see :func:`repro.core.buzen.pad_network`) the
    statistics arrays are sized by the static ``n_max = params.n`` while
    the initial dispatch draws only real clients — bitwise the same draws
    as the unpadded network.
    """
    n = params.n
    if m_max is None:
        m_max = int(m)
    key, k_cli, k_svc = jax.random.split(key, 3)
    clients = jax.random.randint(k_cli, (m_max,), 0, params.active_count)
    active = jnp.arange(m_max) < m
    svc = _draw(k_svc, params.mu_d[clients], distribution, (m_max,))
    phase0 = jnp.where(active, DOWN, INACTIVE).astype(jnp.int32)
    down, comp_total, comp_serving, up, cs_total, cs_busy = _station_counts(
        phase0, clients.astype(jnp.int32), n)
    return EventState(
        t=jnp.zeros((), jnp.float64),
        key=key,
        round=jnp.zeros((), jnp.int32),
        seq_ctr=jnp.zeros((), jnp.int32),
        client=clients.astype(jnp.int32),
        phase=phase0,
        finish=jnp.where(active, svc, jnp.inf),
        seq=jnp.zeros((m_max,), jnp.int32),
        disp_round=jnp.zeros((m_max,), jnp.int32),
        warmup=jnp.asarray(warmup, jnp.int32),
        cap=jnp.asarray(cap, jnp.int32),
        t_cap=jnp.asarray(t_cap, jnp.float64),
        t0=jnp.zeros((), jnp.float64),
        t1=jnp.zeros((), jnp.float64),
        delay_sum=jnp.zeros((n,), jnp.float64),
        delay_cnt=jnp.zeros((n,), jnp.int32),
        energy=jnp.zeros((), jnp.float64),
        occ_int=jnp.zeros((3 * n + 1,), jnp.float64),
        occ=jnp.concatenate([down, comp_total, up, cs_total[None]]),
        serving=comp_serving,
        cs_busy=cs_busy,
    )


def _station_counts(phase, client, n):
    """Per-station occupancy: down[n], comp_total[n], comp_serving[n],
    up[n], cs_total, cs_busy.

    Full recount from the task table — used to seed the O(1)-update
    occupancy carries of :class:`EventState` at :func:`init_state` and as
    the consistency oracle in the tests; the event step itself maintains
    the carries incrementally.
    """
    def count(mask):
        return jnp.zeros((n,), jnp.float64).at[client].add(
            jnp.where(mask, 1.0, 0.0))

    down = count(phase == DOWN)
    comp_total = count((phase == COMP_WAIT) | (phase == COMP_SERV))
    comp_serving = count(phase == COMP_SERV)
    up = count(phase == UP)
    # contract: allow(raw-reduction): 0/1 indicator count over the task table — exact small-integer f64 under any association, and the table axis is m_max (never padded-n)
    cs_total = jnp.sum(
        jnp.where((phase == CS_WAIT) | (phase == CS_SERV), 1.0, 0.0))
    cs_busy = jnp.any(phase == CS_SERV)
    return down, comp_total, comp_serving, up, cs_total, cs_busy


def _station_index(phase, client, n):
    """Row of the ``[3n+1]`` occupancy vector a task in ``(phase, client)``
    occupies: down_i / comp_i (WAIT and SERV share the station) / up_i /
    CS."""
    return jnp.where(
        phase == DOWN, client,
        jnp.where((phase == COMP_WAIT) | (phase == COMP_SERV), n + client,
                  jnp.where(phase == UP, 2 * n + client, 3 * n)))


def step_event(params: NetworkParams, state: EventState, *,
               distribution: str = "exponential",
               power=None,
               route_prefix: Optional[jax.Array] = None
               ) -> tuple[EventState, EventOut]:
    """Advance the network by exactly one event (one service completion).

    Pure and jit/vmap-safe.  ``params.mu_cs is None`` statically selects the
    CS-free network; ``power`` (a ``PowerProfile`` or None) statically
    enables phase-dependent energy accounting (Eq. 14).  ``route_prefix``
    optionally supplies the precomputed routing CDF ``seqcumsum(params.p)``
    (loop-invariant across a scan — see :func:`_route_client`); ``None``
    recomputes it in-body, bitwise the same.

    Structured as a one-event :class:`EventBlocks` draw followed by the
    randomness-free table transition :func:`step_event_block` — the same
    primitives on the same keys as the historical inline body (values are
    position-independent under jit), so trajectories are bitwise
    unchanged; the megastep engine reuses the block step with ``chunk``
    pre-drawn rows.
    """
    law = get_law(distribution)
    key, k_up, k_disp_cli, k_disp_svc, k_comp, k_cs = jax.random.split(
        state.key, 6)
    c_new = _route_client(params.p, k_disp_cli, params.active_count,
                          route_prefix)
    svc_down = _draw(k_disp_svc, params.mu_d[c_new], distribution)
    if law.unit_draw is not None:
        up, comp = law.unit_draw(k_up), law.unit_draw(k_comp)
    else:
        up, comp = k_up, k_comp
    svc_cs = (_draw(k_cs, params.mu_cs, distribution)
              if params.mu_cs is not None else ())
    blk = EventBlocks(c_new=c_new, member=(), svc_down=svc_down,
                      up=up, comp=comp, svc_cs=svc_cs)
    return step_event_block(params, state._replace(key=key), blk,
                            distribution=distribution, power=power)


def step_event_block(params: NetworkParams, state: EventState,
                     blk: EventBlocks, *,
                     distribution: str = "exponential",
                     power=None) -> tuple[EventState, EventOut]:
    """One event transition with its randomness pre-resolved in ``blk``.

    The randomness-free core of :func:`step_event`: consumes no PRNG key
    (``state.key`` passes through untouched — megastep callers advance it
    from the :func:`_chunk_keys` chain) and reads the routing / service
    draws from one :class:`EventBlocks` row, applying the law's unit
    parts against the completing client's rates in-step.
    """
    n = params.n
    m_max = state.phase.shape[0]
    has_cs = params.mu_cs is not None

    j = jnp.argmin(state.finish)
    t_new = state.finish[j]

    # -- statistics over the sojourn ending at this event (pre-event state) --
    # the occupancy vector / busy indicators are O(1)-update carries of the
    # state (exact small-integer f64 arithmetic: bit-identical to a full
    # per-event recount, without its O(m + n) scatter cost)
    measure = (state.round >= state.warmup) & (state.round < state.cap)
    dt_eff = jnp.where(
        measure,
        jnp.clip(jnp.minimum(t_new, state.t_cap)
                 - jnp.minimum(state.t, state.t_cap), 0.0, None),
        0.0)
    occ_int = state.occ_int + dt_eff * state.occ
    energy = state.energy
    if power is not None:
        # one sequential sum over the fused per-client power terms: the
        # energy statistic is on the padded-n bitwise contract
        pwr = seqsum(power.P_c * state.serving
                     + power.P_u * state.occ[2 * n:3 * n]
                     + power.P_d * state.occ[:n])
        if power.P_cs is not None:
            pwr = pwr + power.P_cs * state.cs_busy
        energy = energy + dt_eff * pwr

    # -- the event itself ---------------------------------------------------
    c = state.client[j]
    ph = state.phase[j]

    is_down = ph == DOWN
    is_comp = ph == COMP_SERV
    is_up = ph == UP
    is_cs = ph == CS_SERV
    is_update = is_cs if has_cs else is_up

    delay = state.round - state.disp_round[j]
    new_round = state.round + jnp.where(is_update, 1, 0).astype(jnp.int32)

    # update -> immediate re-dispatch of a fresh task into the freed slot
    c_new = blk.c_new
    svc_up = _apply_unit(blk.up, params.mu_u[c], distribution)
    svc_down = blk.svc_down

    phase_j = jnp.where(
        is_down, COMP_WAIT,
        jnp.where(is_comp, UP, jnp.where(is_update, DOWN, CS_WAIT)))
    finish_j = jnp.where(
        is_comp, t_new + svc_up,
        jnp.where(is_update, t_new + svc_down, jnp.inf))
    joins_fifo = is_down | (is_up & has_cs)
    seq_j = jnp.where(joins_fifo, state.seq_ctr, state.seq[j])
    seq_ctr = state.seq_ctr + joins_fifo.astype(jnp.int32)
    client_j = jnp.where(is_update, c_new, c)
    disp_j = jnp.where(is_update, new_round, state.disp_round[j])

    onej = jnp.arange(m_max) == j
    phase = jnp.where(onej, phase_j, state.phase).astype(jnp.int32)
    finish = jnp.where(onej, finish_j, state.finish)
    seq = jnp.where(onej, seq_j, state.seq).astype(jnp.int32)
    client = jnp.where(onej, client_j, state.client).astype(jnp.int32)
    disp_round = jnp.where(onej, disp_j, state.disp_round).astype(jnp.int32)

    # -- FIFO promotions (post-transition table) ----------------------------
    # compute station of client c: j joined its queue (is_down) or freed its
    # server (is_comp)
    promo_comp = is_down | is_comp
    serving_c = jnp.any((phase == COMP_SERV) & (client == c))
    waiting_c = (phase == COMP_WAIT) & (client == c)
    pick = jnp.argmin(jnp.where(waiting_c, seq, _BIG_SEQ))
    do_comp = promo_comp & ~serving_c & jnp.any(waiting_c)
    svc_c = _apply_unit(blk.comp, params.mu_c[c], distribution)
    onep = (jnp.arange(m_max) == pick) & do_comp
    phase = jnp.where(onep, COMP_SERV, phase)
    finish = jnp.where(onep, t_new + svc_c, finish)

    if has_cs:
        # CS station: j joined its queue (is_up) or freed its server (is_cs)
        promo_cs = is_up | is_cs
        cs_waiting = phase == CS_WAIT
        pick_cs = jnp.argmin(jnp.where(cs_waiting, seq, _BIG_SEQ))
        do_cs = promo_cs & ~jnp.any(phase == CS_SERV) & jnp.any(cs_waiting)
        onec = (jnp.arange(m_max) == pick_cs) & do_cs
        phase = jnp.where(onec, CS_SERV, phase)
        finish = jnp.where(onec, t_new + blk.svc_cs, finish)

    # -- O(1) maintenance of the occupancy carries: slot j moved stations;
    # FIFO promotions stay within theirs (WAIT and SERV share a station),
    # so they only touch the busy indicators -------------------------------
    stations = jnp.arange(3 * n + 1)
    occ_new = (state.occ
               + jnp.where(stations == _station_index(phase_j, client_j, n),
                           1.0, 0.0)
               - jnp.where(stations == _station_index(ph, c, n), 1.0, 0.0))
    delta_srv = (jnp.where(do_comp, 1.0, 0.0)
                 - jnp.where(is_comp, 1.0, 0.0))
    serving_new = state.serving + jnp.where(jnp.arange(n) == c,
                                            delta_srv, 0.0)
    cs_busy_new = ((state.cs_busy & ~is_cs) | do_cs if has_cs
                   else state.cs_busy)

    # -- delay statistics and window marks ----------------------------------
    upd_measured = is_update & measure
    delay_sum = state.delay_sum.at[c].add(
        jnp.where(upd_measured, delay.astype(jnp.float64), 0.0))
    delay_cnt = state.delay_cnt.at[c].add(
        jnp.where(upd_measured, 1, 0).astype(jnp.int32))
    t0 = jnp.where(is_update & (new_round == state.warmup), t_new, state.t0)
    t1 = jnp.where(is_update & (new_round == state.cap), t_new, state.t1)

    new_state = EventState(
        t=t_new, key=state.key, round=new_round, seq_ctr=seq_ctr,
        client=client, phase=phase, finish=finish, seq=seq,
        disp_round=disp_round,
        warmup=state.warmup, cap=state.cap, t_cap=state.t_cap,
        t0=t0, t1=t1, delay_sum=delay_sum, delay_cnt=delay_cnt,
        energy=energy, occ_int=occ_int,
        occ=occ_new, serving=serving_new, cs_busy=cs_busy_new)
    out = EventOut(is_update=is_update,
                   time=t_new,
                   slot=j.astype(jnp.int32),
                   client=c,
                   delay=delay.astype(jnp.int32))
    return new_state, out


def next_update(params: NetworkParams, state: EventState, *,
                distribution: str = "exponential", power=None,
                max_steps: Optional[int] = None,
                backend: Optional[str] = None,
                interpret: Optional[bool] = None,
                route_prefix: Optional[jax.Array] = None,
                chunk: int = 1) -> tuple[EventState, UpdateOut]:
    """Run events until the next model update (uplink/CS completion).

    A ``lax.while_loop`` bounded by ``max_steps`` (default ``3 m_max + 8``,
    ``4 m_max + 8`` with the CS station — between two consecutive updates
    each of the ``m`` tasks can complete at most its downlink, compute and
    uplink (and CS) phases, and the last such completion *is* the update,
    so the bound is never met in a valid state).

    ``backend`` selects the per-event step implementation
    (``repro.sim.backend``): under ``"pallas"`` the table transition runs
    in the ``repro.kernels.events`` TPU kernel — compiled on TPU unless
    ``interpret`` overrides — while ``"reference"``/``"batched"`` share
    the single-lane jnp step (lane batching happens in the caller's
    ``vmap``).

    ``chunk > 1`` (static) selects the megastep body: each while-loop
    iteration pre-draws a block of ``chunk`` events and retires them in an
    inner masked scan (under ``"pallas"``, one kernel launch with an
    in-VMEM early-stop loop) — events past the update, or past the
    ``max_steps`` bound, are discarded and the key chain advances by
    exactly the events consumed, so the returned update (and the state it
    leaves behind) is **bitwise** the single-step result.
    """
    from ..sim.backend import resolve_backend  # dependency-free

    use_pallas = resolve_backend(backend) == "pallas"
    if use_pallas:
        from ..kernels.events import step_event_pallas1

        # the kernel computes the routing CDF in-register; a host-hoisted
        # prefix does not apply (and is bitwise irrelevant either way)
        step_fn = functools.partial(step_event_pallas1, interpret=interpret)
    else:
        step_fn = functools.partial(step_event, route_prefix=route_prefix)
    m_max = state.phase.shape[0]
    if max_steps is None:
        max_steps = (4 if params.mu_cs is not None else 3) * m_max + 8

    dummy = EventOut(is_update=jnp.asarray(False),
                     time=jnp.zeros((), jnp.float64),
                     slot=jnp.zeros((), jnp.int32),
                     client=jnp.zeros((), jnp.int32),
                     delay=jnp.zeros((), jnp.int32))

    def cond(carry):
        _, out, steps = carry
        return (~out.is_update) & (steps < max_steps)

    if chunk == 1:
        def body(carry):
            st, _, steps = carry
            st, out = step_fn(params, st, distribution=distribution,
                              power=power)
            return st, out, steps + 1
    elif use_pallas:
        from ..kernels.events import megastep_event_pallas1

        def body(carry):
            st, out, steps = carry
            st, aux = megastep_event_pallas1(
                params, st, chunk=chunk, rem=max_steps - steps,
                distribution=distribution, power=power,
                interpret=interpret, stop_on_update=True)
            outs = EventOut(is_update=aux.update, time=aux.time,
                            slot=aux.slot, client=aux.client,
                            delay=aux.delay)

            def sel(o, x):
                keep, o2 = x
                return _tree_select(keep, o2, o), None

            out, _ = jax.lax.scan(sel, out, (aux.keep, outs))
            return st, out, steps + aux.taken
    else:
        def body(carry):
            st, out, steps = carry
            chain, blks = draw_event_blocks(
                params, st.key, chunk, distribution=distribution,
                route_prefix=route_prefix)

            def inner(c2, blk):
                st, out, taken = c2
                st2, out2 = step_event_block(
                    params, st, blk, distribution=distribution, power=power)
                take = (~out.is_update) & (steps + taken < max_steps)
                return (_tree_select(take, st2, st),
                        _tree_select(take, out2, out),
                        taken + take.astype(jnp.int32)), None

            (st, out, taken), _ = jax.lax.scan(
                inner, (st, out, jnp.zeros((), jnp.int32)), blks)
            # key chain advances by exactly the events consumed (see
            # _chunk_keys); an all-masked chunk leaves the key untouched
            k = jnp.clip(taken, 1, chunk)
            st = st._replace(key=jnp.where(taken > 0, chain[k - 1], st.key))
            return st, out, steps + taken

    st, out, steps = jax.lax.while_loop(
        cond, body, (state, dummy, jnp.zeros((), jnp.int32)))
    return st, UpdateOut(time=out.time, slot=out.slot, client=out.client,
                         delay=out.delay, steps=steps)


# ---------------------------------------------------------------------------
# stationary statistics (device analogue of AsyncNetworkSim.run)
# ---------------------------------------------------------------------------

def finalize_stats(st: EventState) -> EventStats:
    """Stationary statistics from a final event-scan state (one lane).

    The single definition every ``repro.sim`` backend assembles its
    :class:`EventStats` through — reference, batched and pallas sweeps
    stay bitwise aligned by construction.
    """
    updates = jnp.clip(st.round, 0, st.cap) - st.warmup
    horizon = jnp.where(st.round >= st.cap, st.t1 - st.t0, st.t - st.t0)
    mean_delay = jnp.where(st.delay_cnt > 0,
                           st.delay_sum / jnp.maximum(st.delay_cnt, 1), 0.0)
    return EventStats(
        updates=updates,
        time=horizon,
        throughput=jnp.where(horizon > 0, updates / jnp.maximum(horizon, 1e-12),
                             0.0),
        mean_delay=mean_delay,
        delay_counts=st.delay_cnt,
        energy=st.energy,
        mean_queue_counts=st.occ_int / jnp.maximum(horizon, 1e-12),
    )


def unpad_stats(stats: EventStats, n: int) -> EventStats:
    """Strip the traced-``n`` padding from an :class:`EventStats`.

    Per-client arrays are truncated to the real population ``n`` and the
    ``[3 n_max + 1]`` occupancy vector is re-packed segment-wise into the
    unpadded ``[3n + 1]`` station layout (down / comp / up / CS).  Works on
    any number of leading lane axes.  Because trajectories are bitwise
    invariant to the padding (see :func:`_route_client`), the result equals
    the unpadded run's statistics exactly.  The kind of array is kept:
    NumPy statistics (a host copy) are unpadded on the host, device arrays
    with ``jax.numpy``.
    """
    nm = (stats.mean_queue_counts.shape[-1] - 1) // 3
    occ = stats.mean_queue_counts
    xp = array_module(occ)
    return stats._replace(
        mean_delay=stats.mean_delay[..., :n],
        delay_counts=stats.delay_counts[..., :n],
        mean_queue_counts=xp.concatenate(
            [occ[..., 0:n], occ[..., nm:nm + n],
             occ[..., 2 * nm:2 * nm + n], occ[..., 3 * nm:]], axis=-1))


def _scan_chunked(step_block, draw_blocks, st, num_events: int, chunk: int,
                  ring=None, append=None):
    """Advance ``num_events`` events in megasteps of ``chunk``.

    The outer scan runs ``ceil(num_events / chunk)`` iterations; each
    draws one randomness block from the carried key and retires up to
    ``chunk`` events in a rolled inner scan.  Events past ``num_events``
    (the masked partial final chunk) are computed and discarded via
    :func:`_tree_select`, and the carried key advances by exactly the
    *real* event count from the :func:`_chunk_keys` chain — so the final
    state (statistics windows included: ``warmup``/``cap``/``t_cap`` land
    on exact event boundaries) is **bitwise** the single-step scan's.

    ``append(ring, pre, post, out, keep)`` optionally threads an obs ring
    through the chunked carry; masked events append with ``valid=False``
    (a static no-op on the ring), keeping tracing bitwise non-invasive.
    """
    n_chunks = -(-num_events // chunk)
    offsets = jnp.arange(chunk)

    def outer(carry, _):
        st, rem, ring = carry
        chain, blks = draw_blocks(st.key)

        def inner(c2, xs):
            st, ring = c2
            blk, keep = xs
            st2, out = step_block(st, blk)
            if append is not None:
                ring = append(ring, st, st2, out, keep)
            return (_tree_select(keep, st2, st), ring), None

        (st, ring), _ = jax.lax.scan(inner, (st, ring),
                                     (blks, rem > offsets))
        k = jnp.clip(jnp.minimum(rem, chunk), 1, chunk)
        st = st._replace(key=jnp.where(rem > 0, chain[k - 1], st.key))
        return (st, rem - chunk, ring), None

    (st, _, ring), _ = jax.lax.scan(
        outer, (st, jnp.asarray(num_events, jnp.int32), ring), None,
        length=n_chunks)
    return st, ring


@functools.partial(jax.jit, static_argnames=(
    "num_updates", "warmup", "distribution", "m_max", "chunk"))
def _simulate_stats(params, m, key, num_updates, warmup, distribution,
                    m_max, power, chunk=1):
    # every completed task cycle is down -> comp -> up (-> cs): exactly 3 (4)
    # events per update, plus at most one incomplete cycle per task
    mult = 4 if params.mu_cs is not None else 3
    num_events = mult * (num_updates + warmup) + mult * m_max + 8
    cap = warmup + num_updates
    st = init_state(params, m, key, m_max=m_max, distribution=distribution,
                    warmup=warmup, cap=cap)
    # the routing CDF is loop-invariant: hoist it out of the scan body so it
    # enters as a scan constant instead of an O(n) sequential cumsum per
    # event (same seqcumsum of the same p — trajectories bitwise unchanged)
    route_prefix = seqcumsum(params.p)

    if chunk == 1:
        def body(st, _):
            st, _ = step_event(params, st, distribution=distribution,
                               power=power, route_prefix=route_prefix)
            return st, None

        st, _ = jax.lax.scan(body, st, None, length=num_events)
        return finalize_stats(st)

    def draw(key):
        return draw_event_blocks(params, key, chunk,
                                 distribution=distribution,
                                 route_prefix=route_prefix)

    def step(st, blk):
        return step_event_block(params, st, blk, distribution=distribution,
                                power=power)

    st, _ = _scan_chunked(step, draw, st, num_events, chunk)
    return finalize_stats(st)


@functools.partial(jax.jit, static_argnames=(
    "num_updates", "warmup", "distribution", "m_max", "trace_events",
    "chunk"))
def _simulate_stats_traced(params, m, key, num_updates, warmup, distribution,
                           m_max, power, trace_events, chunk=1):
    """:func:`_simulate_stats` carrying an ``repro.obs`` event ring.

    A separate program on purpose: the untraced scan stays byte-for-byte
    what it was (same name for the compile sentinel, same jit cache
    entry), and the ring rides as extra carry state.  The append reads
    the *pre-event* state (the completed station) and the post-step state
    (the destination station) but never feeds back into either — no
    randomness consumed, no value altered — so the returned
    :class:`EventStats` is **bitwise** equal to the untraced run
    (``tests/test_obs.py`` property-tests this across all backends).
    """
    from ..obs.rings import event_ring_append, event_ring_init

    mult = 4 if params.mu_cs is not None else 3
    num_events = mult * (num_updates + warmup) + mult * m_max + 8
    cap = warmup + num_updates
    st = init_state(params, m, key, m_max=m_max, distribution=distribution,
                    warmup=warmup, cap=cap)
    route_prefix = seqcumsum(params.p)
    n = params.n
    ring = event_ring_init(int(trace_events))

    if chunk == 1:
        def body(carry, _):
            st, ring = carry
            st2, out = step_event(params, st, distribution=distribution,
                                  power=power, route_prefix=route_prefix)
            ph = st.phase[out.slot]
            ring = event_ring_append(
                ring, time=out.time,
                station=_station_index(ph, out.client, n),
                station_to=_station_index(st2.phase[out.slot],
                                          st2.client[out.slot], n),
                kind=ph, slot=out.slot, client=out.client, delay=out.delay,
                update=out.is_update)
            return (st2, ring), None

        (st, ring), _ = jax.lax.scan(body, (st, ring), None,
                                     length=num_events)
        return finalize_stats(st), ring

    def draw(key):
        return draw_event_blocks(params, key, chunk,
                                 distribution=distribution,
                                 route_prefix=route_prefix)

    def step(st, blk):
        return step_event_block(params, st, blk, distribution=distribution,
                                power=power)

    def append(ring, pre, post, out, keep):
        ph = pre.phase[out.slot]
        return event_ring_append(
            ring, time=out.time,
            station=_station_index(ph, out.client, n),
            station_to=_station_index(post.phase[out.slot],
                                      post.client[out.slot], n),
            kind=ph, slot=out.slot, client=out.client, delay=out.delay,
            update=out.is_update, valid=keep)

    st, ring = _scan_chunked(step, draw, st, num_events, chunk,
                             ring=ring, append=append)
    return finalize_stats(st), ring


def simulate_stats(params: NetworkParams, m, num_updates: int, *,
                   warmup: int = 0, key: Optional[jax.Array] = None,
                   seed: int = 0, distribution: str = "exponential",
                   power=None, m_max: Optional[int] = None,
                   backend: Optional[str] = None,
                   interpret: Optional[bool] = None,
                   chunk: int = 1) -> EventStats:
    """Stationary statistics over ``num_updates`` rounds, fully on device.

    Mirrors :meth:`repro.core.simulator.AsyncNetworkSim.run`: statistics are
    collected over the update-count window ``[warmup, warmup + num_updates)``
    inside ONE jitted ``lax.scan`` over events.  ``m`` may be traced and the
    whole function vmaps over seeds (``key``) and padded ``(p, m)`` batches
    (pass a static ``m_max >= m``).

    ``backend`` (default: the ``repro.sim`` process flag) picks the step
    implementation; multi-lane sweeps belong in
    :func:`repro.sim.simulate_stats_lanes`, where ``"batched"`` vs
    ``"reference"`` actually differ.  ``chunk`` (static, default 1 ==
    today's byte-identical programs) selects the megastep execution mode:
    ``chunk`` events retire per scan iteration, bitwise-equal trajectories
    (see :func:`_scan_chunked`).
    """
    from ..sim.backend import resolve_backend  # dependency-free

    get_law(distribution)  # eager: unknown laws fail here with the options
    if key is None:
        key = jax.random.PRNGKey(seed)
    if m_max is None:
        m_max = int(m)
    if resolve_backend(backend) == "pallas":
        from ..sim.batched_events import simulate_stats_lanes

        stats = simulate_stats_lanes(
            jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], params),
            jnp.asarray(m)[None], int(num_updates), warmup=int(warmup),
            keys=key[None], distribution=distribution,
            power=None if power is None else jax.tree_util.tree_map(
                lambda x: jnp.asarray(x)[None], power),
            m_max=m_max, backend="pallas", interpret=interpret, chunk=chunk)
        return jax.tree_util.tree_map(lambda x: x[0], stats)
    return _simulate_stats(params, m, key, int(num_updates), int(warmup),
                           distribution, m_max, power, int(chunk))


# ---------------------------------------------------------------------------
# class-aggregated event engine (O(#classes) per-event statistics)
# ---------------------------------------------------------------------------

class ClassEventState(NamedTuple):
    """Carry of the class-aggregated event scan.

    The task table is identical to :class:`EventState` except each task is
    owned by a ``(cls, member)`` pair — the class index plus the member
    index *within* the class — instead of a flat client id.  All per-client
    statistics collapse to per-class aggregates (members of a class are
    exchangeable, Section 2.6 product form), so the carry is O(#classes)
    wide no matter how large the population: ``n = 10^5..10^6`` simulates
    at the same per-event cost as ``n = 10^2``.
    """

    t: jax.Array          # current wall-clock time
    key: jax.Array        # PRNG carry
    round: jax.Array      # updates completed so far
    seq_ctr: jax.Array    # global FIFO arrival counter
    cls: jax.Array        # [m_max] owning class of each task
    member: jax.Array     # [m_max] member index within the class
    phase: jax.Array      # [m_max]
    finish: jax.Array     # [m_max]
    seq: jax.Array        # [m_max]
    disp_round: jax.Array  # [m_max]
    warmup: jax.Array
    cap: jax.Array
    t_cap: jax.Array
    t0: jax.Array
    t1: jax.Array
    delay_sum: jax.Array  # [C] per-class relative-delay sums
    delay_cnt: jax.Array  # [C]
    energy: jax.Array
    occ_int: jax.Array    # [3C+1] time-weighted per-class occupancy
    occ: jax.Array        # [3C+1] current per-class occupancy
    serving: jax.Array    # [C] count of busy compute servers of each class
    cs_busy: jax.Array


def _route_class(mass: jax.Array, count: jax.Array, key: jax.Array,
                 prefix: Optional[jax.Array] = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Draw ``(class, member)`` for one dispatch.

    Two shape-independent draws: the class by inverse-CDF of the class
    masses ``count * p`` (one scalar uniform against the sequential prefix,
    exactly :func:`_route_client` on the class axis — padded count-0
    classes carry zero mass and repeat the total in the prefix, so
    ``searchsorted`` never lands on them), then a uniform member index in
    ``[0, count[class])`` (one scalar ``randint``; the traced bound only
    depends on the drawn class).  Trajectories are therefore **bitwise
    invariant** to trailing class padding.  The clip targets the last class
    with nonzero count (not the last row, which may be padded) so the
    measure-zero ``u * total >= total`` edge cannot select an empty class.
    """
    if prefix is None:
        prefix = seqcumsum(mass)
    k_cls, k_mem = jax.random.split(key)
    u = jax.random.uniform(k_cls, dtype=mass.dtype) * prefix[-1]
    idx = jnp.searchsorted(prefix, u, side="right")
    cum = seqcumsum(count)
    c_last = jnp.searchsorted(cum, cum[-1] - 1, side="right")
    c = jnp.minimum(idx, c_last).astype(jnp.int32)
    mb = jax.random.randint(k_mem, (), 0, jnp.maximum(count[c], 1))
    return c, mb.astype(jnp.int32)


def draw_class_event_blocks(classes, key: jax.Array, chunk: int, *,
                            distribution: str = "exponential",
                            route_prefix: Optional[jax.Array] = None
                            ) -> tuple[jax.Array, EventBlocks]:
    """Class-engine analogue of :func:`draw_event_blocks`: the routing
    draw resolves a ``(class, member)`` pair per event, the downlink/CS
    services resolve fully, uplink/computation store the law's unit parts
    (or raw subkeys).  Same tiny-carry key chain, same per-law split
    between vmapped block draws and the sequential scalar-shape fallback
    — bitwise the single-step stream."""
    law = get_law(distribution)
    has_cs = classes.mu_cs is not None

    if law.unit_draw is None:
        def body(k, _):
            k2, k_up, k_disp, k_svc, k_comp, k_cs = jax.random.split(k, 6)
            c_new, mb_new = _route_class(classes.mass, classes.count, k_disp,
                                         route_prefix)
            svc_down = _draw(k_svc, classes.mu_d[c_new], distribution)
            svc_cs = (_draw(k_cs, classes.mu_cs, distribution)
                      if has_cs else ())
            blk = EventBlocks(c_new=c_new, member=mb_new, svc_down=svc_down,
                              up=k_up, comp=k_comp, svc_cs=svc_cs)
            return k2, (k2, blk)

        _, (chain, blks) = jax.lax.scan(body, key, None, length=chunk)
        return chain, blks

    def split6(k, _):
        ks = jax.random.split(k, 6)
        return ks[0], (ks[0], ks[1], ks[2], ks[3], ks[4], ks[5])

    _, (chain, k_up, k_disp, k_svc, k_comp, k_cs) = jax.lax.scan(
        split6, key, None, length=chunk)
    c_new, mb_new = jax.vmap(lambda k: _route_class(
        classes.mass, classes.count, k, route_prefix))(k_disp)
    svc_down = jax.vmap(
        lambda k, r: _draw(k, r, distribution))(k_svc, classes.mu_d[c_new])
    up = jax.vmap(law.unit_draw)(k_up)
    comp = jax.vmap(law.unit_draw)(k_comp)
    svc_cs = (jax.vmap(lambda k: _draw(k, classes.mu_cs, distribution))(k_cs)
              if has_cs else ())
    return chain, EventBlocks(c_new=c_new, member=mb_new, svc_down=svc_down,
                              up=up, comp=comp, svc_cs=svc_cs)


def _class_station_counts(phase, cls, C):
    """Per-class occupancy recount: down[C], comp_total[C],
    comp_serving[C], up[C], cs_total, cs_busy.

    ``comp_serving[c]`` counts the COMP_SERV tasks of class ``c`` — each
    member's compute server holds at most one, so this is exactly the
    number of busy compute servers of the class.  Used to seed the O(1)
    occupancy carries at :func:`init_class_state` and as the test oracle.
    """
    def count(mask):
        return jnp.zeros((C,), jnp.float64).at[cls].add(
            jnp.where(mask, 1.0, 0.0))

    down = count(phase == DOWN)
    comp_total = count((phase == COMP_WAIT) | (phase == COMP_SERV))
    comp_serving = count(phase == COMP_SERV)
    up = count(phase == UP)
    # contract: allow(raw-reduction): 0/1 indicator count over the task table — exact small-integer f64 under any association, and the table axis is m_max (never padded-n)
    cs_total = jnp.sum(
        jnp.where((phase == CS_WAIT) | (phase == CS_SERV), 1.0, 0.0))
    cs_busy = jnp.any(phase == CS_SERV)
    return down, comp_total, comp_serving, up, cs_total, cs_busy


def init_class_state(classes, m, key: jax.Array, *,
                     m_max: Optional[int] = None,
                     distribution: str = "exponential",
                     warmup=0, cap=_NO_CAP, t_cap=jnp.inf) -> ClassEventState:
    """Initial state of the class engine: ``m`` tasks dispatched uniformly
    at random over the ``n_total`` population members at ``t = 0``.

    The uniform member is drawn as a flat index in ``[0, n_total)`` and
    split into ``(class, member)`` against the sequential count prefix —
    the same distribution as :func:`init_state` on the expanded network,
    and bitwise invariant to trailing class padding (padded classes repeat
    ``n_total`` in the prefix, and the flat draw is strictly below it).
    """
    C = classes.C
    if m_max is None:
        m_max = int(m)
    key, k_cli, k_svc = jax.random.split(key, 3)
    cum = seqcumsum(classes.count)
    idx = jax.random.randint(k_cli, (m_max,), 0, cum[-1])
    cls = jnp.searchsorted(cum, idx, side="right").astype(jnp.int32)
    member = (idx - jnp.where(cls > 0, cum[jnp.maximum(cls - 1, 0)], 0)
              ).astype(jnp.int32)
    active = jnp.arange(m_max) < m
    svc = _draw(k_svc, classes.mu_d[cls], distribution, (m_max,))
    phase0 = jnp.where(active, DOWN, INACTIVE).astype(jnp.int32)
    down, comp_total, comp_serving, up, cs_total, cs_busy = (
        _class_station_counts(phase0, cls, C))
    return ClassEventState(
        t=jnp.zeros((), jnp.float64),
        key=key,
        round=jnp.zeros((), jnp.int32),
        seq_ctr=jnp.zeros((), jnp.int32),
        cls=cls,
        member=member,
        phase=phase0,
        finish=jnp.where(active, svc, jnp.inf),
        seq=jnp.zeros((m_max,), jnp.int32),
        disp_round=jnp.zeros((m_max,), jnp.int32),
        warmup=jnp.asarray(warmup, jnp.int32),
        cap=jnp.asarray(cap, jnp.int32),
        t_cap=jnp.asarray(t_cap, jnp.float64),
        t0=jnp.zeros((), jnp.float64),
        t1=jnp.zeros((), jnp.float64),
        delay_sum=jnp.zeros((C,), jnp.float64),
        delay_cnt=jnp.zeros((C,), jnp.int32),
        energy=jnp.zeros((), jnp.float64),
        occ_int=jnp.zeros((3 * C + 1,), jnp.float64),
        occ=jnp.concatenate([down, comp_total, up, cs_total[None]]),
        serving=comp_serving,
        cs_busy=cs_busy,
    )


def step_class_event(classes, state: ClassEventState, *,
                     distribution: str = "exponential",
                     power=None,
                     route_prefix: Optional[jax.Array] = None
                     ) -> tuple[ClassEventState, EventOut]:
    """Class-aggregated :func:`step_event`: one service completion, with
    every per-client surface replaced by its per-class aggregate.

    The dynamics are *identical* to the expanded network's — FIFO
    promotion conditions on the completed task's ``(class, member)`` pair,
    so each member still owns a private single-server compute queue — only
    the carried statistics collapse.  ``power`` (when given) holds
    per-class ``[C]`` arrays.  The emitted :class:`EventOut` reports the
    completed task's *class* in the ``client`` field.

    Like :func:`step_event`, a one-event block draw over
    :func:`step_class_event_block` — bitwise the historical inline body.
    """
    law = get_law(distribution)
    key, k_up, k_disp, k_disp_svc, k_comp, k_cs = jax.random.split(
        state.key, 6)
    c_new, mb_new = _route_class(classes.mass, classes.count, k_disp,
                                 route_prefix)
    svc_down = _draw(k_disp_svc, classes.mu_d[c_new], distribution)
    if law.unit_draw is not None:
        up, comp = law.unit_draw(k_up), law.unit_draw(k_comp)
    else:
        up, comp = k_up, k_comp
    svc_cs = (_draw(k_cs, classes.mu_cs, distribution)
              if classes.mu_cs is not None else ())
    blk = EventBlocks(c_new=c_new, member=mb_new, svc_down=svc_down,
                      up=up, comp=comp, svc_cs=svc_cs)
    return step_class_event_block(classes, state._replace(key=key), blk,
                                  distribution=distribution, power=power)


def step_class_event_block(classes, state: ClassEventState,
                           blk: EventBlocks, *,
                           distribution: str = "exponential",
                           power=None) -> tuple[ClassEventState, EventOut]:
    """Class analogue of :func:`step_event_block`: one event with its
    randomness pre-resolved (``state.key`` passes through untouched)."""
    C = classes.C
    m_max = state.phase.shape[0]
    has_cs = classes.mu_cs is not None

    j = jnp.argmin(state.finish)
    t_new = state.finish[j]

    measure = (state.round >= state.warmup) & (state.round < state.cap)
    dt_eff = jnp.where(
        measure,
        jnp.clip(jnp.minimum(t_new, state.t_cap)
                 - jnp.minimum(state.t, state.t_cap), 0.0, None),
        0.0)
    occ_int = state.occ_int + dt_eff * state.occ
    energy = state.energy
    if power is not None:
        # serving is a per-class busy-server COUNT (members share the class
        # power rating), uplink/downlink go by the class occupancy segments
        pwr = seqsum(power.P_c * state.serving
                     + power.P_u * state.occ[2 * C:3 * C]
                     + power.P_d * state.occ[:C])
        if power.P_cs is not None:
            pwr = pwr + power.P_cs * state.cs_busy
        energy = energy + dt_eff * pwr

    c = state.cls[j]
    mb = state.member[j]
    ph = state.phase[j]

    is_down = ph == DOWN
    is_comp = ph == COMP_SERV
    is_up = ph == UP
    is_cs = ph == CS_SERV
    is_update = is_cs if has_cs else is_up

    delay = state.round - state.disp_round[j]
    new_round = state.round + jnp.where(is_update, 1, 0).astype(jnp.int32)

    c_new, mb_new = blk.c_new, blk.member
    svc_up = _apply_unit(blk.up, classes.mu_u[c], distribution)
    svc_down = blk.svc_down

    phase_j = jnp.where(
        is_down, COMP_WAIT,
        jnp.where(is_comp, UP, jnp.where(is_update, DOWN, CS_WAIT)))
    finish_j = jnp.where(
        is_comp, t_new + svc_up,
        jnp.where(is_update, t_new + svc_down, jnp.inf))
    joins_fifo = is_down | (is_up & has_cs)
    seq_j = jnp.where(joins_fifo, state.seq_ctr, state.seq[j])
    seq_ctr = state.seq_ctr + joins_fifo.astype(jnp.int32)
    cls_j = jnp.where(is_update, c_new, c)
    member_j = jnp.where(is_update, mb_new, mb)
    disp_j = jnp.where(is_update, new_round, state.disp_round[j])

    onej = jnp.arange(m_max) == j
    phase = jnp.where(onej, phase_j, state.phase).astype(jnp.int32)
    finish = jnp.where(onej, finish_j, state.finish)
    seq = jnp.where(onej, seq_j, state.seq).astype(jnp.int32)
    cls = jnp.where(onej, cls_j, state.cls).astype(jnp.int32)
    member = jnp.where(onej, member_j, state.member).astype(jnp.int32)
    disp_round = jnp.where(onej, disp_j, state.disp_round).astype(jnp.int32)

    # -- FIFO promotions: the compute queue belongs to MEMBER (c, mb) -------
    promo_comp = is_down | is_comp
    mine = (cls == c) & (member == mb)
    serving_m = jnp.any((phase == COMP_SERV) & mine)
    waiting_m = (phase == COMP_WAIT) & mine
    pick = jnp.argmin(jnp.where(waiting_m, seq, _BIG_SEQ))
    do_comp = promo_comp & ~serving_m & jnp.any(waiting_m)
    svc_c = _apply_unit(blk.comp, classes.mu_c[c], distribution)
    onep = (jnp.arange(m_max) == pick) & do_comp
    phase = jnp.where(onep, COMP_SERV, phase)
    finish = jnp.where(onep, t_new + svc_c, finish)

    if has_cs:
        promo_cs = is_up | is_cs
        cs_waiting = phase == CS_WAIT
        pick_cs = jnp.argmin(jnp.where(cs_waiting, seq, _BIG_SEQ))
        do_cs = promo_cs & ~jnp.any(phase == CS_SERV) & jnp.any(cs_waiting)
        onec = (jnp.arange(m_max) == pick_cs) & do_cs
        phase = jnp.where(onec, CS_SERV, phase)
        finish = jnp.where(onec, t_new + blk.svc_cs, finish)

    stations = jnp.arange(3 * C + 1)
    occ_new = (state.occ
               + jnp.where(stations == _station_index(phase_j, cls_j, C),
                           1.0, 0.0)
               - jnp.where(stations == _station_index(ph, c, C), 1.0, 0.0))
    delta_srv = (jnp.where(do_comp, 1.0, 0.0)
                 - jnp.where(is_comp, 1.0, 0.0))
    serving_new = state.serving + jnp.where(jnp.arange(C) == c,
                                            delta_srv, 0.0)
    cs_busy_new = ((state.cs_busy & ~is_cs) | do_cs if has_cs
                   else state.cs_busy)

    upd_measured = is_update & measure
    delay_sum = state.delay_sum.at[c].add(
        jnp.where(upd_measured, delay.astype(jnp.float64), 0.0))
    delay_cnt = state.delay_cnt.at[c].add(
        jnp.where(upd_measured, 1, 0).astype(jnp.int32))
    t0 = jnp.where(is_update & (new_round == state.warmup), t_new, state.t0)
    t1 = jnp.where(is_update & (new_round == state.cap), t_new, state.t1)

    new_state = ClassEventState(
        t=t_new, key=state.key, round=new_round, seq_ctr=seq_ctr,
        cls=cls, member=member, phase=phase, finish=finish, seq=seq,
        disp_round=disp_round,
        warmup=state.warmup, cap=state.cap, t_cap=state.t_cap,
        t0=t0, t1=t1, delay_sum=delay_sum, delay_cnt=delay_cnt,
        energy=energy, occ_int=occ_int,
        occ=occ_new, serving=serving_new, cs_busy=cs_busy_new)
    out = EventOut(is_update=is_update,
                   time=t_new,
                   slot=j.astype(jnp.int32),
                   client=c,
                   delay=delay.astype(jnp.int32))
    return new_state, out


@functools.partial(jax.jit, static_argnames=(
    "num_updates", "warmup", "distribution", "m_max", "chunk"))
def _simulate_stats_classes(classes, m, key, num_updates, warmup,
                            distribution, m_max, power, chunk=1):
    mult = 4 if classes.mu_cs is not None else 3
    num_events = mult * (num_updates + warmup) + mult * m_max + 8
    cap = warmup + num_updates
    st = init_class_state(classes, m, key, m_max=m_max,
                          distribution=distribution, warmup=warmup, cap=cap)
    # hoisted loop-invariant routing CDF (see _simulate_stats)
    route_prefix = seqcumsum(classes.mass)

    if chunk == 1:
        def body(st, _):
            st, _ = step_class_event(classes, st, distribution=distribution,
                                     power=power, route_prefix=route_prefix)
            return st, None

        st, _ = jax.lax.scan(body, st, None, length=num_events)
        return finalize_stats(st)

    def draw(key):
        return draw_class_event_blocks(classes, key, chunk,
                                       distribution=distribution,
                                       route_prefix=route_prefix)

    def step(st, blk):
        return step_class_event_block(classes, st, blk,
                                      distribution=distribution, power=power)

    st, _ = _scan_chunked(step, draw, st, num_events, chunk)
    return finalize_stats(st)


@functools.partial(jax.jit, static_argnames=(
    "num_updates", "warmup", "distribution", "m_max", "trace_events",
    "chunk"))
def _simulate_stats_classes_traced(classes, m, key, num_updates, warmup,
                                   distribution, m_max, power, trace_events,
                                   chunk=1):
    """:func:`_simulate_stats_classes` carrying an event ring (the
    ``client`` column records the completed task's *class*; stations use
    the ``[3C+1]`` class layout).  Bitwise non-invasive, like
    :func:`_simulate_stats_traced`."""
    from ..obs.rings import event_ring_append, event_ring_init

    mult = 4 if classes.mu_cs is not None else 3
    num_events = mult * (num_updates + warmup) + mult * m_max + 8
    cap = warmup + num_updates
    st = init_class_state(classes, m, key, m_max=m_max,
                          distribution=distribution, warmup=warmup, cap=cap)
    route_prefix = seqcumsum(classes.mass)
    C = classes.C
    ring = event_ring_init(int(trace_events))

    if chunk == 1:
        def body(carry, _):
            st, ring = carry
            st2, out = step_class_event(classes, st,
                                        distribution=distribution,
                                        power=power,
                                        route_prefix=route_prefix)
            ph = st.phase[out.slot]
            ring = event_ring_append(
                ring, time=out.time,
                station=_station_index(ph, out.client, C),
                station_to=_station_index(st2.phase[out.slot],
                                          st2.cls[out.slot], C),
                kind=ph, slot=out.slot, client=out.client, delay=out.delay,
                update=out.is_update)
            return (st2, ring), None

        (st, ring), _ = jax.lax.scan(body, (st, ring), None,
                                     length=num_events)
        return finalize_stats(st), ring

    def draw(key):
        return draw_class_event_blocks(classes, key, chunk,
                                       distribution=distribution,
                                       route_prefix=route_prefix)

    def step(st, blk):
        return step_class_event_block(classes, st, blk,
                                      distribution=distribution, power=power)

    def append(ring, pre, post, out, keep):
        ph = pre.phase[out.slot]
        return event_ring_append(
            ring, time=out.time,
            station=_station_index(ph, out.client, C),
            station_to=_station_index(post.phase[out.slot],
                                      post.cls[out.slot], C),
            kind=ph, slot=out.slot, client=out.client, delay=out.delay,
            update=out.is_update, valid=keep)

    st, ring = _scan_chunked(step, draw, st, num_events, chunk,
                             ring=ring, append=append)
    return finalize_stats(st), ring


def simulate_stats_classes(classes, m, num_updates: int, *,
                           warmup: int = 0, key: Optional[jax.Array] = None,
                           seed: int = 0, distribution: str = "exponential",
                           power=None,
                           m_max: Optional[int] = None,
                           chunk: int = 1) -> EventStats:
    """Class-aggregated :func:`simulate_stats`: statistics over
    ``num_updates`` rounds with O(#classes) per-event state.

    Returns an :class:`EventStats` whose per-client fields are per-CLASS
    aggregates (``mean_delay``/``delay_counts`` of shape ``[C]``, occupancy
    ``[3C+1]``); expand to the per-member view on demand with
    :func:`expand_class_stats`.  ``power`` (when given) must hold per-class
    ``[C]`` arrays.  Runs on the jnp step only — the class table transition
    has no Pallas kernel (per-event cost is already n-independent).
    """
    get_law(distribution)  # eager: unknown laws fail here with the options
    if key is None:
        key = jax.random.PRNGKey(seed)
    if m_max is None:
        m_max = int(m)
    return _simulate_stats_classes(classes, m, key, int(num_updates),
                                   int(warmup), distribution, m_max, power,
                                   int(chunk))


def expand_class_stats(stats: EventStats, count) -> EventStats:
    """Expand per-class :class:`EventStats` to the per-member view.

    Host-side, on demand (O(n) by construction — the class engine never
    materializes per-member state).  Members of a class are exchangeable,
    so class aggregates expand to per-member *averages*: ``mean_delay``
    repeats the class mean, ``delay_counts`` becomes the average count per
    member (``cnt_c / count_c``, a float), and each per-class occupancy
    segment divides equally among the members.  Padded count-0 classes are
    dropped.  Works on any number of leading lane axes.
    """
    cnt = np.asarray(count)
    keep = cnt > 0
    reps = cnt[keep].astype(np.int64)
    w = reps.astype(np.float64)
    C = cnt.shape[0]

    def rep(x, per_member=False):
        x = np.asarray(x)[..., keep]
        if per_member:
            x = x / w
        return np.repeat(x, reps, axis=-1)

    occ = np.asarray(stats.mean_queue_counts)
    return EventStats(
        updates=stats.updates,
        time=stats.time,
        throughput=stats.throughput,
        mean_delay=jnp.asarray(rep(stats.mean_delay)),
        delay_counts=jnp.asarray(rep(stats.delay_counts, per_member=True)),
        energy=stats.energy,
        mean_queue_counts=jnp.asarray(np.concatenate(
            [rep(occ[..., 0:C], per_member=True),
             rep(occ[..., C:2 * C], per_member=True),
             rep(occ[..., 2 * C:3 * C], per_member=True),
             occ[..., 3 * C:]], axis=-1)),
    )
