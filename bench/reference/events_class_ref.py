"""Plain reference of the closed-network event dynamics on a class fleet.

The dynamics of ``events_ref.py``, with the population given as classes
of exchangeable members: each task is owned by a ``(class, member)`` pair,
each member has a private FIFO compute queue, and the statistics are
per class (``updates``, ``time``, ``throughput``, ``mean_delay`` and
``delay_counts`` per class, ``mean_queue_counts`` as ``[3C + 1]``:
downlink, compute and uplink of each class, then the absent CS station).

The randomness is JAX's threefry stream consumed in the class engine's
order, so a run of the same lane seed follows the same trajectory:

* start: as ``events_ref.py``, with ``m_max`` flat member indices in
  ``[0, n)`` split into ``(class, member)`` against the running sum of the
  counts;
* event ``i``: ``key, k_up, k_route, k_down, k_comp, _ = split(key, 6)``
  and ``k_class, k_member = split(k_route)``: the class by one
  ``uniform`` times the total against the running sum of ``count * p``,
  clipped to the last class whose count is not zero, then the member by
  one ``randint`` in ``[0, count[class])``.

Nothing here imports the system under test.
"""
from __future__ import annotations

import collections
import functools

import numpy as np

from bench.reference.events_ref import (COMP_SERV, COMP_WAIT, DOWN, UP, _jax,
                                        _start)


@functools.lru_cache(maxsize=None)
def _chain_fn(num_events: int):
    """Jitted (on the host CPU) draw of every event's randomness; the
    member keys come back raw, for the member draw once the class is
    known."""
    jax = _jax()
    jnp = jax.numpy

    def body(k, _):
        ks = jax.random.split(k, 6)
        return ks[0], (ks[1], ks[2], ks[3], ks[4])

    def draws(key):
        _, (k_up, k_route, k_down, k_comp) = jax.lax.scan(
            body, key, None, length=num_events)
        k_class, k_member = jax.vmap(jax.random.split, out_axes=1)(k_route)
        exp = jax.vmap(lambda k: jax.random.exponential(k, (), jnp.float64))
        class_u = jax.vmap(
            lambda k: jax.random.uniform(k, (), jnp.float64))(k_class)
        return class_u, k_member, exp(k_down), exp(k_up), exp(k_comp)

    return jax.jit(draws)


@functools.lru_cache(maxsize=None)
def _member_fn():
    jax = _jax()
    return jax.jit(jax.vmap(lambda k, n: jax.random.randint(k, (), 0, n)))


def lane_stats(classes: dict, p, m: int, m_max: int, seed: int, warmup: int,
               updates: int, dtype=np.float64) -> dict:
    """Stationary per-class statistics of one lane (its draws on the host
    CPU).  ``classes`` holds per-class rates ``mu_c``, ``mu_d``, ``mu_u``
    and member counts ``count``; ``p`` is each member's routing
    probability, per class.  ``dtype`` is the clock's precision:
    ``float64`` as the configuration states, ``float32`` for the
    control."""
    jax = _jax()
    with jax.default_device(jax.devices("cpu")[0]):
        return _lane_stats(classes, p, m, m_max, seed, warmup, updates,
                           dtype)


def _lane_stats(classes, p, m, m_max, seed, warmup, updates, dtype):
    f = np.dtype(dtype).type
    mu_c = np.asarray(classes["mu_c"], np.float64).astype(dtype)
    mu_d = np.asarray(classes["mu_d"], np.float64).astype(dtype)
    mu_u = np.asarray(classes["mu_u"], np.float64).astype(dtype)
    count = np.asarray(classes["count"], np.int64)
    C = len(count)
    num_events = 3 * (warmup + updates) + 3 * m_max + 8
    cap = warmup + updates

    # initial owners: flat member indices split against the count prefix
    cum = np.cumsum(count)
    key, flat, unit0 = _start(seed, m_max, int(cum[-1]))
    cls = np.searchsorted(cum, flat, side="right")
    member = flat - np.where(cls > 0, cum[np.maximum(cls - 1, 0)], 0)

    class_u, k_member, e_down, e_up, e_comp = _chain_fn(num_events)(key)
    e_down = np.asarray(e_down).astype(dtype)
    e_up = np.asarray(e_up).astype(dtype)
    e_comp = np.asarray(e_comp).astype(dtype)

    # routing draws: the class by the running sum of the class masses
    prefix = np.cumsum(count * np.asarray(p, np.float64))
    last = int(np.flatnonzero(count > 0)[-1])
    cls_new = np.minimum(np.searchsorted(
        prefix, np.asarray(class_u) * prefix[-1], side="right"), last)
    mem_new = np.asarray(_member_fn()(k_member,
                                      np.maximum(count[cls_new], 1)))

    # task table
    cls = cls.astype(np.int64)
    member = member.astype(np.int64)
    phase = np.full(m_max, -1)
    phase[:m] = DOWN
    finish = np.full(m_max, np.inf, dtype)
    finish[:m] = (unit0.astype(dtype) / mu_d[cls])[:m]
    dispatched = np.zeros(m_max, np.int64)
    queues = collections.defaultdict(collections.deque)  # member -> waiters
    busy = set()                                         # members in service

    occ = np.zeros(3 * C + 1, dtype)
    for j in range(m):
        occ[cls[j]] += 1
    occ_int = np.zeros(3 * C + 1, dtype)
    delay_sum = np.zeros(C, dtype)
    delay_cnt = np.zeros(C, np.int64)
    t, t0, t1 = f(0), f(0), f(0)
    rnd = 0

    def start_service(j, now, i):
        finish[j] = now + e_comp[i] / mu_c[cls[j]]
        phase[j] = COMP_SERV

    for i in range(num_events):
        if rnd >= cap:
            break  # the window is closed: nothing measured changes
        j = int(np.argmin(finish))
        now = finish[j]
        if warmup <= rnd:
            occ_int += max(now - t, f(0)) * occ
        c, owner, ph = int(cls[j]), (int(cls[j]), int(member[j])), phase[j]
        if ph == DOWN:
            occ[c] -= 1
            occ[C + c] += 1
            phase[j] = COMP_WAIT
            finish[j] = np.inf
            if owner in busy:
                queues[owner].append(j)
            else:
                busy.add(owner)
                start_service(j, now, i)
        elif ph == COMP_SERV:
            occ[C + c] -= 1
            occ[2 * C + c] += 1
            phase[j] = UP
            finish[j] = now + e_up[i] / mu_u[c]
            if queues[owner]:
                start_service(queues[owner].popleft(), now, i)
            else:
                busy.discard(owner)
        else:  # UP: a model update, and a fresh task for the freed slot
            if warmup <= rnd:
                delay_sum[c] += f(rnd - dispatched[j])
                delay_cnt[c] += 1
            rnd += 1
            if rnd == warmup:
                t0 = now
            if rnd == cap:
                t1 = now
            occ[2 * C + c] -= 1
            c_new = int(cls_new[i])
            cls[j], member[j] = c_new, int(mem_new[i])
            occ[c_new] += 1
            phase[j] = DOWN
            finish[j] = now + e_down[i] / mu_d[c_new]
            dispatched[j] = rnd
        t = now

    done = min(rnd, cap) - warmup
    horizon = (t1 - t0) if rnd >= cap else (t - t0)
    safe = max(horizon, f(1e-12))
    return {
        "updates": int(done),
        "time": float(horizon),
        "throughput": float(done / safe) if horizon > 0 else 0.0,
        "mean_delay": np.where(delay_cnt > 0,
                               delay_sum / np.maximum(delay_cnt, 1), 0.0),
        "delay_counts": delay_cnt,
        "mean_queue_counts": occ_int / safe,
    }
