"""Plain reference of the closed-network event dynamics (Fig. 1 of the paper).

One lane at a time, one event per loop iteration, in NumPy: ``m`` tasks
circulate downlink (infinite server) -> the owner's compute queue (single
server, FIFO) -> uplink (infinite server); an uplink completion is a model
update and re-dispatches a fresh task to a client drawn from the routing
``p``.  Every completion time is an absolute clock and the next event is
the earliest one (first slot on ties).  Statistics are taken over the
update-count window ``[warmup, warmup + updates)``.

The randomness is JAX's threefry stream consumed in the event engine's
order, so a run of the same lane seed follows the same trajectory:

* start: ``key, k_client, k_service = split(PRNGKey(seed), 3)``; ``m_max``
  owners drawn with ``randint`` and ``m_max`` exponential downlink draws;
* event ``i``: ``key, k_up, k_route, k_down, k_comp, _ = split(key, 6)``;
  routing is one ``uniform`` against the running sum of ``p``; services
  are unit exponentials over the server's rate.

The draws are made up front (they do not depend on the state), the
dynamics and statistics here.  ``dtype`` is the clock's precision:
``float64`` as the configuration states, ``float32`` for the control.
Nothing here imports the system under test.
"""
from __future__ import annotations

import collections
import functools

import numpy as np

DOWN, COMP_WAIT, COMP_SERV, UP = 0, 1, 2, 3


def _jax():
    import jax

    jax.config.update("jax_enable_x64", True)
    return jax


@functools.lru_cache(maxsize=None)
def _chain_fn(num_events: int):
    """Jitted (on the host CPU) draw of every event's randomness."""
    jax = _jax()
    jnp = jax.numpy

    def body(k, _):
        ks = jax.random.split(k, 6)
        return ks[0], (ks[1], ks[2], ks[3], ks[4])

    def draws(key):
        _, (k_up, k_route, k_down, k_comp) = jax.lax.scan(
            body, key, None, length=num_events)
        exp = jax.vmap(lambda k: jax.random.exponential(k, (), jnp.float64))
        route_u = jax.vmap(
            lambda k: jax.random.uniform(k, (), jnp.float64))(k_route)
        return route_u, exp(k_down), exp(k_up), exp(k_comp)

    return jax.jit(draws)


def _start(seed: int, m_max: int, population: int):
    """Initial owners and unit downlink draws."""
    jax = _jax()
    key, k_cli, k_svc = jax.random.split(jax.random.PRNGKey(seed), 3)
    owners = np.asarray(jax.random.randint(k_cli, (m_max,), 0, population))
    unit = np.asarray(jax.random.exponential(k_svc, (m_max,),
                                             jax.numpy.float64))
    return key, owners, unit


def lane_stats(fleet: dict, p, m: int, m_max: int, seed: int, warmup: int,
               updates: int, dtype=np.float64) -> dict:
    """Stationary statistics of one lane (its draws on the host CPU).

    ``fleet`` holds per-client rates ``mu_c``, ``mu_d``, ``mu_u``.
    Returns ``updates``, ``time``, ``throughput``, ``mean_delay`` and
    ``delay_counts`` per row, and ``mean_queue_counts`` (``[3R + 1]``:
    downlink, compute and uplink of each row, then the absent CS station).
    """
    jax = _jax()
    with jax.default_device(jax.devices("cpu")[0]):
        return _lane_stats(fleet, p, m, m_max, seed, warmup, updates, dtype)


def _lane_stats(fleet, p, m, m_max, seed, warmup, updates, dtype):
    f = np.dtype(dtype).type
    mu_c = np.asarray(fleet["mu_c"], np.float64).astype(dtype)
    mu_d = np.asarray(fleet["mu_d"], np.float64).astype(dtype)
    mu_u = np.asarray(fleet["mu_u"], np.float64).astype(dtype)
    p = np.asarray(p, np.float64)
    R = len(mu_c)
    num_events = 3 * (warmup + updates) + 3 * m_max + 8
    cap = warmup + updates

    key, owners, unit0 = _start(seed, m_max, R)
    route_u, e_down, e_up, e_comp = _chain_fn(num_events)(key)
    route_u = np.asarray(route_u)
    e_down = np.asarray(e_down).astype(dtype)
    e_up = np.asarray(e_up).astype(dtype)
    e_comp = np.asarray(e_comp).astype(dtype)

    # routing draws: the client by the running sum of the routing mass
    prefix = np.cumsum(p)
    row_new = np.minimum(np.searchsorted(prefix, route_u * prefix[-1],
                                         side="right"), R - 1)

    # task table
    row = owners.astype(np.int64)
    phase = np.full(m_max, -1)
    phase[:m] = DOWN
    finish = np.full(m_max, np.inf, dtype)
    finish[:m] = (unit0.astype(dtype) / mu_d[row])[:m]
    dispatched = np.zeros(m_max, np.int64)
    queues = collections.defaultdict(collections.deque)  # client -> waiters
    busy = set()                                         # clients in service

    occ = np.zeros(3 * R + 1, dtype)
    for j in range(m):
        occ[row[j]] += 1
    occ_int = np.zeros(3 * R + 1, dtype)
    delay_sum = np.zeros(R, dtype)
    delay_cnt = np.zeros(R, np.int64)
    t, t0, t1 = f(0), f(0), f(0)
    rnd = 0

    def start_service(j, now, i):
        finish[j] = now + e_comp[i] / mu_c[row[j]]
        phase[j] = COMP_SERV

    for i in range(num_events):
        if rnd >= cap:
            break  # the window is closed: nothing measured changes
        j = int(np.argmin(finish))
        now = finish[j]
        if warmup <= rnd:
            occ_int += max(now - t, f(0)) * occ
        r, ph = int(row[j]), phase[j]
        if ph == DOWN:
            occ[r] -= 1
            occ[R + r] += 1
            phase[j] = COMP_WAIT
            finish[j] = np.inf
            if r in busy:
                queues[r].append(j)
            else:
                busy.add(r)
                start_service(j, now, i)
        elif ph == COMP_SERV:
            occ[R + r] -= 1
            occ[2 * R + r] += 1
            phase[j] = UP
            finish[j] = now + e_up[i] / mu_u[r]
            if queues[r]:
                start_service(queues[r].popleft(), now, i)
            else:
                busy.discard(r)
        else:  # UP: a model update, and a fresh task for the freed slot
            if warmup <= rnd:
                delay_sum[r] += f(rnd - dispatched[j])
                delay_cnt[r] += 1
            rnd += 1
            if rnd == warmup:
                t0 = now
            if rnd == cap:
                t1 = now
            occ[2 * R + r] -= 1
            r_new = int(row_new[i])
            row[j] = r_new
            occ[r_new] += 1
            phase[j] = DOWN
            finish[j] = now + e_down[i] / mu_d[r_new]
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
