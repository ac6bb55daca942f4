"""Plain reference of the paper's closed forms and of the ``time_opt``
search, in float64 ``jax.numpy`` on the host CPU.

For a fleet of ``n`` clients (compute rate ``mu_c``, downlink ``mu_d``,
uplink ``mu_u``) under routing ``p`` and concurrency ``m``:

* Buzen's normalizing constants ``Z[0..M]`` of the closed network: the
  ``2n`` infinite-server stations (downlink, uplink) enter as one Poisson
  factor of load ``sum_i p_i (1/mu_d,i + 1/mu_u,i)``, each compute queue as
  a geometric factor of load ``p_i / mu_c,i`` (in log space);
* Prop. 4: throughput ``lambda = Z[m-1] / Z[m]``;
* Thm 2: the mean relative delay of client ``i``, the mean number of its
  tasks in the network at population ``m - 1``;
* Thm 3: the round complexity ``K_eps``, and ``tau = K_eps / lambda``;
* ``time_opt``: for each ``m``, Adam (lr 0.05, betas 0.9 / 0.999, eps
  1e-8, bias-corrected) on the logits of ``p = softmax(theta)`` from the
  uniform routing, minimising ``tau``, for a given number of steps.

``dtype`` is the precision: ``float64`` as the configuration states,
``float32`` for the control.

Nothing here imports the system under test.
"""
from __future__ import annotations

import functools
import math

import numpy as np


def _jax():
    import jax

    jax.config.update("jax_enable_x64", True)
    return jax


def _consts(c: dict) -> tuple:
    B = 6.0 * (c["sigma"] ** 2 + 2.0 * c["M"] ** 2)
    C = 6.0 * (c["sigma"] ** 2 + c["G"] ** 2)
    return float(c["L"]), float(c["delta"]), float(c["eps"]), B, C


@functools.lru_cache(maxsize=None)
def _functions(M: int, consts: tuple, dtype: str):
    jax = _jax()
    jnp = jax.numpy
    from jax.scipy.special import logsumexp

    L, delta, eps, B, C = consts
    k = jnp.arange(M + 1)
    fk = k.astype(dtype)
    log_fact = jnp.asarray([math.lgamma(i + 1.0) for i in range(M + 1)],
                           dtype)
    diff = k[:, None] - k[None, :]          # [out, in]: out - in
    fdiff = diff.astype(dtype)

    def log_z(p, mu_c, mu_d, mu_u):
        lg = jnp.log(jnp.sum(p * (1.0 / mu_d + 1.0 / mu_u)))
        z = jnp.where(k == 0, 0.0, fk * lg - log_fact)

        def fold(z, lr):
            # new[a] = log sum_{b <= a} exp(z[b] + (a - b) lr)
            terms = jnp.where(diff >= 0, z[None, :] + fdiff * lr, -jnp.inf)
            return logsumexp(terms, axis=1), None

        z, _ = jax.lax.scan(fold, z, jnp.log(p) - jnp.log(mu_c))
        return z

    def at(z, i):
        return jnp.where(i >= 0, z[jnp.clip(i, 0, M)], -jnp.inf)

    def forms(p, m, mu_c, mu_d, mu_u):
        n = p.shape[0]
        z = log_z(p, mu_c, mu_d, mu_u)
        thr = jnp.exp(at(z, m - 1) - at(z, m))
        pop = m - 1
        lr = jnp.log(p) - jnp.log(mu_c)
        kk = jnp.arange(1, M + 1, dtype=dtype)
        terms = jnp.where(kk[None, :] <= pop,
                          lr[:, None] * kk[None, :]
                          + at(z, pop - k[1:])[None, :], -jnp.inf)
        comp = jnp.exp(logsumexp(terms, axis=1) - at(z, pop))
        gamma = p * (1.0 / mu_d + 1.0 / mu_u)
        delays = comp + gamma * jnp.exp(at(z, pop - 1) - at(z, pop))
        first = (4.0 + B / eps) * jnp.sum(1.0 / (n * p))
        stale = jnp.sum(delays / p ** 2)
        second = jnp.sqrt(C * (m - 1) / eps * stale)
        k_eps = 24.0 * L * delta / (n * eps) * (first + second)
        return {"throughput": thr, "K_eps": k_eps, "tau": k_eps / thr,
                "delays": delays}

    def optimize(m, steps, mu_c, mu_d, mu_u):
        n = mu_c.shape[0]
        b1, b2, e, lr = 0.9, 0.999, 1e-8, 0.05

        def loss(theta):
            return forms(jax.nn.softmax(theta), m, mu_c, mu_d, mu_u)["tau"]

        def step(carry, t):
            theta, mu, nu = carry
            g = jax.grad(loss)(theta)
            mu = b1 * mu + (1 - b1) * g
            nu = b2 * nu + (1 - b2) * g * g
            mh = mu / (1 - b1 ** (t + 1.0))
            nh = nu / (1 - b2 ** (t + 1.0))
            return (theta - lr * mh / (jnp.sqrt(nh) + e), mu, nu), None

        theta0 = jnp.full((n,), math.log(1.0 / n), dtype)
        zeros = jnp.zeros((n,), dtype)
        (theta, _, _), _ = jax.lax.scan(
            step, (theta0, zeros, zeros), jnp.arange(steps, dtype=dtype))
        p = jax.nn.softmax(theta)
        return p, forms(p, m, mu_c, mu_d, mu_u)["tau"]

    return (jax.jit(forms),
            jax.jit(jax.vmap(optimize, in_axes=(0, None, None, None, None)),
                    static_argnums=1))


def closed_forms(fleet: dict, p, m: int, constants: dict, m_max: int,
                 dtype=np.float64) -> dict:
    """Throughput, ``K_eps``, ``tau`` and per-client delays at ``(p, m)``."""
    jax = _jax()
    dt = np.dtype(dtype)
    forms, _ = _functions(int(m_max), _consts(constants), dt.name)
    a = [np.asarray(fleet[k], dt) for k in ("mu_c", "mu_d", "mu_u")]
    with jax.default_device(jax.devices("cpu")[0]):
        out = forms(np.asarray(p, dt), int(m), *a)
        return {k: np.asarray(v) for k, v in out.items()}


def time_opt(fleet: dict, ms, constants: dict, m_max: int, steps: int,
             dtype=np.float64) -> tuple:
    """``(p [len(ms), n], tau [len(ms)])`` of the search at each ``m``."""
    jax = _jax()
    dt = np.dtype(dtype)
    _, opt = _functions(int(m_max), _consts(constants), dt.name)
    a = [np.asarray(fleet[k], dt) for k in ("mu_c", "mu_d", "mu_u")]
    with jax.default_device(jax.devices("cpu")[0]):
        p, tau = opt(np.asarray(ms, np.int64), int(steps), *a)
        return np.asarray(p), np.asarray(tau)
