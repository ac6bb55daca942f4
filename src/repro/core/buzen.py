"""Buzen's recursive algorithm for closed-network normalization constants.

Implements Proposition 15 (client-only network of Section 2.6) and
Proposition 19 (network with a CS-side single-server queue, Section 7) of the
paper, in log space.

Network structure (Section 2.6):
  * ``n`` single-server FIFO queues ``c_i`` with service rate ``mu_c[i]`` and
    visit ratio ``p[i]``  ->  load ``rho[i] = p[i] / mu_c[i]``;
  * ``2n`` infinite-server queues (downlink ``d_i``, uplink ``u_i``) with
    loads ``p[i]/mu_d[i]`` and ``p[i]/mu_u[i]``.

With the CS buffer (Section 7) there is one extra single-server queue with
load ``1/mu_cs`` (every task visits the CS once per cycle; the multinomial
class structure of Eq. (20) sums out to a plain geometric factor, see
``DESIGN.md``).

Two evaluation strategies, tested to agree:

  * ``method="literal"`` — the station-by-station recursion of Prop. 15:
    each single-server station convolves the running constants with a
    geometric series, each IS station with a Poisson series.  O(n m^2).
  * ``method="aggregate"`` — beyond-paper fast path: all 2n IS stations
    merge analytically into a single Poisson factor with aggregate load
    ``gamma_tot = sum_i p_i (1/mu_d[i] + 1/mu_u[i])``, because product-form
    IS stations only enter Z through the total-load exponential series.
    O(n m + m^2).

All functions return ``logZ`` arrays of shape ``[m_max + 1]`` with
``logZ[k] = log Z_{n,k}``; ``Z_{n,0} = 1``.

Backends: the DP can also run on the Pallas TPU kernel
(``repro.kernels.buzen``).  Select it per call with ``backend="pallas"``,
process-wide with :func:`set_backend` (or ``REPRO_BUZEN_BACKEND=pallas``).
The kernel computes the forward pass in float32 (compiled on TPU,
interpreted elsewhere) and differentiates through the float64 reference, so
it is usable inside the routing optimizer; the default remains ``"jnp"``
because the analytic identities in the test-suite hold to 1e-12 only in
float64.
"""
from __future__ import annotations
# contract: padded-n — reductions here are on the bitwise padding contract

import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import logsumexp

from . import numerics  # noqa: F401  (enables x64)
from .numerics import NEG_INF, array_module, seqcumsum, seqsum

_BACKENDS = ("jnp", "pallas")
# contract: allow(env-read): import-time default only — set_backend() overrides it at runtime, nothing caches the value
_backend = os.environ.get("REPRO_BUZEN_BACKEND", "jnp")


def set_backend(name: str) -> None:
    """Set the process-wide default Buzen backend (``"jnp"``/``"pallas"``)."""
    global _backend
    if name not in _BACKENDS:
        raise ValueError(f"unknown buzen backend: {name!r}")
    _backend = name


def get_backend() -> str:
    return _backend


class NetworkParams(NamedTuple):
    """Rates of the closed queueing network (Section 2.6 / 7.1).

    Padded-``n`` convention: arrays may be padded to a static ``n_max``
    (zero routing mass, unit rates beyond the real population) with
    ``n_active`` holding the traced count of *real* clients — see
    :func:`pad_network`.  ``n_active is None`` means every row is real
    (the historical static-``n`` layout).  All closed forms and both event
    engines treat padded clients as structurally absent, bitwise.
    """

    p: jax.Array  # [n] routing probabilities (positive; need not sum to 1 for raw partials)
    mu_c: jax.Array  # [n] computation rates (single-server queues)
    mu_d: jax.Array  # [n] downlink rates (infinite-server queues)
    mu_u: jax.Array  # [n] uplink rates (infinite-server queues)
    mu_cs: Optional[jax.Array] = None  # scalar CS processing rate (None = infinite)
    n_active: Optional[jax.Array] = None  # traced real-client count (None = n)

    @property
    def n(self) -> int:
        return self.p.shape[0]

    @property
    def active_count(self):
        """Real-client count: the traced ``n_active`` if padded, else the
        static array length ``n``."""
        return self.n if self.n_active is None else self.n_active

    @property
    def active_mask(self) -> Optional[jax.Array]:
        """``[n] bool`` mask of real clients, or ``None`` when unpadded."""
        if self.n_active is None:
            return None
        return jnp.arange(self.n) < self.n_active

    @property
    def log_rho(self) -> jax.Array:
        """Log-loads of the client single-server (computation) queues."""
        return jnp.log(self.p) - jnp.log(self.mu_c)

    @property
    def gamma(self) -> jax.Array:
        """Per-client aggregate IS load ``gamma_i`` (Theorem 2)."""
        return self.p * (1.0 / self.mu_d + 1.0 / self.mu_u)

    @property
    def log_gamma_total(self) -> jax.Array:
        # sequential sum: padded clients (gamma = 0) must be bitwise
        # invisible, which XLA's reassociating reduce does not guarantee
        return jnp.log(seqsum(self.gamma))

    def with_cs(self, mu_cs) -> "NetworkParams":
        xp = array_module(self.p)
        return self._replace(mu_cs=xp.asarray(mu_cs, dtype=self.p.dtype))


def pad_network(params: NetworkParams, n_max: int) -> NetworkParams:
    """Pad a network to ``n_max`` client rows (the traced-``n`` convention).

    Padded rows carry zero routing mass and unit service rates, and
    ``n_active`` records the real population — so padded stations are
    load-0/visit-0 in the Buzen DP (the geometric factor of a load-0
    station is the convolution identity), padded clients receive zero
    dispatch probability in the event engines, and every downstream
    quantity is **bitwise** what the unpadded network produces (asserted in
    ``tests/test_padded_n.py``).  Mirrors the ``m_max`` convention of
    ``repro.core.batched``: one compiled program covers a whole
    mixed-population scenario batch.

    The kind of array is kept: NumPy rows pad to NumPy rows on the host
    (the simulate planner's lane batch), anything else to ``jax.numpy``.
    """
    n = params.n
    if n_max < n:
        raise ValueError(f"n_max={n_max} is smaller than the network's "
                         f"population n={n}")
    n_act = params.active_count  # re-padding keeps the original real count
    xp = array_module(params.p)

    def pad(x, fill):
        x = xp.asarray(x)
        return xp.concatenate(
            [x, xp.full((n_max - n,), fill, dtype=x.dtype)])

    return params._replace(
        p=pad(params.p, 0.0), mu_c=pad(params.mu_c, 1.0),
        mu_d=pad(params.mu_d, 1.0), mu_u=pad(params.mu_u, 1.0),
        n_active=xp.asarray(n_act, xp.int64))


class ClassParams(NamedTuple):
    """Class-aggregated network: ``C`` client classes with multiplicities.

    The product-form network depends on a client only through its
    ``(p, mu_c, mu_d, mu_u)`` profile, so ``count[c]`` identical clients
    collapse into one *class*: their ``count`` single-server computation
    stations enter the Buzen DP as a single negative-binomial generating
    series (the multiplicity is an analytic exponent, see
    :func:`_negbinom_series`), and the IS stations enter through the
    aggregate Poisson factor as always.  Closed forms become O(C) instead
    of O(n) — the scaling law for ``n = 10^5..10^6`` populations.

    ``p`` is the **per-member** routing mass (each member of class ``c``
    has routing probability ``p[c]``); the class as a whole carries mass
    ``count[c] * p[c]``.  Padded classes (the traced-``C`` convention of
    :func:`pad_classes`) have ``count = 0`` and ``p = 0`` and are bitwise
    invisible: their negative-binomial factor is the convolution identity
    and all class reductions are strictly sequential (``seqsum``).

    :meth:`expand` unrolls back to the per-client :class:`NetworkParams` —
    the oracle every class-space surface is tested against.
    """

    p: jax.Array  # [C] per-member routing mass (0 on padded classes)
    mu_c: jax.Array  # [C] computation rates
    mu_d: jax.Array  # [C] downlink rates
    mu_u: jax.Array  # [C] uplink rates
    count: jax.Array  # [C] integer multiplicity (0 = padded class)
    mu_cs: Optional[jax.Array] = None  # scalar CS rate (None = no CS station)

    @property
    def C(self) -> int:
        """Static class-axis length (including padded classes)."""
        return self.p.shape[0]

    @property
    def n_total(self):
        """Traced total population ``sum_c count[c]`` (padded classes add 0)."""
        return seqsum(self.count)

    @property
    def mass(self) -> jax.Array:
        """Class routing mass ``count * p`` (what the inverse-CDF routes on)."""
        return self.count.astype(self.p.dtype) * self.p

    @property
    def log_rho(self) -> jax.Array:
        """Per-member log-load of one computation station of each class."""
        return jnp.log(self.p) - jnp.log(self.mu_c)

    @property
    def gamma(self) -> jax.Array:
        """Per-member aggregate IS load ``gamma_c`` (Theorem 2)."""
        return self.p * (1.0 / self.mu_d + 1.0 / self.mu_u)

    @property
    def log_gamma_total(self) -> jax.Array:
        """Aggregate IS log-load over the whole population (sequential)."""
        return jnp.log(seqsum(self.count.astype(self.p.dtype) * self.gamma))

    def with_cs(self, mu_cs) -> "ClassParams":
        xp = array_module(self.p)
        return self._replace(mu_cs=xp.asarray(mu_cs, dtype=self.p.dtype))

    def expand(self) -> NetworkParams:
        """Unroll to the per-client network (host-side; the test oracle).

        Requires concrete counts — this is O(n) by construction and exists
        for validation and small-population interop, not for the hot path.
        """
        import numpy as np

        reps = np.asarray(self.count).astype(int)
        xp = array_module(self.p)

        def rep(x):
            return xp.asarray(np.repeat(np.asarray(x), reps))

        return NetworkParams(p=rep(self.p), mu_c=rep(self.mu_c),
                             mu_d=rep(self.mu_d), mu_u=rep(self.mu_u),
                             mu_cs=self.mu_cs)


def pad_classes(classes: ClassParams, c_max: int) -> ClassParams:
    """Pad a class set to ``c_max`` rows (the traced-``C`` convention).

    Padded classes carry zero count, zero routing mass and unit rates, so
    they are **bitwise** invisible to the class-space DP, closed forms and
    event engine (the class analogue of :func:`pad_network`): a count-0
    class has the convolution-identity negative-binomial factor, adds
    exactly 0 to every sequential class reduction, and receives zero mass
    in the routing inverse-CDF.  Like :func:`pad_network`, it keeps the
    kind of array it is given.
    """
    C = classes.C
    if c_max < C:
        raise ValueError(f"c_max={c_max} is smaller than the class-set "
                         f"size C={C}")
    xp = array_module(classes.p)

    def pad(x, fill):
        x = xp.asarray(x)
        return xp.concatenate(
            [x, xp.full((c_max - C,), fill, dtype=x.dtype)])

    return classes._replace(
        p=pad(classes.p, 0.0), mu_c=pad(classes.mu_c, 1.0),
        mu_d=pad(classes.mu_d, 1.0), mu_u=pad(classes.mu_u, 1.0),
        count=pad(classes.count, 0))


def classes_from_network(params: NetworkParams) -> ClassParams:
    """Group identical clients of a concrete network into classes.

    Host-side: rows with bitwise-equal ``(p, mu_c, mu_d, mu_u)`` profiles
    collapse into one class (first-occurrence order preserved).  Padded
    rows (beyond ``n_active``) are dropped — re-pad with
    :func:`pad_classes` if a static class axis is needed.
    """
    import numpy as np

    n = params.n if params.n_active is None else int(params.n_active)
    cols = np.stack([np.asarray(params.p)[:n], np.asarray(params.mu_c)[:n],
                     np.asarray(params.mu_d)[:n],
                     np.asarray(params.mu_u)[:n]], axis=1)
    _, first, counts = np.unique(
        cols, axis=0, return_index=True, return_counts=True)
    order = np.argsort(first)  # undo np.unique's lexicographic sort
    cols_u = cols[np.sort(first)]
    return ClassParams(
        p=jnp.asarray(cols_u[:, 0]), mu_c=jnp.asarray(cols_u[:, 1]),
        mu_d=jnp.asarray(cols_u[:, 2]), mu_u=jnp.asarray(cols_u[:, 3]),
        count=jnp.asarray(counts[order], dtype=jnp.int64),
        mu_cs=params.mu_cs)


def _log_conv(log_a: jax.Array, log_b: jax.Array) -> jax.Array:
    """Truncated convolution in log space.

    ``out[m] = logsumexp_{k=0..m} (log_a[k] + log_b[m - k])`` for
    ``m in [0, M]`` where both inputs have shape ``[M + 1]``.
    """
    M = log_a.shape[0] - 1
    k = jnp.arange(M + 1)
    # pairs[m, k] = log_a[k] + log_b[m - k], masked to k <= m
    idx = k[None, :]
    rev = jnp.arange(M + 1)[:, None] - idx  # m - k
    valid = rev >= 0
    terms = jnp.where(valid, log_a[None, :] + log_b[jnp.clip(rev, 0)], NEG_INF)
    # contract: allow(raw-reduction): logsumexp over the k = 0..m_max convolution axis — compile-time length, never client/class padded
    return logsumexp(terms, axis=1)


def _geometric_series(log_rho: jax.Array, m_max: int) -> jax.Array:
    """``[k * log_rho for k in 0..m_max]`` — generating series of a single-server station.

    The ``k = 0`` term is pinned to exactly ``0`` so a load-0 station
    (``log_rho = -inf``, e.g. a padded client under the traced-``n``
    convention) yields ``[0, -inf, ...]`` — the log-convolution identity —
    instead of a ``0 * inf`` NaN; for finite loads the ``where`` is
    bitwise-neutral.
    """
    k = jnp.arange(m_max + 1)
    return jnp.where(k == 0, 0.0, k * log_rho)


def _log_factorials(m_max: int) -> np.ndarray:
    """``[log k! for k in 0..m_max]``, a float64 constant of the trace.

    The table depends on the static ``m_max`` only, so no ``lgamma`` is
    traced: the TPU has no float64 unit and XLA's emulated float64
    ``lgamma`` costs seconds of compilation per use.
    """
    return np.array([math.lgamma(k + 1.0) for k in range(m_max + 1)])


def _poisson_series(log_load: jax.Array, m_max: int) -> jax.Array:
    """``[k log_load - log k! for k in 0..m_max]`` — series of an IS station
    (``k = 0`` pinned as in :func:`_geometric_series`)."""
    k = jnp.arange(m_max + 1)
    return jnp.where(k == 0, 0.0, k * log_load - _log_factorials(m_max))


def _log_multisets(count: jax.Array, m_max: int,
                   dtype=jnp.float64) -> jax.Array:
    """``[log C(j + count - 1, j) for j in 0..m_max]`` (``[..., m_max + 1]``
    for ``count`` of shape ``[...]``).

    That is ``lgamma(j + count) - lgamma(j + 1) - lgamma(count)``, formed
    as the left-to-right running sum of ``log((count + i) / (1 + i))`` over
    ``i < j``: no ``lgamma`` is traced (see :func:`_log_factorials`), and
    the large ``lgamma(count)`` terms never have to cancel.  ``count = 1``
    gives exactly ``0`` (each step is ``log(1 + i) - log(1 + i)``);
    ``count = 0`` gives ``-inf`` from ``j = 1`` on.
    """
    cnt = jnp.asarray(count, dtype=dtype)[..., None]
    i = jnp.arange(m_max, dtype=dtype)
    steps = jnp.log(cnt + i) - jnp.log(1.0 + i)
    return jnp.concatenate([jnp.zeros_like(cnt), seqcumsum(steps)], axis=-1)


def _negbinom_series(log_rho: jax.Array, count: jax.Array,
                     m_max: int) -> jax.Array:
    """Generating series of ``count`` identical single-server stations.

    ``count`` stations of per-member load ``rho`` contribute the factor
    ``(1 - rho x)^{-count} = sum_j C(j + count - 1, j) rho^j x^j`` — the
    multiplicity enters as an analytic exponent instead of ``count``
    convolution folds.  In log space::

        coef[j] = j log_rho + lgamma(j + count) - lgamma(j + 1) - lgamma(count)

    ``count = 0`` (a padded class) makes every ``j >= 1`` coefficient
    ``-inf`` (see :func:`_log_multisets`), and the ``j = 0`` term is pinned
    to exactly ``0`` — the convolution identity, mirroring the load-0 pin
    of :func:`_geometric_series`.  ``count = 1`` reduces to the geometric
    series exactly.
    """
    j = jnp.arange(m_max + 1)
    return jnp.where(j == 0, 0.0, j * log_rho + _log_multisets(count, m_max))


def log_normalizing_constants(
    params: NetworkParams,
    m_max: int,
    *,
    method: str = "aggregate",
    backend: Optional[str] = None,
) -> jax.Array:
    """Log normalization constants ``log Z_{n,m}`` for ``m = 0..m_max``.

    Includes the CS single-server station when ``params.mu_cs`` is not None
    (these are the ``W_{n,m}`` constants of Proposition 19).  ``backend``
    overrides the process-wide flag (see :func:`set_backend`); the Pallas
    path only implements the ``"aggregate"`` method.
    """
    backend = _backend if backend is None else backend
    if backend == "pallas":
        if method != "aggregate":
            raise ValueError(
                f"the pallas backend only implements method='aggregate', "
                f"got {method!r}")
        from .batched import batch_log_normalizing_constants  # lazy: no cycle

        return batch_log_normalizing_constants(
            params, params.p[None, :], m_max, backend="pallas")[0]
    if backend not in _BACKENDS:
        raise ValueError(f"unknown buzen backend: {backend!r}")

    log_rho = params.log_rho

    if method == "aggregate":
        # Start from the aggregated IS factor, then fold in single-server stations.
        logZ = _poisson_series(params.log_gamma_total, m_max)
        def fold(carry, lr):
            return _log_conv(carry, _geometric_series(lr, m_max)), None
        logZ, _ = jax.lax.scan(fold, logZ, log_rho)
    elif method == "literal":
        # Station-by-station, exactly the ordering of Proposition 15:
        # n single-server computation queues, then n downlink IS, then n uplink IS.
        logZ = jnp.where(jnp.arange(m_max + 1) == 0, 0.0, NEG_INF)  # Z_{.,0}=1 only
        logZ = logZ.at[0].set(0.0)
        for i in range(params.n):
            logZ = _log_conv(logZ, _geometric_series(log_rho[i], m_max))
        for i in range(params.n):
            logZ = _log_conv(
                logZ, _poisson_series(jnp.log(params.p[i] / params.mu_d[i]), m_max)
            )
        for i in range(params.n):
            logZ = _log_conv(
                logZ, _poisson_series(jnp.log(params.p[i] / params.mu_u[i]), m_max)
            )
    else:
        raise ValueError(f"unknown method: {method}")

    if params.mu_cs is not None:
        # Multi-class CS station: the multinomial class structure of Eq. (20)
        # sums out to a geometric factor with load sum_j p_j / mu_cs (= 1/mu_cs
        # on the simplex).  Keeping the explicit sum_j p_j lets raw partials
        # d/dp_j flow through the CS station, matching Theorem 7's CS terms.
        log_load_cs = jnp.log(seqsum(params.p)) - jnp.log(params.mu_cs)
        logZ = _log_conv(logZ, _geometric_series(log_load_cs, m_max))
    return logZ


def class_log_normalizing_constants(
    classes: ClassParams,
    m_max: int,
    *,
    backend: Optional[str] = None,
) -> jax.Array:
    """Class-space Buzen DP: ``log Z_{n,m}`` in O(C m^2) instead of O(n m^2).

    The ``2n`` IS stations enter through the aggregate Poisson factor
    (as in ``method="aggregate"``), and each class's ``count`` computation
    stations fold in as ONE negative-binomial series
    (:func:`_negbinom_series`).  Agrees with
    :func:`log_normalizing_constants` on ``classes.expand()`` to f64
    roundoff (the fold order differs, so not bitwise across the two
    representations) and is **bitwise** invariant to class padding
    (:func:`pad_classes`).  ``backend="pallas"`` routes through the
    class-space TPU kernel (``repro.kernels.buzen``, float32).
    """
    backend = _backend if backend is None else backend
    if backend not in _BACKENDS:
        raise ValueError(f"unknown buzen backend: {backend!r}")
    if backend == "pallas":
        from ..kernels.buzen import buzen_classes_pallas_batched  # no cycle

        log_rho = classes.log_rho
        count = classes.count.astype(classes.p.dtype)
        if classes.mu_cs is not None:
            # the CS station is a count-1 "class" with load sum(mass)/mu_cs
            log_load_cs = jnp.log(seqsum(classes.mass)) - jnp.log(
                classes.mu_cs)
            log_rho = jnp.concatenate([log_rho, log_load_cs[None]])
            count = jnp.concatenate([count, jnp.ones((1,), count.dtype)])
        out = buzen_classes_pallas_batched(
            log_rho[None, :], count[None, :],
            classes.log_gamma_total[None], m_max)[0]
        return out.astype(classes.p.dtype)

    logZ = _poisson_series(classes.log_gamma_total, m_max)

    def fold(carry, xs):
        lr, cnt = xs
        return _log_conv(carry, _negbinom_series(lr, cnt, m_max)), None

    logZ, _ = jax.lax.scan(fold, logZ, (classes.log_rho, classes.count))
    if classes.mu_cs is not None:
        # same geometric CS factor as the per-client DP, with the class-mass
        # sequential sum standing in for sum_j p_j
        log_load_cs = jnp.log(seqsum(classes.mass)) - jnp.log(classes.mu_cs)
        logZ = _log_conv(logZ, _geometric_series(log_load_cs, m_max))
    return logZ


def log_Z_ratio(logZ: jax.Array, num: int, den: int) -> jax.Array:
    """``Z[num] / Z[den]`` in linear space, with ``Z[k<0] = 0``."""
    if num < 0:
        return jnp.zeros(())
    return jnp.exp(logZ[num] - logZ[den])


def brute_force_log_Z(params: NetworkParams, m: int) -> float:
    """Exact Z_{n,m} by state enumeration — test oracle, tiny systems only."""
    import itertools
    import numpy as np

    n = params.n
    p = np.asarray(params.p)
    mu_c = np.asarray(params.mu_c)
    mu_d = np.asarray(params.mu_d)
    mu_u = np.asarray(params.mu_u)
    stations = []  # (load, is_infinite_server)
    for i in range(n):
        stations.append((p[i] / mu_c[i], False))
    for i in range(n):
        stations.append((p[i] / mu_d[i], True))
    for i in range(n):
        stations.append((p[i] / mu_u[i], True))
    if params.mu_cs is not None:
        # contract: allow(raw-reduction): host-side numpy in the O(C(m+S-1,S-1)) literal oracle — never traced, never padded
        stations.append((float(p.sum()) / float(params.mu_cs), False))

    S = len(stations)
    total = 0.0
    # enumerate compositions of m into S parts
    for comp in itertools.combinations(range(m + S - 1), S - 1):
        prev = -1
        xs = []
        for c in comp:
            xs.append(c - prev - 1)
            prev = c
        xs.append(m + S - 2 - prev)
        term = 1.0
        for (load, is_is), x in zip(stations, xs):
            term *= load**x
            if is_is:
                import math

                term /= math.factorial(x)
        total += term
    import math

    return math.log(total)
