"""The declarative Scenario spec — ONE pytree drives the whole pipeline.

The paper's object of study is a single thing: a closed queueing network
with timing laws, a routing/concurrency strategy, and an objective.
:class:`Scenario` says exactly that, declaratively::

    net = NetworkSpec.from_clusters(PAPER_CLUSTERS_TABLE1, scale=10)
    scn = Scenario(network=net, learning=LearningSpec(grad_clip=5.0),
                   strategy=StrategySpec("time_opt"))

and every execution mode consumes the same spec (see
``repro.scenario.suite``): ``analyze`` evaluates the closed forms,
``simulate`` runs the device event engine, ``train`` runs the fused
AsyncSGD trainer.

Static/traced field split: each sub-spec is a frozen dataclass registered
as a JAX pytree whose *data* fields are the numeric arrays (rates, routing,
power coefficients, learning constants) and whose *meta* fields are the
structure (timing-law / strategy / objective names, population counts,
optimizer settings).  Two scenarios with equal meta flatten to identical
treedefs, so a batch of them stacks leaf-wise and rides the padded-lane
conventions of ``repro.core.batched`` and ``repro.fl.engine`` under one
compile — batching over *scenarios*, not just seeds.

Serialization: ``to_dict`` / ``from_dict`` round-trip through plain JSON
types **bitwise** (Python's ``json`` emits ``repr``-exact floats), so an
experiment file pins its scenario exactly; :meth:`Scenario.hash` is the
canonical-JSON digest used to key benchmark trajectories
(``BENCH_smoke.json``) across API churn.

Validation is *eager*: unknown timing laws, strategies, objectives or
malformed shapes raise at construction — with the registered options in the
message — not deep inside a jit trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.complexity import LearningConstants
from ..core.buzen import ClassParams, NetworkParams
from ..core.energy import PowerProfile
from .registry import OBJECTIVES, PARTITIONS, STRATEGIES, TIMING_LAWS

# The paper's step sizes for the Table-3 comparison: max-throughput needs a
# 20x-reduced learning rate to stay stable (Section 5.3).  Single source of
# truth; ``repro.fl.strategies`` re-exports for seed call sites.
DEFAULT_ETA = 0.05
MAX_THROUGHPUT_ETA = 0.01

EXPLICIT = "explicit"  # StrategySpec.name for a hand-given (p, m)


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# Validation nesting guard: pytree unflattening re-runs ``__post_init__``;
# under jit/vmap the leaves are tracers (validation skips itself), but the
# eager :func:`stack` rebuilds specs with *batched* concrete leaves, where
# the 1-D shape checks must be suspended.
_SKIP_VALIDATION = 0


@contextlib.contextmanager
def _no_validation():
    global _SKIP_VALIDATION
    _SKIP_VALIDATION += 1
    try:
        yield
    finally:
        _SKIP_VALIDATION -= 1


def _coerce_vec(obj, field: str, n: Optional[int] = None,
                positive: bool = False) -> Optional[int]:
    """Coerce a 1-D float64 vector field in place (tracer-transparent);
    returns its length (or ``n`` unchanged for an absent optional field)."""
    v = getattr(obj, field)
    if v is None or _is_tracer(v):
        return n
    arr = np.asarray(v, dtype=np.float64)
    if arr.ndim != 1:
        raise ValueError(f"{type(obj).__name__}.{field} must be 1-D, "
                         f"got shape {arr.shape}")
    if n is not None and arr.shape[0] != n:
        raise ValueError(f"{type(obj).__name__}.{field} has length "
                         f"{arr.shape[0]}, expected {n}")
    if positive and not (arr > 0).all():
        raise ValueError(f"{type(obj).__name__}.{field} must be positive")
    object.__setattr__(obj, field, arr)
    return arr.shape[0]


def _pytree_dataclass(data_fields):
    """Register a frozen dataclass as a pytree with the given data fields
    (everything else is meta/static).  Equality must be array-aware, so the
    classes set ``eq=False`` and get a structural ``__eq__`` here."""
    data_fields = tuple(data_fields)

    def deco(cls):
        meta = tuple(f.name for f in dataclasses.fields(cls)
                     if f.name not in data_fields)
        jax.tree_util.register_dataclass(cls, data_fields=list(data_fields),
                                         meta_fields=list(meta))

        def __eq__(self, other):
            if type(other) is not type(self):
                return NotImplemented
            for f in dataclasses.fields(self):
                a, b = getattr(self, f.name), getattr(other, f.name)
                if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
                    if not (isinstance(a, np.ndarray)
                            and isinstance(b, np.ndarray)
                            and a.shape == b.shape and (a == b).all()):
                        return False
                elif a != b:
                    return False
            return True

        cls.__eq__ = __eq__
        cls.__hash__ = object.__hash__
        return cls

    return deco


def _dict_vec(v):
    return None if v is None else [float(x) for x in np.asarray(v)]


def _opt_float(v):
    return None if v is None else float(v)


# ---------------------------------------------------------------------------
# cluster rows (Table 1 / Table 4 / Table 6)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ClusterSpec:
    """One client cluster row of Table 1 / Table 4."""

    name: str
    mu_c: float
    mu_u: float
    mu_d: float
    count: int
    kappa: float = 0.0   # DVFS energy coefficient (Table 4)
    P_u: float = 0.0
    P_d: float = 0.0


# Table 1 — the paper's main experimental population (n = 100).
PAPER_CLUSTERS_TABLE1 = [
    ClusterSpec("A", 10.0, 2.0, 2.5, 15, kappa=0.08, P_u=5.0, P_d=3.0),
    ClusterSpec("B", 0.3, 9.0, 10.0, 15, kappa=200.0, P_u=15.0, P_d=10.0),
    ClusterSpec("C", 5.0, 6.0, 7.0, 20, kappa=0.25, P_u=4.0, P_d=3.0),
    ClusterSpec("D", 0.15, 0.1, 0.12, 40, kappa=14400.0, P_u=0.5, P_d=0.2),
    ClusterSpec("E", 12.0, 10.0, 11.0, 10, kappa=1.50, P_u=50.0, P_d=40.0),
]

# Table 6 — the round-complexity experiment population (Appendix H).
PAPER_CLUSTERS_TABLE6 = [
    ClusterSpec("A", 10.0, 2.0, 2.5, 15),
    ClusterSpec("B", 2.5, 8.0, 9.0, 35),
    ClusterSpec("C", 5.0, 5.0, 6.0, 30),
    ClusterSpec("D", 0.5, 0.8, 1.1, 15),
    ClusterSpec("E", 15.0, 10.0, 11.0, 5),
]


def expand_clusters(clusters, scale: int = 1):
    """Cluster rows -> per-client columns ``(labels, mu_c, mu_d, mu_u,
    kappa, P_u, P_d)`` with the population scaled down by ``scale``."""
    cols = {k: [] for k in ("label", "mu_c", "mu_d", "mu_u",
                            "kappa", "P_u", "P_d")}
    for c in clusters:
        cnt = max(1, c.count // scale)
        cols["label"] += [c.name] * cnt
        for k in ("mu_c", "mu_d", "mu_u", "kappa", "P_u", "P_d"):
            cols[k] += [getattr(c, k)] * cnt
    return (tuple(cols["label"]),) + tuple(
        np.asarray(cols[k], dtype=np.float64)
        for k in ("mu_c", "mu_d", "mu_u", "kappa", "P_u", "P_d"))


# ---------------------------------------------------------------------------
# sub-specs
# ---------------------------------------------------------------------------

@_pytree_dataclass(data_fields=("mu_c", "mu_d", "mu_u", "p", "count"))
@dataclasses.dataclass(frozen=True, eq=False)
class ClassSpec:
    """Client classes with integer multiplicities — the O(C) population axis.

    The product-form network depends on a client only through its
    ``(p, mu_c, mu_d, mu_u)`` profile, so ``count[c]`` identical clients
    collapse into one class (``repro.core.buzen.ClassParams``): closed
    forms run the O(C) negative-binomial Buzen DP, the event engine carries
    O(C) statistics, and the population size ``n_total = sum(count)``
    becomes a free variable — ``n = 10^5..10^6`` scenarios cost the same
    as ``n = 10^2`` ones.  ``p`` is the *per-member* routing mass (class
    ``c`` as a whole carries ``count[c] * p[c]``); ``None`` means uniform
    ``1 / n_total``.  :meth:`NetworkSpec.params` expands back to the
    per-client oracle (O(n), for validation and small-``n`` interop).
    """

    mu_c: np.ndarray                  # [C] computation rates
    mu_d: np.ndarray                  # [C] downlink rates
    mu_u: np.ndarray                  # [C] uplink rates
    count: np.ndarray                 # [C] integer multiplicities (>= 1)
    p: Optional[np.ndarray] = None    # [C] per-member routing (None = uniform)
    labels: Optional[tuple] = None    # per-class cluster labels (meta)

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        C = _coerce_vec(self, "mu_c", positive=True)
        C = _coerce_vec(self, "mu_d", C, positive=True)
        C = _coerce_vec(self, "mu_u", C, positive=True)
        _coerce_vec(self, "p", C, positive=True)
        if self.count is not None and not _is_tracer(self.count):
            arr = np.asarray(self.count)
            if arr.ndim != 1:
                raise ValueError(f"ClassSpec.count must be 1-D, got shape "
                                 f"{arr.shape}")
            if C is not None and arr.shape[0] != C:
                raise ValueError(f"ClassSpec.count has length "
                                 f"{arr.shape[0]}, expected {C}")
            if (not np.issubdtype(arr.dtype, np.integer)
                    and not np.all(arr == np.round(arr))):
                raise ValueError("ClassSpec.count must be integers")
            arr = arr.astype(np.int64)
            if not (arr >= 1).all():
                raise ValueError("ClassSpec.count must be >= 1 (padding "
                                 "with count-0 classes happens at the "
                                 "ClassParams level, not in the spec)")
            object.__setattr__(self, "count", arr)
        if self.labels is not None:
            object.__setattr__(self, "labels", tuple(self.labels))
            if C is not None and len(self.labels) != C:
                raise ValueError("labels/rates length mismatch")

    @classmethod
    def from_clusters(cls, clusters, scale: int = 1) -> "ClassSpec":
        """One class per cluster row — the aggregated form of
        :meth:`NetworkSpec.from_clusters` (same ``scale`` semantics)."""
        return cls(
            mu_c=np.asarray([c.mu_c for c in clusters], np.float64),
            mu_d=np.asarray([c.mu_d for c in clusters], np.float64),
            mu_u=np.asarray([c.mu_u for c in clusters], np.float64),
            count=np.asarray([max(1, c.count // scale) for c in clusters],
                             np.int64),
            labels=tuple(c.name for c in clusters))

    @property
    def C(self) -> int:
        return len(self.count)

    @property
    def n_total(self) -> int:
        return int(np.asarray(self.count).sum())

    def class_params(self, p=None, mu_cs=None) -> ClassParams:
        """Materialize :class:`repro.core.buzen.ClassParams` (routing
        override ``p`` > spec base ``p`` > uniform ``1/n_total``)."""
        if p is None:
            p = (self.p if self.p is not None
                 else np.full(self.C, 1.0 / self.n_total))
        cp = ClassParams(
            p=jnp.asarray(p, jnp.float64),
            mu_c=jnp.asarray(self.mu_c), mu_d=jnp.asarray(self.mu_d),
            mu_u=jnp.asarray(self.mu_u),
            count=jnp.asarray(self.count, jnp.int64))
        if mu_cs is not None:
            cp = cp.with_cs(mu_cs)
        return cp

    def to_dict(self) -> dict:
        return {"mu_c": _dict_vec(self.mu_c), "mu_d": _dict_vec(self.mu_d),
                "mu_u": _dict_vec(self.mu_u),
                "count": [int(x) for x in np.asarray(self.count)],
                "p": _dict_vec(self.p),
                "labels": None if self.labels is None else list(self.labels)}

    @classmethod
    def from_dict(cls, d: dict) -> "ClassSpec":
        return cls(**{**d, "labels": None if d.get("labels") is None
                      else tuple(d["labels"])})


@_pytree_dataclass(data_fields=("mu_c", "mu_d", "mu_u", "p", "mu_cs",
                                "classes"))
@dataclasses.dataclass(frozen=True, eq=False)
class NetworkSpec:
    """The closed queueing network: per-client rates, base routing, the
    service-time law, and the optional CS-side buffer (Section 7).

    Two population representations, mutually exclusive:

      * per-client arrays ``mu_c``/``mu_d``/``mu_u``/``p`` (the original
        O(n) form), or
      * ``classes=``, a :class:`ClassSpec` of class profiles with integer
        multiplicities — all closed forms and the event engine then run
        O(#classes), making ``n`` a free variable.
    """

    mu_c: Optional[np.ndarray] = None  # [n] computation rates
    mu_d: Optional[np.ndarray] = None  # [n] downlink rates
    mu_u: Optional[np.ndarray] = None  # [n] uplink rates
    p: Optional[np.ndarray] = None    # [n] base routing (None = uniform)
    mu_cs: Optional[float] = None     # CS buffer rate (None = no CS station)
    law: str = "exponential"          # registered timing law (meta)
    labels: Optional[tuple] = None    # per-client cluster labels (meta)
    classes: Optional[ClassSpec] = None  # class-aggregated population

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        if self.classes is not None:
            if any(getattr(self, f) is not None
                   for f in ("mu_c", "mu_d", "mu_u", "p")):
                raise ValueError(
                    "NetworkSpec with classes= must not also carry "
                    "per-client rate/routing arrays — the ClassSpec is the "
                    "population")
        else:
            if self.mu_c is None:
                raise ValueError("NetworkSpec needs either per-client "
                                 "rates (mu_c/mu_d/mu_u) or classes=")
            n = _coerce_vec(self, "mu_c", positive=True)
            n = _coerce_vec(self, "mu_d", n, positive=True)
            n = _coerce_vec(self, "mu_u", n, positive=True)
            _coerce_vec(self, "p", n, positive=True)
            if self.labels is not None:
                object.__setattr__(self, "labels", tuple(self.labels))
                if n is not None and len(self.labels) != n:
                    raise ValueError("labels/rates length mismatch")
        if self.mu_cs is not None and not _is_tracer(self.mu_cs):
            if not float(self.mu_cs) > 0:
                raise ValueError(f"mu_cs must be positive, got {self.mu_cs}")
            object.__setattr__(self, "mu_cs", float(self.mu_cs))
        TIMING_LAWS.get(self.law)  # eager: unknown laws fail here, not in jit

    @classmethod
    def from_clusters(cls, clusters, scale: int = 1, *,
                      mu_cs: Optional[float] = None,
                      law: str = "exponential",
                      aggregate: bool = False) -> "NetworkSpec":
        """Per-client network from cluster rows; ``aggregate=True`` builds
        the class-aggregated form (one :class:`ClassSpec` class per
        cluster) instead of expanding to per-client arrays."""
        if aggregate:
            return cls(classes=ClassSpec.from_clusters(clusters, scale),
                       mu_cs=mu_cs, law=law)
        labels, mu_c, mu_d, mu_u, _, _, _ = expand_clusters(clusters, scale)
        return cls(mu_c=mu_c, mu_d=mu_d, mu_u=mu_u, mu_cs=mu_cs, law=law,
                   labels=labels)

    @property
    def n(self) -> int:
        return (self.classes.n_total if self.classes is not None
                else len(self.mu_c))

    def params(self, p=None) -> NetworkParams:
        """Materialize :class:`repro.core.NetworkParams` (routing override
        ``p`` > spec base ``p`` > uniform).

        For a class network this *expands* the population (O(n) — the
        oracle path; the O(C) planner paths call :meth:`class_params`
        instead), with ``p`` interpreted per-member over classes.
        """
        if self.classes is not None:
            return self.class_params(p).expand()
        if p is None:
            p = self.p if self.p is not None else np.full(self.n, 1.0 / self.n)
        params = NetworkParams(
            p=jnp.asarray(p, jnp.float64),
            mu_c=jnp.asarray(self.mu_c), mu_d=jnp.asarray(self.mu_d),
            mu_u=jnp.asarray(self.mu_u))
        if self.mu_cs is not None:
            params = params.with_cs(self.mu_cs)
        return params

    def class_params(self, p=None) -> ClassParams:
        """Materialize :class:`repro.core.buzen.ClassParams` (class
        networks only; ``p`` is per-member routing over classes)."""
        if self.classes is None:
            raise ValueError("not a class network: construct NetworkSpec "
                             "with classes= for the O(C) forms")
        return self.classes.class_params(p, mu_cs=self.mu_cs)

    def to_dict(self) -> dict:
        d = {"mu_c": _dict_vec(self.mu_c), "mu_d": _dict_vec(self.mu_d),
             "mu_u": _dict_vec(self.mu_u), "p": _dict_vec(self.p),
             "mu_cs": _opt_float(self.mu_cs), "law": self.law,
             "labels": None if self.labels is None else list(self.labels)}
        # absent (not null) when unset — the SimSpec/DataSpec precedent:
        # pre-existing per-client scenarios keep their canonical JSON, and
        # hence their Scenario.hash(), unchanged
        if self.classes is not None:
            d["classes"] = self.classes.to_dict()
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "NetworkSpec":
        return cls(**{**d, "labels": None if d.get("labels") is None
                      else tuple(d["labels"]),
                      "classes": None if d.get("classes") is None
                      else ClassSpec.from_dict(d["classes"])})


@_pytree_dataclass(data_fields=("consts",))
@dataclasses.dataclass(frozen=True, eq=False)
class LearningSpec:
    """Learning-side spec: Assumption A1-A5 constants, the step-size rule
    (``None`` = the per-strategy Table-3 defaults), gradient clipping."""

    consts: LearningConstants = LearningConstants(
        L=1.0, delta=1.0, sigma=1.0, M=2.0, G=5.0, eps=1.0)
    eta: Optional[float] = None       # None -> per-strategy default table
    grad_clip: Optional[float] = None

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        if not isinstance(self.consts, LearningConstants):
            object.__setattr__(self, "consts",
                               LearningConstants(*self.consts))

    def eta_for(self, strategy_name: str) -> float:
        """Resolved step size: explicit ``eta`` wins, else the paper's
        per-strategy defaults (Section 5.3)."""
        if self.eta is not None:
            return float(self.eta)
        return (MAX_THROUGHPUT_ETA if strategy_name == "max_throughput"
                else DEFAULT_ETA)

    def to_dict(self) -> dict:
        c = self.consts
        return {"consts": {"L": float(c.L), "delta": float(c.delta),
                           "sigma": float(c.sigma), "M": float(c.M),
                           "G": float(c.G), "eps": float(c.eps)},
                "eta": _opt_float(self.eta),
                "grad_clip": _opt_float(self.grad_clip)}

    @classmethod
    def from_dict(cls, d: dict) -> "LearningSpec":
        return cls(consts=LearningConstants(**d["consts"]), eta=d.get("eta"),
                   grad_clip=d.get("grad_clip"))


@_pytree_dataclass(data_fields=("kappa", "P_u", "P_d", "P_cs"))
@dataclasses.dataclass(frozen=True, eq=False)
class EnergySpec:
    """Phase-dependent power profile (Table 4): cubic-DVFS computation
    power ``kappa * mu_c**3`` plus radio powers (Section 6.5.1)."""

    kappa: np.ndarray                # [n] DVFS coefficients
    P_u: np.ndarray                  # [n] uplink powers
    P_d: np.ndarray                  # [n] downlink powers
    P_cs: Optional[float] = None     # CS processing power (Section 7.5)

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        n = _coerce_vec(self, "kappa")
        n = _coerce_vec(self, "P_u", n)
        _coerce_vec(self, "P_d", n)
        if self.P_cs is not None and not _is_tracer(self.P_cs):
            object.__setattr__(self, "P_cs", float(self.P_cs))

    @classmethod
    def from_clusters(cls, clusters, scale: int = 1, *,
                      P_cs: Optional[float] = None) -> "EnergySpec":
        _, _, _, _, kappa, P_u, P_d = expand_clusters(clusters, scale)
        return cls(kappa=kappa, P_u=P_u, P_d=P_d, P_cs=P_cs)

    def profile(self, network: NetworkSpec) -> PowerProfile:
        """For class networks the arrays are per-CLASS (``[C]``, one power
        rating shared by the members of a class)."""
        mu_c = (network.classes.mu_c if network.classes is not None
                else network.mu_c)
        return PowerProfile.from_dvfs(
            jnp.asarray(self.kappa), jnp.asarray(mu_c),
            jnp.asarray(self.P_u), jnp.asarray(self.P_d),
            P_cs=None if self.P_cs is None else jnp.asarray(self.P_cs))

    def to_dict(self) -> dict:
        return {"kappa": _dict_vec(self.kappa), "P_u": _dict_vec(self.P_u),
                "P_d": _dict_vec(self.P_d), "P_cs": _opt_float(self.P_cs)}

    @classmethod
    def from_dict(cls, d: dict) -> "EnergySpec":
        return cls(**d)


@_pytree_dataclass(data_fields=("p",))
@dataclasses.dataclass(frozen=True, eq=False)
class StrategySpec:
    """Routing/concurrency strategy: a registered name (resolved by the
    strategy registry at suite time) or ``"explicit"`` with ``(p, m)``."""

    name: str = "asyncsgd"
    p: Optional[np.ndarray] = None    # explicit routing (name="explicit")
    m: Optional[int] = None           # explicit / forced concurrency
    m_max: Optional[int] = None       # concurrency search bound (default n+8)
    steps: int = 300                  # Adam steps of the routing optimizer
    search: str = "batched"           # "batched" | "pruned" | "sequential"

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        _coerce_vec(self, "p", positive=True)
        if self.m is not None:
            object.__setattr__(self, "m", int(self.m))
        if self.m_max is not None:
            object.__setattr__(self, "m_max", int(self.m_max))
        if self.search not in ("batched", "pruned", "sequential"):
            raise ValueError(f"unknown search mode: {self.search!r}; "
                             "expected 'batched', 'pruned' or 'sequential'")
        if self.name == EXPLICIT:
            if self.p is None or self.m is None:
                raise ValueError(
                    "explicit strategy needs both p and m")
        else:
            # registrations live in repro.scenario.suite — make sure they
            # are loaded, then fail eagerly on unknown names
            from . import suite  # noqa: F401
            STRATEGIES.get(self.name)

    def to_dict(self) -> dict:
        return {"name": self.name, "p": _dict_vec(self.p), "m": self.m,
                "m_max": self.m_max, "steps": int(self.steps),
                "search": self.search}

    @classmethod
    def from_dict(cls, d: dict) -> "StrategySpec":
        return cls(**d)


@_pytree_dataclass(data_fields=())
@dataclasses.dataclass(frozen=True, eq=False)
class ObjectiveSpec:
    """What to optimize / report: a registered objective plus its Pareto
    weight ``rho`` (used by the ``"joint"`` objective/strategy, Eq. 18)."""

    name: str = "time"
    rho: float = 0.1

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        object.__setattr__(self, "rho", float(self.rho))
        from . import suite  # noqa: F401  (loads objective registrations)
        OBJECTIVES.get(self.name)

    def to_dict(self) -> dict:
        return {"name": self.name, "rho": float(self.rho)}

    @classmethod
    def from_dict(cls, d: dict) -> "ObjectiveSpec":
        return cls(**d)


@_pytree_dataclass(data_fields=())
@dataclasses.dataclass(frozen=True, eq=False)
class TraceSpec:
    """Telemetry-channel selection for ``repro.obs`` (see its docs).

    ``events``/``updates`` are ring capacities (records kept; 0 disables
    the channel — the rings are zero-length and XLA dead-code-eliminates
    them, so an untraced scenario compiles the exact pre-existing
    program).  Tracing is **bitwise non-invasive**: results are identical
    with any capacities.  ``tolerance`` is the relative drift band the
    monitors (``repro.obs.drift``) allow between ring empirics and the
    closed-form predictions.
    """

    events: int = 0        # event-ring capacity (engine channel)
    updates: int = 0       # update-ring capacity (fused-trainer channel)
    tolerance: float = 0.25

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        for f in ("events", "updates"):
            v = int(getattr(self, f))
            if v < 0:
                raise ValueError(f"TraceSpec.{f} must be >= 0, got {v}")
            object.__setattr__(self, f, v)
        tol = float(self.tolerance)
        if not tol > 0:
            raise ValueError(f"TraceSpec.tolerance must be > 0, got {tol}")
        object.__setattr__(self, "tolerance", tol)

    def to_dict(self) -> dict:
        return {"events": int(self.events), "updates": int(self.updates),
                "tolerance": float(self.tolerance)}

    @classmethod
    def from_dict(cls, d: dict) -> "TraceSpec":
        return cls(**d)


@_pytree_dataclass(data_fields=())
@dataclasses.dataclass(frozen=True, eq=False)
class SimSpec:
    """Event-engine execution knobs: which ``repro.sim`` backend runs this
    scenario's trajectories (``None`` = the process-wide
    ``REPRO_SIM_BACKEND`` default), for the Pallas backend an
    ``interpret``-mode override (``None`` = auto: compiled on TPU,
    interpreted elsewhere), the megastep chunk size (``chunk``: events
    retired per scan iteration / kernel launch — trajectories are bitwise
    invariant to it, default 1), and the optional ``repro.obs`` telemetry
    channels (``trace``; ``None`` = tracing off)."""

    backend: Optional[str] = None     # "reference" | "batched" | "pallas"
    interpret: Optional[bool] = None
    chunk: int = 1                    # megastep events per scan iteration
    trace: Optional[TraceSpec] = None

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        if self.backend is not None:
            from ..sim.backend import _check  # dependency-free

            object.__setattr__(self, "backend", _check(str(self.backend)))
        if self.interpret is not None:
            object.__setattr__(self, "interpret", bool(self.interpret))
        object.__setattr__(self, "chunk", int(self.chunk))
        if self.chunk < 1:
            raise ValueError(f"chunk must be a positive integer, got "
                             f"{self.chunk}")
        if self.trace is not None and not isinstance(self.trace, TraceSpec):
            object.__setattr__(self, "trace", TraceSpec(**dict(self.trace)))

    def to_dict(self) -> dict:
        d = {"backend": self.backend, "interpret": self.interpret}
        # absent (not null) when unset: pre-obs SimSpec JSON — and every
        # Scenario.hash() over it — is unchanged by the trace field
        if self.trace is not None:
            d["trace"] = self.trace.to_dict()
        # same convention for the megastep knob: absent at the default, so
        # pre-megastep hashes are stable and chunk=1 stays byte-identical
        if self.chunk != 1:
            d["chunk"] = self.chunk
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "SimSpec":
        d = dict(d)
        trace = d.pop("trace", None)
        return cls(trace=None if trace is None
                   else TraceSpec.from_dict(trace), **d)


@_pytree_dataclass(data_fields=())
@dataclasses.dataclass(frozen=True, eq=False)
class DataSpec:
    """Declarative training data: a dataset builder plus an ``@partition``
    registry key (and its dirichlet ``alpha``), so
    ``ScenarioSuite.run(mode="train")`` can build the per-client datasets
    from the spec instead of requiring an explicit ``clients=``.

    Registered datasets (``repro.data.DATASETS``): ``"synthetic"`` (the
    procedural class-glyph images) and ``"emnist"`` — a download-free
    EMNIST-style loader that reads a local ``.npz`` cache
    (``$REPRO_EMNIST_PATH`` / ``<checkout>/data/emnist.npz``) when present
    and otherwise falls back to a deterministic synthetic stand-in with
    the same 28x28 tensor format (``repro.data.emnist``)."""

    dataset: str = "synthetic"        # dataset builder name
    partition: str = "iid"            # @partition registry key
    alpha: float = 0.2                # dirichlet concentration (if used)
    num_classes: int = 4
    samples_per_class: int = 40
    test_fraction: float = 0.25
    seed: int = 0

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        from .. import data  # registers the partitioners + dataset builders

        if self.dataset not in data.DATASETS:
            raise ValueError(f"unknown dataset: {self.dataset!r}; "
                             f"registered datasets: "
                             f"{sorted(data.DATASETS)}")
        PARTITIONS.get(self.partition)
        object.__setattr__(self, "alpha", float(self.alpha))
        for f in ("num_classes", "samples_per_class", "seed"):
            object.__setattr__(self, f, int(getattr(self, f)))
        object.__setattr__(self, "test_fraction", float(self.test_fraction))

    def build(self, n: int):
        """Materialize ``(clients, test_data)`` for an ``n``-client network:
        ``clients[i] = (x_i, y_i)`` per the registered partitioner."""
        import inspect

        from ..data import get_dataset, train_test_split

        full = get_dataset(
            self.dataset, num_classes=self.num_classes,
            samples_per_class=self.samples_per_class, seed=self.seed)
        ds, test = train_test_split(full, self.test_fraction,
                                    seed=self.seed + 1)
        part = PARTITIONS.get(self.partition)
        kw = {"seed": self.seed}
        if "alpha" in inspect.signature(part).parameters:
            kw["alpha"] = self.alpha
        parts = part(ds.y, n, **kw)
        clients = [(ds.x[i], ds.y[i]) for i in parts]
        return clients, (test.x, test.y)

    def to_dict(self) -> dict:
        return {"dataset": self.dataset, "partition": self.partition,
                "alpha": float(self.alpha),
                "num_classes": int(self.num_classes),
                "samples_per_class": int(self.samples_per_class),
                "test_fraction": float(self.test_fraction),
                "seed": int(self.seed)}

    @classmethod
    def from_dict(cls, d: dict) -> "DataSpec":
        return cls(**d)


# ---------------------------------------------------------------------------
# the Scenario
# ---------------------------------------------------------------------------

@_pytree_dataclass(data_fields=("network", "learning", "energy", "strategy",
                                "objective", "sim", "data"))
@dataclasses.dataclass(frozen=True, eq=False)
class Scenario:
    """One complete experiment: network x learning x energy x strategy x
    objective (x optional sim backend and data layout).  See the module
    docstring for the 5-line EMNIST example."""

    network: NetworkSpec
    learning: LearningSpec = dataclasses.field(default_factory=LearningSpec)
    energy: Optional[EnergySpec] = None
    strategy: StrategySpec = dataclasses.field(default_factory=StrategySpec)
    objective: ObjectiveSpec = dataclasses.field(
        default_factory=ObjectiveSpec)
    sim: Optional[SimSpec] = None     # None = process-default backend
    data: Optional[DataSpec] = None   # None = explicit clients= required
    name: str = ""

    def __post_init__(self):
        if _SKIP_VALIDATION:
            return
        if self.energy is not None and not _is_tracer(self.energy.kappa):
            # class networks carry per-CLASS power arrays
            expected = (self.network.classes.C
                        if self.network.classes is not None
                        else self.network.n)
            if len(self.energy.kappa) != expected:
                raise ValueError("energy/network population mismatch")
        # contract: allow(stringly-dispatch): eager construction-time check that these two strategies need an EnergySpec — resolution itself routes through STRATEGIES
        if (self.strategy.name in ("energy_opt", "joint")
                and self.energy is None):
            raise ValueError(
                f"strategy {self.strategy.name!r} needs an EnergySpec")

    # -- convenience ---------------------------------------------------------

    @property
    def n(self) -> int:
        return self.network.n

    @property
    def consts(self) -> LearningConstants:
        return self.learning.consts

    def params(self, p=None) -> NetworkParams:
        return self.network.params(p)

    def class_params(self, p=None) -> ClassParams:
        return self.network.class_params(p)

    @property
    def is_class_network(self) -> bool:
        return self.network.classes is not None

    def power(self) -> Optional[PowerProfile]:
        return None if self.energy is None else self.energy.profile(
            self.network)

    def eta(self) -> float:
        return self.learning.eta_for(self.strategy.name)

    @property
    def sim_backend(self) -> Optional[str]:
        """The pinned ``repro.sim`` backend (None = process default)."""
        return None if self.sim is None else self.sim.backend

    @property
    def trace(self) -> Optional[TraceSpec]:
        """The ``repro.obs`` telemetry channels (None = tracing off)."""
        return None if self.sim is None else self.sim.trace

    def replace(self, **kw) -> "Scenario":
        return dataclasses.replace(self, **kw)

    def with_strategy(self, strategy, **kw) -> "Scenario":
        """New scenario with a different strategy: pass a name (plus
        StrategySpec field overrides) or a full :class:`StrategySpec`.

        Rewriting a named strategy as ``"explicit"`` (e.g. pinning its
        resolved ``(p, m)``) freezes the *current* resolved step size into
        the learning spec — otherwise ``eta_for("explicit")`` would
        silently revert e.g. max-throughput's 20x-reduced eta to the
        default.
        """
        if isinstance(strategy, StrategySpec):
            spec = dataclasses.replace(strategy, **kw) if kw else strategy
        else:
            spec = dataclasses.replace(self.strategy, name=str(strategy),
                                       **kw)
        learning = self.learning
        if (spec.name == EXPLICIT and self.strategy.name != EXPLICIT
                and learning.eta is None):
            learning = dataclasses.replace(learning, eta=self.eta())
        name = self.name or None
        return dataclasses.replace(
            self, strategy=spec, learning=learning,
            name=f"{name}:{spec.name}" if name else spec.name)

    def fl_config(self, **overrides):
        """Materialize an :class:`repro.fl.AsyncFLConfig` for this scenario
        (law, grad clip and resolved eta pre-filled; kwargs override)."""
        from ..fl.trainer import AsyncFLConfig  # local: fl imports scenario

        kw = dict(eta=self.eta(), distribution=self.network.law,
                  grad_clip=self.learning.grad_clip)
        kw.update(overrides)
        return AsyncFLConfig(**kw)

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        d = {
            "version": 1,
            "kind": "Scenario",
            "name": self.name,
            "network": self.network.to_dict(),
            "learning": self.learning.to_dict(),
            "energy": None if self.energy is None else self.energy.to_dict(),
            "strategy": self.strategy.to_dict(),
            "objective": self.objective.to_dict(),
        }
        # absent (not null) when unset: scenarios predating SimSpec/DataSpec
        # keep their canonical JSON — and hence their hash() — unchanged,
        # so the BENCH_smoke.json perf trajectory stays joinable
        if self.sim is not None:
            d["sim"] = self.sim.to_dict()
        if self.data is not None:
            d["data"] = self.data.to_dict()
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Scenario":
        if d.get("kind", "Scenario") != "Scenario":
            raise ValueError(f"not a Scenario dict: kind={d.get('kind')!r}")
        return cls(
            network=NetworkSpec.from_dict(d["network"]),
            learning=LearningSpec.from_dict(d["learning"]),
            energy=None if d.get("energy") is None
            else EnergySpec.from_dict(d["energy"]),
            strategy=StrategySpec.from_dict(d["strategy"]),
            objective=ObjectiveSpec.from_dict(d["objective"]),
            sim=None if d.get("sim") is None
            else SimSpec.from_dict(d["sim"]),
            data=None if d.get("data") is None
            else DataSpec.from_dict(d["data"]),
            name=d.get("name", ""),
        )

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":"))

    @classmethod
    def from_json(cls, s: str) -> "Scenario":
        return cls.from_dict(json.loads(s))

    def hash(self) -> str:
        """Short digest of the canonical JSON — the churn-stable key for
        benchmark trajectories.

        The cosmetic ``name`` is excluded: two physically identical
        scenarios must hash equal, or a mere rename would sever the
        ``BENCH_smoke.json`` perf trajectory the hash exists to protect.
        """
        d = self.to_dict()
        d.pop("name", None)
        return hashlib.sha256(json.dumps(
            d, sort_keys=True, separators=(",", ":")).encode()
        ).hexdigest()[:12]


def stack(scenarios) -> Scenario:
    """Stack structurally-identical scenarios leaf-wise into one batched
    Scenario pytree (leading axis = scenario lane) — the vmap-ready form.

    All scenarios must share their meta fields (same treedef: same law,
    strategy/objective names, population size, ...); mixed batches belong
    in a :class:`repro.scenario.suite.ScenarioSuite`, which buckets by
    structure first.
    """
    scenarios = list(scenarios)
    if not scenarios:
        raise ValueError("need at least one scenario")
    treedefs = {jax.tree_util.tree_structure(s) for s in scenarios}
    if len(treedefs) != 1:
        raise ValueError(
            "scenarios have mixed static structure and cannot be stacked "
            "directly; run them through ScenarioSuite (which buckets by "
            f"structure): {sorted(map(str, treedefs))}")
    with _no_validation():  # leaves gain a lane axis: skip the 1-D checks
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *scenarios)
