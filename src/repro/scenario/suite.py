"""ScenarioSuite — plan and dispatch batches of Scenarios in few compiles.

One entry point, three execution modes, all driven by the same spec::

    suite = ScenarioSuite.strategy_grid(base, ("asyncsgd", "time_opt"),
                                        seeds=range(4))
    closed  = suite.run(mode="analyze")                    # closed forms
    stats   = suite.run(mode="simulate", num_updates=2000) # event engine
    logs    = suite.run(mode="train", model=m, clients=c,
                        horizon_time=240.0)                # fused trainer

Planning: scenarios x seeds flatten into *lanes*; lanes are bucketed by
static structure (population size, timing law, CS buffer, energy
accounting, padded ``m_max``) and each bucket executes as ONE jitted,
vmapped program — a suite of S structurally-alike scenarios costs one
compile, not S (``SuiteResult.programs`` records the count; the
``scenario_suite`` smoke benchmark tracks it).  ``train`` mode delegates
lane bucketing to the PR-2 planner of ``repro.fl.engine`` (scan lengths
from an exact queueing-only pre-simulation).

This module also hosts the **strategy** and **objective** registrations
(the implementations live in ``repro.core``): the five paper strategies
resolve through ``STRATEGIES``, the closed-form objectives through
``OBJECTIVES`` — the registries that replaced the stringly-typed dispatch
previously scattered across ``make_strategies`` and the ``make_*_objective``
factories.
"""
from __future__ import annotations
# contract: padded-n — reductions here are on the bitwise padding contract

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.buzen import (NetworkParams, class_log_normalizing_constants,
                          log_normalizing_constants, pad_classes,
                          pad_network)
from ..core.events import unpad_stats
from ..core.numerics import array_module
from ..core.complexity import LearningConstants, wallclock_time
from ..core.energy import (PowerProfile, energy_optimal_routing,
                           minimal_energy)
from ..core.batched import (energy_complexity_classes,
                            energy_complexity_padded,
                            expected_relative_delay_classes,
                            expected_relative_delay_padded,
                            make_energy_objective_padded,
                            make_joint_objective_padded,
                            make_round_objective_padded,
                            make_throughput_objective_padded,
                            make_time_objective_padded,
                            round_complexity_classes,
                            round_complexity_padded, throughput_padded)
from ..core.optimize import (joint_optimal, make_energy_objective,
                             make_joint_objective, make_round_objective,
                             make_throughput_objective, make_time_objective,
                             optimize_routing, time_optimal)
from .registry import OBJECTIVES, STRATEGIES, objective, strategy
from .spec import EXPLICIT, Scenario

MODES = ("analyze", "simulate", "train")


# ---------------------------------------------------------------------------
# objective registry — named closed-form objectives (static + padded forms)
# ---------------------------------------------------------------------------

class ObjectiveDef(NamedTuple):
    """One optimizable/reportable closed form.

    ``static(params, consts, power, refs)`` returns the classic
    ``obj(p, m)`` callable; ``padded(params, consts, power, refs, m_max)``
    the traced-``m`` ``obj(p, m, logZ[, rho])`` of ``repro.core.batched``.
    ``refs`` carries the joint objective's normalizers
    (``tau_star``/``e_star``); ``uses_ctx`` marks objectives whose padded
    form takes the per-row sweep context (the Pareto weight ``rho``).
    """

    static: Callable
    padded: Callable
    needs_power: bool = False
    needs_refs: bool = False
    uses_ctx: bool = False


@objective("time")
def _obj_time() -> ObjectiveDef:
    return ObjectiveDef(
        static=lambda prm, c, pw, refs: make_time_objective(prm, c),
        padded=lambda prm, c, pw, refs, mx:
            make_time_objective_padded(prm, c, mx))


@objective("round")
def _obj_round() -> ObjectiveDef:
    return ObjectiveDef(
        static=lambda prm, c, pw, refs: make_round_objective(prm, c),
        padded=lambda prm, c, pw, refs, mx:
            make_round_objective_padded(prm, c, mx))


@objective("throughput")
def _obj_throughput() -> ObjectiveDef:
    return ObjectiveDef(
        static=lambda prm, c, pw, refs: make_throughput_objective(prm),
        padded=lambda prm, c, pw, refs, mx:
            make_throughput_objective_padded(prm, mx))


@objective("energy")
def _obj_energy() -> ObjectiveDef:
    return ObjectiveDef(
        static=lambda prm, c, pw, refs: make_energy_objective(prm, c, pw),
        padded=lambda prm, c, pw, refs, mx:
            make_energy_objective_padded(prm, c, pw, mx),
        needs_power=True)


@objective("joint")
def _obj_joint() -> ObjectiveDef:
    return ObjectiveDef(
        static=lambda prm, c, pw, refs: make_joint_objective(
            prm, c, pw, refs["rho"], refs["tau_star"], refs["e_star"]),
        padded=lambda prm, c, pw, refs, mx: make_joint_objective_padded(
            prm, c, pw, refs["tau_star"], refs["e_star"], mx),
        needs_power=True, needs_refs=True, uses_ctx=True)


def get_objective(name: str) -> ObjectiveDef:
    return OBJECTIVES.get(name)()


# ---------------------------------------------------------------------------
# strategy registry — the paper's scheduling configurations (Section 5.3/6.5)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ResolveContext:
    """Inputs a strategy resolver sees (one scenario's worth)."""

    params: NetworkParams             # base network (uniform/base routing)
    consts: LearningConstants
    power: Optional[PowerProfile]
    rho: float                        # Pareto weight (objective spec)
    m: Optional[int]                  # forced concurrency (None = strategy's)
    m_max: int                        # concurrency search bound
    steps: int                        # Adam steps
    search: str                       # "batched" | "pruned" | "sequential"
    resolved: dict                    # earlier (p, m) results in this batch
    cache: dict                       # shared memo (e.g. tau_star / e_star)


def _as_pm(p, m) -> tuple[np.ndarray, int]:
    return np.asarray(p, dtype=np.float64), int(m)


@strategy("asyncsgd")
def _strat_asyncsgd(ctx: ResolveContext):
    """Uniform routing, m = n (Alg. 2 of [29])."""
    n = ctx.params.n
    return _as_pm(np.full(n, 1.0 / n), ctx.m if ctx.m is not None else n)


@strategy("max_throughput")
def _strat_max_throughput(ctx: ResolveContext):
    """p*_lambda at m = n."""
    m = ctx.m if ctx.m is not None else ctx.params.n
    obj = get_objective("throughput").static(ctx.params, ctx.consts,
                                             ctx.power, None)
    res = optimize_routing(obj, ctx.params.n, m, steps=ctx.steps)
    return _as_pm(res.p, m)


@strategy("round_opt")
def _strat_round_opt(ctx: ResolveContext):
    """p*_K at m = n ([31, 2])."""
    m = ctx.m if ctx.m is not None else ctx.params.n
    obj = get_objective("round").static(ctx.params, ctx.consts, ctx.power,
                                        None)
    res = optimize_routing(obj, ctx.params.n, m, steps=ctx.steps)
    return _as_pm(res.p, m)


@strategy("time_opt")
def _strat_time_opt(ctx: ResolveContext):
    """(p*_tau, m*_tau) — the paper's proposed strategy."""
    if ctx.m is not None:
        obj = get_objective("time").static(ctx.params, ctx.consts, ctx.power,
                                           None)
        res = optimize_routing(obj, ctx.params.n, ctx.m, steps=ctx.steps)
        return _as_pm(res.p, ctx.m)
    res = time_optimal(ctx.params, ctx.consts, m_max=ctx.m_max,
                       steps=ctx.steps, search=ctx.search)
    ctx.cache["tau_star"] = float(res.value)
    return _as_pm(res.p, res.m)


@strategy("energy_opt")
def _strat_energy_opt(ctx: ResolveContext):
    """Closed-form (p*_E, m = 1) — Eq. 16."""
    if ctx.power is None:
        raise ValueError("strategy 'energy_opt' needs a power profile "
                         "(EnergySpec)")
    return _as_pm(energy_optimal_routing(ctx.params, ctx.power),
                  ctx.m if ctx.m is not None else 1)


@strategy("joint")
def _strat_joint(ctx: ResolveContext):
    """(p*_rho, m*_rho) — the Eq. 18 scalarization at the scenario's rho."""
    if ctx.power is None:
        raise ValueError("strategy 'joint' needs a power profile "
                         "(EnergySpec)")
    tau_star = ctx.cache.get("tau_star")
    if tau_star is None:
        if "time_opt" in ctx.resolved:
            p_tau, m_tau = ctx.resolved["time_opt"]
            tau_star = float(wallclock_time(
                ctx.params._replace(p=jnp.asarray(p_tau)), m_tau, ctx.consts))
        else:
            tau_star = time_optimal(ctx.params, ctx.consts, m_max=ctx.m_max,
                                    steps=ctx.steps,
                                    search=ctx.search).value
        ctx.cache["tau_star"] = tau_star
    e_star = ctx.cache.get("e_star")
    if e_star is None:
        e_star = ctx.cache["e_star"] = float(
            minimal_energy(ctx.params, ctx.consts, ctx.power))
    res = joint_optimal(ctx.params, ctx.consts, ctx.power, ctx.rho, tau_star,
                        e_star, m_max=ctx.m_max, steps=ctx.steps,
                        search=ctx.search)
    return _as_pm(res.p, res.m)


def default_m_max(n: int) -> int:
    """The historical ``make_strategies`` search bound."""
    return n + max(8, n // 4)


def _resolve_class_strategy(scenario: Scenario, cache: dict
                            ) -> tuple[np.ndarray, int]:
    """Class-space strategy resolution — O(#classes), never expands.

    Returns a PER-CLASS routing vector ``p`` of shape ``[C]`` (one member's
    probability for each class; the class mass is ``count_c * p_c``).
    Supported strategies: ``"asyncsgd"`` (uniform per-member routing,
    ``m = n_total`` unless forced) and ``"time_opt"`` (the class-space
    concurrency sweep of ``repro.core.optimize.time_optimal_classes``;
    requires an explicit ``StrategySpec.m_max`` — the per-client default
    ``n + max(8, n//4)`` would be absurd at ``n = 10^6``).  Other
    registered strategies raise: resolve them on the expanded per-client
    network (``aggregate=False``) when the population is small enough.
    """
    from ..core.optimize import time_optimal_classes

    spec = scenario.strategy
    classes = scenario.class_params()
    n_total = int(scenario.n)
    C = scenario.network.classes.C
    if spec.name == "asyncsgd":
        m = spec.m if spec.m is not None else n_total
        return _as_pm(np.full(C, 1.0 / n_total), m)
    if spec.name == "time_opt":
        if spec.m_max is None:
            raise ValueError(
                "class-network 'time_opt' needs an explicit "
                "StrategySpec.m_max: the per-client default scales with the "
                f"population (n_total = {n_total} here)")
        if spec.m is not None and spec.m > spec.m_max:
            raise ValueError(f"forced m={spec.m} exceeds m_max={spec.m_max}")
        from ..core.batched import make_time_objective_classes
        from ..core.optimize import batched_concurrency_sweep

        if spec.m is not None:
            res = batched_concurrency_sweep(
                make_time_objective_classes(classes, scenario.consts,
                                            spec.m_max),
                classes, m_grid=[spec.m], m_max=spec.m_max,
                steps=spec.steps).best
        else:
            res = time_optimal_classes(classes, scenario.consts, spec.m_max,
                                       search=spec.search, steps=spec.steps)
        cache.setdefault("tau_star", float(res.value))
        return _as_pm(res.p, res.m)
    raise ValueError(
        f"strategy {scenario.strategy.name!r} has no class-space resolver; "
        "class networks support 'explicit', 'asyncsgd' and 'time_opt' "
        "(expand with NetworkSpec.from_clusters(..., aggregate=False) to "
        "use the per-client resolvers)")


def resolve_strategy(scenario: Scenario, *, resolved: Optional[dict] = None,
                     cache: Optional[dict] = None
                     ) -> tuple[np.ndarray, int]:
    """One scenario's ``(p, m)``: explicit spec or registry resolver.

    Class-aggregated networks dispatch to the O(#classes) resolvers BEFORE
    any per-client array exists — ``scenario.params()`` would expand the
    population, which is exactly what the class axis avoids.
    """
    spec = scenario.strategy
    if spec.name == EXPLICIT:
        return _as_pm(spec.p, spec.m)
    if scenario.is_class_network:
        return _resolve_class_strategy(scenario,
                                       {} if cache is None else cache)
    n = scenario.n
    ctx = ResolveContext(
        params=scenario.params(), consts=scenario.consts,
        power=scenario.power(), rho=scenario.objective.rho, m=spec.m,
        m_max=spec.m_max if spec.m_max is not None else default_m_max(n),
        steps=spec.steps, search=spec.search,
        resolved={} if resolved is None else resolved,
        cache={} if cache is None else cache)
    return STRATEGIES.get(spec.name)(ctx)


# ---------------------------------------------------------------------------
# the suite
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SuiteResult:
    """Result of one :meth:`ScenarioSuite.run` call.

    ``entries[name]`` is mode-dependent: a closed-form dict (``analyze``),
    a per-seed list of ``EventStats`` (``simulate``), or a per-seed list of
    ``TrainLog`` (``train``).  ``programs`` counts the distinct compiled
    programs (buckets) the call dispatched — the bucketing win is
    ``programs < len(entries)`` for structurally-alike scenarios.
    ``cache_hits`` counts entries served from the suite-level result cache
    (keyed by ``Scenario.hash()`` x seeds x mode x run settings): re-running
    an unchanged scenario costs nothing.

    Scenarios carrying a ``TraceSpec`` (``SimSpec.trace``) additionally
    fill ``traces[name]`` — per-seed decoded telemetry rings
    (``repro.obs.rings.decode`` dicts for ``simulate``, update-ring dicts
    for ``train``) — and, for ``simulate``, ``drift[name]``: per-seed
    ``repro.obs.drift.drift_report`` comparisons of the ring empirics
    against the closed forms.  Both stay ``None`` when nothing traced.
    """

    mode: str
    entries: dict
    seeds: tuple
    lanes: int
    programs: int
    strategies: dict  # name -> (p, m) resolved routing/concurrency
    cache_hits: int = 0
    metrics: Optional[dict] = None  # Metrics.snapshot() of the owning suite
    traces: Optional[dict] = None   # name -> per-seed decoded rings
    drift: Optional[dict] = None    # name -> per-seed drift reports


@dataclasses.dataclass
class SuiteCaches:
    """The content-keyed caches a :class:`ScenarioSuite` runs on, as a
    shareable bundle: pass one ``SuiteCaches`` to many suites (the
    ``repro.serve`` dispatcher builds a fresh suite per micro-batch) and
    they share resident jitted programs, built trainers, per-entry
    results and DataSpec-built datasets.  Name-keyed state (resolved
    strategies) stays per-suite — names are caller-chosen and collide
    across requests."""

    jit: dict = dataclasses.field(default_factory=dict)
    trainers: dict = dataclasses.field(default_factory=dict)
    results: dict = dataclasses.field(default_factory=dict)
    data: dict = dataclasses.field(default_factory=dict)


class ScenarioSuite:
    """A keyed collection of Scenarios sharing a seed set."""

    def __init__(self, scenarios, seeds=(0,), *, caches=None, metrics=None):
        from ..obs.metrics import Metrics  # standalone helper module

        if isinstance(scenarios, Scenario):
            scenarios = [scenarios]
        if not isinstance(scenarios, dict):
            scenarios = {
                (s.name or f"scenario{i}"): s
                for i, s in enumerate(scenarios)}
        if not scenarios:
            raise ValueError("need at least one scenario")
        for k, s in scenarios.items():
            if not isinstance(s, Scenario):
                raise TypeError(f"suite entry {k!r} is not a Scenario: {s!r}")
        self.scenarios: dict[str, Scenario] = dict(scenarios)
        self.seeds = tuple(int(s) for s in seeds)
        self.caches = caches if caches is not None else SuiteCaches()
        self.metrics = metrics if metrics is not None else Metrics()
        self._strategies: dict[str, tuple[np.ndarray, int]] = {}
        self._jit_cache = self.caches.jit
        self._trainers = self.caches.trainers
        self._result_cache = self.caches.results  # Scenario.hash keys
        self._data_cache = self.caches.data  # DataSpec-built datasets

    @classmethod
    def strategy_grid(cls, base: Scenario, strategies, seeds=(0,),
                      **strategy_kw) -> "ScenarioSuite":
        """One suite entry per strategy name, derived from ``base``."""
        return cls({name: base.with_strategy(name, **strategy_kw)
                    for name in strategies}, seeds=seeds)

    def __len__(self) -> int:
        return len(self.scenarios)

    def to_dict(self) -> dict:
        return {"seeds": list(self.seeds),
                "scenarios": {k: s.to_dict()
                              for k, s in self.scenarios.items()}}

    @classmethod
    def from_dict(cls, d: dict) -> "ScenarioSuite":
        return cls({k: Scenario.from_dict(v)
                    for k, v in d["scenarios"].items()},
                   seeds=tuple(d.get("seeds", (0,))))

    # -- strategy resolution (cached) ---------------------------------------

    def resolve(self) -> dict[str, tuple[np.ndarray, int]]:
        """Resolved ``{name: (p, m)}`` for every scenario (cached; shared
        normalizers like tau*/E* are computed once per network).

        The sharing key covers everything the cached values depend on —
        network, constants, energy spec AND the strategy search settings
        (``m_max``/``steps``/``search``) — so a suite sweeping power
        profiles or optimizer budgets never reuses a stale tau*/E*.
        """
        with self.metrics.timed("suite.resolve"):
            caches: dict = {}
            for name, scn in self.scenarios.items():
                if name in self._strategies:
                    continue
                net_key = (str(scn.network.to_dict()),
                           str(scn.learning.to_dict()),
                           str(None if scn.energy is None
                               else scn.energy.to_dict()),
                           scn.strategy.m_max, scn.strategy.steps,
                           scn.strategy.search)
                shared = caches.setdefault(net_key,
                                           {"cache": {}, "resolved": {}})
                pm = resolve_strategy(scn, resolved=shared["resolved"],
                                      cache=shared["cache"])
                shared["resolved"][scn.strategy.name] = pm
                self._strategies[name] = pm
            return {name: self._strategies[name] for name in self.scenarios}

    # -- dispatch ------------------------------------------------------------

    def run(self, mode: str = "analyze", **kw) -> SuiteResult:
        """Every scenario in ``mode``.  Host spans (``repro.obs.metrics``):
        ``suite.run`` holds ``suite.resolve`` and then, per bucket,
        ``suite.pack`` (lane padding and stacking, the program lookup),
        ``suite.dispatch`` (the program, to ``block_until_ready``) and
        ``suite.unpack`` (per-lane slicing and unpadding, the result
        cache); train buckets record ``suite.dispatch`` only.  In
        ``simulate``, ``suite.pack`` builds the lane batch in NumPy and
        moves it with one ``jax.device_put``, and ``suite.unpack`` fetches
        the statistics with one ``jax.device_get`` and slices and unpads
        on the host; the counter ``suite.transfers{mode, dir}`` counts
        those two per bucket."""
        runners = {"analyze": self._run_analyze,
                   "simulate": self._run_simulate,
                   "train": self._run_train}
        if mode not in runners:
            raise ValueError(
                f"unknown mode: {mode!r}; expected one of {MODES}")
        with self.metrics.timed("suite.run", mode=mode):
            res = runners[mode](**kw)
        self.metrics.inc("suite.requests", by=len(self.scenarios), mode=mode)
        self.metrics.inc("suite.cache_hits", by=res.cache_hits, mode=mode)
        self.metrics.inc("suite.programs", by=res.programs, mode=mode)
        self.metrics.inc("suite.lanes", by=res.lanes, mode=mode)
        res.metrics = self.metrics.snapshot()
        return res

    # -- analyze: closed forms, one jit per structure bucket -----------------

    def _run_analyze(self) -> SuiteResult:
        """Closed forms for every scenario, bucketed by static structure.

        Populations are padded to the suite-wide ``n_max`` under the
        traced-``n`` convention (``repro.core.buzen.pad_network``), so a
        mixed-population suite plans into buckets keyed only by
        ``(CS buffer, power structure)`` — one compiled program where the
        pre-padding planner compiled one per distinct ``n`` — and the
        padded rows reproduce the unpadded per-scenario closed forms
        bitwise (``tests/test_padded_n.py``).
        """
        strategies = self.resolve()
        names = list(self.scenarios)
        # class-aggregated scenarios never inflate the per-client pad: the
        # suite-wide n_max spans plain scenarios only, class lanes pad on
        # the CLASS axis (c_max) instead
        n_max = max((s.n for s in self.scenarios.values()
                     if not s.is_class_network), default=0)
        c_max = max((s.network.classes.C for s in self.scenarios.values()
                     if s.is_class_network), default=0)
        entries: dict = {}
        cache_hits = 0
        buckets: dict = {}
        for name in names:
            scn = self.scenarios[name]
            ckey = ("analyze", scn.hash())
            hit = self._result_cache.get(ckey)
            if hit is not None:
                entries[name] = hit
                cache_hits += 1
                continue
            key = (scn.network.mu_cs is not None, _power_sig(scn),
                   scn.is_class_network)
            buckets.setdefault(key, []).append(name)

        programs = 0
        for (has_cs, power_sig, is_classes), members in buckets.items():
            has_power = power_sig is not None
            with self.metrics.timed("suite.pack", mode="analyze"):
                m_max = max(strategies[name][1] for name in members)
                axis_max = c_max if is_classes else n_max
                if is_classes:
                    prm = _stack_params(
                        [pad_classes(
                            self.scenarios[n_].class_params(strategies[n_][0]),
                            c_max) for n_ in members])
                else:
                    prm = _stack_params(
                        [pad_network(
                            self.scenarios[n_].params(strategies[n_][0]),
                            n_max) for n_ in members])
                consts = _stack_consts([self.scenarios[n_].consts
                                        for n_ in members])
                power = (_stack_power([_pad_power(self.scenarios[n_].power(),
                                                  axis_max) for n_ in members])
                         if has_power else None)
                m_vec = jnp.asarray([strategies[n_][1] for n_ in members],
                                    jnp.int64)
                rho = jnp.asarray([self.scenarios[n_].objective.rho
                                   for n_ in members])
                sig = ("analyze", is_classes, axis_max, has_cs, power_sig,
                       m_max)
                fn = self._jit_cache.get(sig)
                if fn is None:
                    build = (_build_analyze_classes if is_classes
                             else _build_analyze)
                    fn = self._jit_cache[sig] = build(m_max, has_power)
                    programs += 1
            with self.metrics.timed("suite.dispatch", mode="analyze"):
                out = jax.block_until_ready(fn(prm, m_vec, consts, power,
                                               rho))
            self.metrics.observe("suite.lanes_per_dispatch", len(members),
                                 mode="analyze")
            with self.metrics.timed("suite.unpack", mode="analyze"):
                for i, name in enumerate(members):
                    # class rows report per-CLASS delays (one member each);
                    # truncate to the scenario's own axis either way
                    n_i = (self.scenarios[name].network.classes.C if is_classes
                           else self.scenarios[name].n)
                    row = {k: np.asarray(v[i]) for k, v in out.items()}
                    row["delays"] = row["delays"][:n_i]
                    p, m = strategies[name]
                    obj_name = self.scenarios[name].objective.name
                    # None (not a mislabeled tau) for objectives analyze cannot
                    # evaluate: registered extensions without an analyze column
                    val_key = _ANALYZE_KEY.get(obj_name)
                    entries[name] = {
                        "p": p, "m": m, "eta": self.scenarios[name].eta(),
                        "throughput": float(row["throughput"]),
                        "K_eps": float(row["K_eps"]),
                        "tau": float(row["tau"]),
                        "delays": row["delays"],  # E0[D_i] (Thm 2)
                        "energy": (float(row["energy"]) if has_power
                                   else None),
                        "objective": obj_name,
                        "value": (float(row[val_key])
                                  if val_key is not None and val_key in row
                                  else None),
                    }
                    ckey = ("analyze", self.scenarios[name].hash())
                    self._result_cache[ckey] = entries[name]
        return SuiteResult(mode="analyze", entries=entries, seeds=self.seeds,
                           lanes=len(names), programs=programs,
                           strategies=strategies, cache_hits=cache_hits)

    # -- simulate: device event engine, one jit per structure bucket ---------

    def _run_simulate(self, num_updates: int, *, warmup: int = 0,
                      m_max: Optional[int] = None,
                      backend: Optional[str] = None) -> SuiteResult:
        """Device event engine through the ``repro.sim`` backend dispatch.

        Backend precedence: the ``backend=`` kwarg, else each scenario's
        ``SimSpec``, else the process-wide ``REPRO_SIM_BACKEND``; lanes are
        bucketed by structure AND backend, so pinned scenarios coexist.
        ``"reference"`` and ``"batched"`` are bitwise identical on alike
        lanes (``tests/test_sim_backends.py``).

        Mixed populations share one program: lanes are padded to the
        suite-wide ``n_max`` (clients ``>= n`` carry zero routing mass and
        never receive tasks), and because trajectories are bitwise
        invariant to that padding (``events._route_client``), each lane's
        statistics — unpadded before they are returned/cached — equal the
        per-scenario unpadded run at the same table size exactly.
        """
        from ..sim.backend import resolve_backend
        from ..sim.batched_events import build_class_lanes_fn, build_lanes_fn

        strategies = self.resolve()
        names = list(self.scenarios)
        n_max = max((s.n for s in self.scenarios.values()
                     if not s.is_class_network), default=0)
        c_max = max((s.network.classes.C for s in self.scenarios.values()
                     if s.is_class_network), default=0)
        entries: dict = {}
        traces: dict = {}
        drift: dict = {}
        cache_hits = 0
        buckets: dict = {}
        for name in names:
            scn = self.scenarios[name]
            bk = resolve_backend(backend if backend is not None
                                 else scn.sim_backend)
            interp = None if scn.sim is None else scn.sim.interpret
            tr = 0 if scn.trace is None else int(scn.trace.events)
            ck = 1 if scn.sim is None else int(scn.sim.chunk)
            key = (scn.network.law, scn.network.mu_cs is not None,
                   _power_sig(scn), bk, interp, scn.is_class_network, tr, ck)
            buckets.setdefault(key, []).append(name)

        programs = 0
        S = len(self.seeds)
        for (law, has_cs, power_sig, bk, interp, is_classes, tr, ck), \
                members in buckets.items():
            has_power = power_sig is not None
            # the table size comes from ALL bucket members (trajectories
            # depend on it: init_state draws per-slot), so the *effective*
            # size — not the raw kwarg — keys the result cache: a hit is
            # bitwise identical to what this bucket would compute fresh,
            # regardless of which members happen to be cached already
            m_top = max(strategies[name][1] for name in members)
            mx = m_max or m_top
            if mx < m_top:
                # jit'd gathers clamp silently — a task table smaller than
                # a lane's m would return plausible-but-wrong statistics
                raise ValueError(
                    f"m_max={mx} is smaller than the largest resolved "
                    f"concurrency m={m_top} in this suite")
            todo = []
            for name in members:
                ckey = ("simulate", self.scenarios[name].hash(), self.seeds,
                        int(num_updates), int(warmup), mx, bk, interp)
                hit = self._result_cache.get(ckey)
                if hit is not None:
                    entries[name] = hit
                    cache_hits += 1
                    if tr:  # cached alongside the stats, same ckey
                        thit = self._result_cache.get(("trace",) + ckey)
                        if thit is not None:
                            traces[name], drift[name] = thit
                else:
                    todo.append((name, ckey))
            if not todo:
                continue
            with self.metrics.timed("suite.pack", mode="simulate"):
                axis_max = c_max if is_classes else n_max
                lanes = jax.device_put(_host_lanes(
                    [self.scenarios[n_] for n_, _ in todo],
                    [strategies[n_] for n_, _ in todo], self.seeds,
                    axis_max, is_classes, has_power))
                self.metrics.inc("suite.transfers", mode="simulate",
                                 dir="to_device")
                sig = ("simulate", is_classes, axis_max, law, has_cs,
                       power_sig, mx, int(num_updates), int(warmup), bk,
                       interp, tr, ck)
                fn = self._jit_cache.get(sig)
                if fn is None:
                    if is_classes:
                        fn = self._jit_cache[sig] = build_class_lanes_fn(
                            bk, int(num_updates), int(warmup), law, mx,
                            has_power, trace_events=tr, chunk=ck)
                    else:
                        fn = self._jit_cache[sig] = build_lanes_fn(
                            bk, int(num_updates), int(warmup), law, mx,
                            has_power, interpret=interp, trace_events=tr,
                            chunk=ck)
                    programs += 1
            with self.metrics.timed("suite.dispatch", mode="simulate"):
                out = jax.block_until_ready(fn(*lanes))
            self.metrics.observe("suite.lanes_per_dispatch", len(todo) * S,
                                 mode="simulate")
            with self.metrics.timed("suite.unpack", mode="simulate"):
                # one host copy of the bucket; lanes are NumPy views of it
                out = jax.device_get(out)
                self.metrics.inc("suite.transfers", mode="simulate",
                                 dir="to_host")
                stats, rings = out if tr else (out, None)
                for i, (name, ckey) in enumerate(todo):
                    # class lanes: statistics are per-CLASS — unpad on the
                    # class axis (expand_class_stats recovers per-member views)
                    n_i = (self.scenarios[name].network.classes.C if is_classes
                           else self.scenarios[name].n)
                    entries[name] = [unpad_stats(_lane(stats, i * S + j), n_i)
                                     for j in range(S)]
                    self._result_cache[ckey] = entries[name]
            if not tr:
                continue
            from ..obs.drift import drift_report, predict
            from ..obs.rings import decode

            for i, (name, ckey) in enumerate(todo):
                scn = self.scenarios[name]
                m_i = strategies[name][1]
                # closed forms are seed- and run-invariant: one predict
                # per (scenario, m), cached across suite runs
                pkey = ("drift_pred", scn.hash(), int(m_i))
                preds = self._result_cache.get(pkey)
                if preds is None:
                    # Scenario.params() expands a class network, so the
                    # closed forms always see the member population
                    preds = predict(scn.params(strategies[name][0]), m_i)
                    if is_classes:
                        # class rings index stations per CLASS: fold the
                        # per-member delay predictions onto the class
                        # axis (E0[D_c] = sum of the members' shares)
                        cnt = np.asarray(
                            scn.class_params(strategies[name][0]).count)
                        lbl = np.repeat(np.arange(len(cnt)), cnt)
                        d = np.bincount(
                            lbl,
                            weights=np.asarray(preds["delays"],
                                               dtype=np.float64),
                            minlength=len(cnt))
                        preds = dict(preds,
                                     delays=[float(v) for v in d])
                    self._result_cache[pkey] = preds
                traces[name] = [decode(_lane(rings, i * S + j))
                                for j in range(S)]
                drift[name] = [
                    drift_report(d, predictions=preds, law=law,
                                 tolerance=scn.trace.tolerance)
                    for d in traces[name]]
                self._result_cache[("trace",) + ckey] = (traces[name],
                                                         drift[name])
        return SuiteResult(mode="simulate", entries=entries, seeds=self.seeds,
                           lanes=len(names) * S, programs=programs,
                           strategies=strategies, cache_hits=cache_hits,
                           traces=traces or None, drift=drift or None)

    # -- train: fused device trainer (PR-2 lane planner) ---------------------

    def _client_data(self, scn: Scenario, name: str):
        """``(clients, test_data)`` for a scenario's ``DataSpec`` (memoized
        by spec content x population, so alike scenarios share the arrays
        and the trainer memo keeps hitting)."""
        if scn.data is None:
            raise ValueError(
                f"mode='train' for scenario {name!r} needs either an "
                "explicit clients= argument or a DataSpec on the scenario")
        key = (str(scn.data.to_dict()), scn.n)
        hit = self._data_cache.get(key)
        if hit is None:
            hit = self._data_cache[key] = scn.data.build(scn.n)
        return hit

    def _run_train(self, *, model, clients=None, horizon_time: float,
                   test_data=None, max_updates: Optional[int] = None,
                   loss_fn=None, **config_overrides) -> SuiteResult:
        from ..fl.engine import DeviceTrainer  # local: fl imports scenario
        from ..fl.models import cross_entropy_loss

        strategies = self.resolve()
        names = list(self.scenarios)
        run_sig = (float(horizon_time), max_updates,
                   tuple(sorted(config_overrides.items())))
        entries: dict = {}
        traces: dict = {}
        cache_hits = 0
        buckets: dict = {}
        for name in names:
            scn = self.scenarios[name]
            ckey = ("train", scn.hash(), self.seeds, run_sig)
            hit = self._result_cache.get(ckey)
            # identity-checked: a hit requires the SAME model/clients/
            # test_data objects the cached logs were trained with
            if hit is not None and hit[0] is model and hit[1] is clients \
                    and hit[2] is test_data and hit[3] is loss_fn:
                entries[name] = hit[4]
                if hit[5] is not None:
                    traces[name] = hit[5]
                cache_hits += 1
                continue
            if clients is None and not scn.is_class_network:
                # DataSpec-driven scenarios bucket by STRUCTURE (like
                # analyze/simulate): the network, client table and power
                # profile ride each lane as vmapped arguments, so
                # mixed-population train requests share one program.
                # fl_config draws only law/grad_clip from the spec (eta is
                # per-lane); the power profile needs only its structural
                # signature; the data spec pins the shared test set.
                key = ("nets", scn.network.law,
                       scn.network.mu_cs is not None, _power_sig(scn),
                       scn.learning.grad_clip,
                       str(None if scn.data is None else scn.data.to_dict()),
                       scn.sim_backend,
                       None if scn.sim is None else scn.sim.interpret,
                       0 if scn.trace is None else int(scn.trace.updates),
                       tuple(sorted(config_overrides.items())))
            else:
                key = ("exact", str(scn.network.to_dict()),
                       scn.learning.grad_clip,
                       str(None if scn.energy is None
                           else scn.energy.to_dict()),
                       str(None if scn.data is None else scn.data.to_dict()),
                       scn.sim_backend,
                       None if scn.sim is None else scn.sim.interpret,
                       0 if scn.trace is None else int(scn.trace.updates),
                       tuple(sorted(config_overrides.items())))
            buckets.setdefault(key, []).append((name, ckey))

        programs = 0
        for key, members in buckets.items():
            lane_mode = key[0] == "nets"
            # the template scenario sizes the trainer's static row count:
            # the largest population in a structural bucket, any member in
            # an exact one (all identical networks)
            ref_name = (max((nm for nm, _ in members),
                            key=lambda nm: self.scenarios[nm].n)
                        if lane_mode else members[0][0])
            scn0 = self.scenarios[ref_name]
            cfg = scn0.fl_config(**config_overrides)
            if clients is None:
                bucket_clients, built_test = self._client_data(
                    scn0, ref_name)
                bucket_test = test_data if test_data is not None \
                    else built_test
            else:
                bucket_clients, bucket_test = clients, test_data
            # identity-checked memo: the cached trainer holds strong refs
            # to everything it was built from, and a hit requires the SAME
            # objects (model, clients, test data, loss) — never a stale
            # trainer evaluating against a superseded test set
            cached = self._trainers.get(key)
            trainer = None
            if cached is not None and cached[0] is model \
                    and cached[1] is bucket_clients \
                    and cached[2] is bucket_test and cached[3] is loss_fn:
                trainer = cached[4]
            if trainer is None:
                template_net = (pad_network(scn0.params(), scn0.n)
                                if lane_mode else scn0.params())
                trainer = DeviceTrainer(
                    model, bucket_clients, template_net, cfg,
                    test_data=bucket_test,
                    power=None if lane_mode else scn0.power(),
                    loss_fn=loss_fn or cross_entropy_loss,
                    sim_backend=scn0.sim_backend,
                    sim_interpret=None if scn0.sim is None
                    else scn0.sim.interpret,
                    trace_updates=0 if scn0.trace is None
                    else scn0.trace.updates)
                self._trainers[key] = (model, bucket_clients, bucket_test,
                                       loss_fn, trainer)
            n_top = trainer.n
            ps, ms, etas, seeds = [], [], [], []
            nets, lane_clients, lane_powers = [], [], []
            for name, _ in members:
                scn = self.scenarios[name]
                p, m = strategies[name]
                if lane_mode:
                    p = np.concatenate(
                        [np.asarray(p, np.float64),
                         np.zeros(n_top - len(p))])
                    net_i = pad_network(scn.params(), n_top)
                    cl_i, _ = self._client_data(scn, name)
                    pw_i = scn.power()
                    if pw_i is not None:
                        pw_i = _pad_power(pw_i, n_top)
                for s in self.seeds:
                    ps.append(p)
                    ms.append(m)
                    etas.append(scn.eta())
                    seeds.append(s)
                    if lane_mode:
                        nets.append(net_i)
                        lane_clients.append(cl_i)
                        lane_powers.append(pw_i)
            lane_kw = {}
            if lane_mode:
                lane_kw = dict(
                    nets=nets, lane_clients=lane_clients,
                    lane_powers=(None if lane_powers[0] is None
                                 else lane_powers))
            before = len(trainer._jit_cache)
            with self.metrics.timed("suite.dispatch", mode="train"):
                logs, _ = trainer.run_lanes(ps, ms, etas, seeds,
                                            float(horizon_time),
                                            max_updates=max_updates,
                                            **lane_kw)
            self.metrics.observe("suite.lanes_per_dispatch", len(ps),
                                 mode="train")
            programs += max(len(trainer._jit_cache) - before, 0)
            S = len(self.seeds)
            lane_rings = trainer.last_update_rings
            if lane_rings is not None:
                from ..obs.rings import decode
            for i, (name, ckey) in enumerate(members):
                entries[name] = logs[i * S:(i + 1) * S]
                if lane_rings is not None:
                    traces[name] = [decode(lane_rings[i * S + j])
                                    for j in range(S)]
                self._result_cache[ckey] = (model, clients, test_data,
                                            loss_fn, entries[name],
                                            traces.get(name))
        return SuiteResult(mode="train", entries=entries, seeds=self.seeds,
                           lanes=len(names) * len(self.seeds),
                           programs=programs, strategies=strategies,
                           cache_hits=cache_hits, traces=traces or None)


_ANALYZE_KEY = {"time": "tau", "round": "K_eps", "throughput": "throughput",
                "energy": "energy", "joint": "joint"}


# ---------------------------------------------------------------------------
# lane stacking / bucket program builders
# ---------------------------------------------------------------------------

def _power_sig(scn) -> Optional[bool]:
    """Structural signature of a scenario's power profile for bucketing:
    ``None`` (no energy spec) or whether the CS power term is present —
    both change the stacked-pytree structure and the compiled program."""
    if scn.energy is None:
        return None
    return scn.energy.P_cs is not None


def _stack_params(params_list) -> NetworkParams:
    """Stack per-lane NetworkParams leaf-wise ([L, n] / [L] arrays)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)


def _pad_power(power: PowerProfile, n_max: int) -> PowerProfile:
    """Pad a power profile to ``n_max`` client rows with zero powers —
    padded clients are never busy, so they contribute exactly 0 energy.
    Keeps the kind of array it is given, like ``pad_network``."""
    xp = array_module(power.P_c)

    def pad(x):
        x = xp.asarray(x)
        return xp.concatenate(
            [x, xp.zeros((n_max - x.shape[0],), dtype=x.dtype)])

    return power._replace(P_c=pad(power.P_c), P_u=pad(power.P_u),
                          P_d=pad(power.P_d))


def _lane_keys(seeds) -> np.ndarray:
    """``[S, 2]`` uint32 lane keys on the host, bitwise
    ``jnp.stack([jax.random.PRNGKey(s) for s in seeds])``: a threefry key
    holds the high and the low 32-bit word of the int64 seed."""
    s = np.asarray(seeds, np.int64)
    return np.stack([s >> 32, s & 0xFFFFFFFF], axis=-1).astype(np.uint32)


def _host_lanes(scenarios, strategies, seeds, axis_max: int,
                is_classes: bool, has_power: bool) -> tuple:
    """One simulate bucket's lane inputs ``(lane_params, m_vec, keys,
    power)`` as NumPy arrays, lanes scenario-major then seed.

    Each scenario's network is materialized and padded once, on the host,
    and its row repeated over the seeds.  The DVFS power profile is the
    one exception: its arithmetic runs where ``Scenario.power`` always ran
    it, so its values stay bitwise what the device computes, and the rows
    come back in one fetch.
    """
    S = len(seeds)

    def repeat(rows):
        return jax.tree_util.tree_map(
            lambda *xs: np.repeat(np.stack(xs), S, axis=0), *rows)

    if is_classes:
        rows = [pad_classes(scn.network.class_params(p, xp=np), axis_max)
                for scn, (p, _) in zip(scenarios, strategies)]
    else:
        rows = [pad_network(scn.network.params(p, xp=np), axis_max)
                for scn, (p, _) in zip(scenarios, strategies)]
    power = None
    if has_power:
        power = repeat([_pad_power(pw, axis_max) for pw in
                        jax.device_get([scn.power() for scn in scenarios])])
    m_vec = np.repeat(np.asarray([m for _, m in strategies], np.int32), S)
    keys = np.tile(_lane_keys(seeds), (len(scenarios), 1))
    return repeat(rows), m_vec, keys, power


def _lane(tree, k: int):
    """Lane ``k`` of a lane-stacked pytree (``[k, ...]`` keeps a NumPy
    leaf an array, never a scalar)."""
    return jax.tree_util.tree_map(lambda a: a[k, ...], tree)


def _stack_consts(consts_list) -> LearningConstants:
    return LearningConstants(*[jnp.asarray([float(getattr(c, f))
                                            for c in consts_list])
                               for f in LearningConstants._fields])


def _stack_power(power_list) -> PowerProfile:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *power_list)


def _build_analyze(m_max: int, has_power: bool):
    """One jitted, vmapped closed-form evaluation over scenario lanes."""

    def one(prm, m, consts, power, rho):
        logZ = log_normalizing_constants(prm, m_max)
        thr = throughput_padded(logZ, m)
        delays = expected_relative_delay_padded(prm, m, logZ, m_max)
        k_eps = round_complexity_padded(prm, m, consts, logZ, m_max)
        tau = k_eps / thr
        out = {"throughput": thr, "K_eps": k_eps, "tau": tau,
               "delays": delays}
        if has_power:
            en = energy_complexity_padded(prm, m, consts, power, logZ, m_max)
            out["energy"] = en
            out["joint"] = rho * en + (1.0 - rho) * tau
        return out

    if has_power:
        return jax.jit(jax.vmap(one))

    # named (not a lambda) so repro.analysis.tracecheck program budgets can
    # identify the analyze bucket program in the compile log
    def analyze_lanes(prm, m, consts, _pw, rho):
        return one(prm, m, consts, None, rho)

    return jax.jit(jax.vmap(analyze_lanes, in_axes=(0, 0, 0, None, 0)))


def _build_analyze_classes(m_max: int, has_power: bool):
    """The class-space analogue of :func:`_build_analyze`.

    Each lane is a :class:`~repro.core.buzen.ClassParams` network: the
    class Buzen DP is O(C m^2) and every population sum is class-weighted,
    so the analyze pass never materializes a per-client array — n = 10^6
    scenarios cost the same as n = 10 at equal class counts.  ``delays``
    is per-CLASS (one member of each class).
    """

    def one(cls_, m, consts, power, rho):
        logZ = class_log_normalizing_constants(cls_, m_max)
        thr = throughput_padded(logZ, m)
        delays = expected_relative_delay_classes(cls_, m, logZ, m_max)
        k_eps = round_complexity_classes(cls_, m, consts, logZ, m_max)
        tau = k_eps / thr
        out = {"throughput": thr, "K_eps": k_eps, "tau": tau,
               "delays": delays}
        if has_power:
            en = energy_complexity_classes(cls_, m, consts, power, logZ,
                                           m_max)
            out["energy"] = en
            out["joint"] = rho * en + (1.0 - rho) * tau
        return out

    if has_power:
        return jax.jit(jax.vmap(one))

    # named (not a lambda) for the tracecheck program budgets
    def analyze_class_lanes(prm, m, consts, _pw, rho):
        return one(prm, m, consts, None, rho)

    return jax.jit(jax.vmap(analyze_class_lanes,
                            in_axes=(0, 0, 0, None, 0)))


