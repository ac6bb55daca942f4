"""Generator of ``analyze`` traffic: requests for the time-optimal routing
and concurrency through ``ScenarioSuite.run(mode="analyze")``.

Each request is a fresh suite on the configuration's fleet with the
traffic file's strategy (``time_opt`` over ``m = 2..m_max``, ``steps``
Adam steps): the planner resolves the strategy (the concurrency sweep)
and evaluates the closed forms at the optimum.  Requests run back to back
and share one ``SuiteCaches``; each drops the suite's cached closed forms
of the scenario first, so that every request computes its answer in
full.  The requests are identical: the sweep bakes the fleet's rates into
its program, so a new fleet per request would compile inside the window
(see PERF.md, Open questions).

Correctness, once the window has closed, on one request of the window
drawn from the seed: ``closed_form_gap`` is the worst relative gap between
the throughput, ``K_eps``, ``tau`` and per-client delays that request
returned and the float64 reference's at the request's own ``(p, m)``;
``opt_gap`` compares the returned ``tau`` with the reference search's at
the same ``m`` and at its neighbours (a better neighbour counts against
the program).
"""
from __future__ import annotations

import numpy as np

from bench import fleet
from bench.reference import closed_forms
from bench.modes.simulate import leaf_gap

LEAVES = ("throughput", "K_eps", "tau", "delays")


class Mode:
    def __init__(self, cell, seed):
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)

    def setup(self):
        import jax

        from repro.core.complexity import LearningConstants
        from repro.obs.metrics import Metrics
        from repro.scenario import LearningSpec, Scenario, StrategySpec
        from repro.scenario.suite import SuiteCaches

        t = self.traffic
        with jax.profiler.TraceAnnotation("bench.build"):
            self.scenario = Scenario(
                network=fleet.network(self.config),
                learning=LearningSpec(consts=LearningConstants(
                    **self.config["learning_constants"])),
                strategy=StrategySpec(t["strategy"], m_max=int(t["m_max"]),
                                      steps=int(t["steps"]),
                                      search=t["search"]),
                name=self.config["name"])
        self.caches = SuiteCaches()
        self.metrics = Metrics()
        self._run()  # warm: the sweep and the closed forms

    def _run(self):
        import jax

        from repro.scenario import ScenarioSuite

        self.caches.results.pop(("analyze", self.scenario.hash()), None)
        suite = ScenarioSuite(self.scenario, caches=self.caches,
                              metrics=self.metrics)
        with jax.profiler.TraceAnnotation("bench.resolve"):
            suite.resolve()
        with jax.profiler.TraceAnnotation("bench.suite_run"):
            res = suite.run(mode="analyze")
        if res.cache_hits:
            raise RuntimeError("the closed forms came from the result cache")
        # this request's sweep's (p, m) and the closed forms evaluated there
        return res.entries[self.scenario.name]

    def request(self, i: int):
        return {}, self._run()

    def annotate(self, run):
        run.spans = [s for s in self.metrics.spans()
                     if run.window_start <= s["start"] <= run.window_end]

    def readings(self, run) -> dict:
        t = self.traffic
        self.caches = None  # the program's state goes before the reference
        rng = np.random.default_rng([self.seed & (2**64 - 1), 1])
        done = run.done
        entry = done[int(rng.integers(len(done)))].output
        arrays = fleet.arrays(self.config)
        consts = self.config["learning_constants"]
        m_max = int(t["m_max"])
        m = int(entry["m"])
        ref = closed_forms.closed_forms(arrays, entry["p"], m, consts, m_max)
        ms = [x for x in (m - 1, m, m + 1) if 2 <= x <= m_max]
        _, taus = closed_forms.time_opt(arrays, ms, consts, m_max,
                                        int(t["steps"]))
        tau = float(entry["tau"])
        at_m, best = float(taus[ms.index(m)]), float(np.min(taus))
        return {
            "closed_form_gap": max(leaf_gap(entry[k], ref[k])
                                   for k in LEAVES),
            "opt_gap": max(abs(tau - at_m) / at_m, (tau - best) / best)}

    def check(self, run) -> list:
        lim = self.traffic["check"]["limits"]
        return [(k, v, float(lim[k])) for k, v in self.readings(run).items()]
