"""Generator of ``simulate`` traffic: event-engine requests through
``ScenarioSuite.run(mode="simulate")``.

Each request is one suite of explicit-strategy scenarios, one per
concurrency ``m`` of the traffic file (uniform routing), each run on
``seeds_per_concurrency`` lane seeds drawn from ``--seed`` and the
request's index, so no request repeats another and none hits the suite's
result cache.  All requests share one ``SuiteCaches``, as the server does,
so the lane program stays resident.

Correctness: once the window has closed, a sample of the window's lanes
drawn from the seed (for every ``m``, ``lanes_per_concurrency`` distinct
lanes of one request) is run again by the plain reference of the fleet's
kind (``bench/reference/events_ref.py`` client by client,
``events_class_ref.py`` class by class) from the same lane seed, and the
two sets of statistics are compared:
``count_mismatch`` counts integer statistics that differ (updates, updates
per client), ``stats_gap`` is the worst relative gap of the float
statistics (time, throughput, mean delay per client or class, mean
station occupancy), each leaf by its largest entry.
"""
from __future__ import annotations

import numpy as np

from bench import fleet
from bench.reference import events_class_ref, events_ref

FLOAT_LEAVES = ("time", "throughput", "mean_delay", "mean_queue_counts")
INT_LEAVES = ("updates", "delay_counts")


def lane_seeds(seed: int, index: int, count: int) -> list:
    """``count`` lane seeds of request ``index`` (``0`` is the warm-up)."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), int(index)])
    return [int(s) for s in ss.generate_state(count, np.uint32)]


def reference(config: dict) -> tuple:
    """``(replay, fleet arrays)``: the plain lane replay of the
    configuration's fleet kind and the fleet as that replay reads it."""
    if config["fleet"] == "classes":
        return events_class_ref.lane_stats, fleet.class_arrays(config)
    return events_ref.lane_stats, fleet.arrays(config)


def leaf_gap(prog, ref) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = float(np.max(np.abs(ref))) if ref.size else 0.0
    diff = float(np.max(np.abs(prog - ref))) if ref.size else 0.0
    return diff / scale if scale > 0 else diff


def compare(prog: dict, ref: dict) -> tuple:
    """``(integer entries that differ, worst relative float-leaf gap)``."""
    mismatch = sum(int(np.sum(np.asarray(prog[k]) != np.asarray(ref[k])))
                   for k in INT_LEAVES)
    gap = max(leaf_gap(prog[k], ref[k]) for k in FLOAT_LEAVES)
    return mismatch, gap


class Mode:
    def __init__(self, cell, seed):
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)

    def setup(self):
        import jax

        from repro.obs.metrics import Metrics
        from repro.scenario import Scenario, SimSpec, StrategySpec
        from repro.scenario.suite import SuiteCaches

        t = self.traffic
        with jax.profiler.TraceAnnotation("bench.build"):
            net = fleet.network(self.config)
            p = fleet.uniform_routing(self.config)
            self.scenarios = {
                f"m{m}": Scenario(network=net,
                                  strategy=StrategySpec("explicit", p=p,
                                                        m=int(m)),
                                  sim=SimSpec(backend=t["backend"]),
                                  name=f"m{m}")
                for m in t["concurrency"]}
        self.caches = SuiteCaches()
        self.metrics = Metrics()
        self.lanes = len(self.scenarios) * int(t["seeds_per_concurrency"])
        self._run(0)  # warm: the lane program, from the cache or compiled

    def _run(self, index: int):
        import jax

        from repro.scenario import ScenarioSuite

        t = self.traffic
        seeds = lane_seeds(self.seed, index, int(t["seeds_per_concurrency"]))
        suite = ScenarioSuite(self.scenarios, seeds=seeds, caches=self.caches,
                              metrics=self.metrics)
        with jax.profiler.TraceAnnotation("bench.suite_run"):
            res = suite.run(mode="simulate", num_updates=int(t["updates"]),
                            warmup=int(t["warmup"]), m_max=int(t["m_max"]),
                            backend=t["backend"])
        return seeds, res

    def request(self, i: int):
        seeds, res = self._run(i + 1)
        t = self.traffic
        work = {"updates": self.lanes * (int(t["warmup"]) + int(t["updates"]))}
        return work, (seeds, res.entries)

    def annotate(self, run):
        run.spans = [s for s in self.metrics.spans()
                     if run.window_start <= s["start"] <= run.window_end]

    def readings(self, run, control: bool = False) -> dict:
        """The numbers compared, on lanes drawn from the seed: the
        program's statistics against the float64 reference's or, with
        ``control``, the float32-clock reference's in the program's place."""
        t = self.traffic
        self.caches = None  # the program's state goes before the reference
        rng = np.random.default_rng([self.seed & (2**64 - 1), 1])
        replay, arrays = reference(self.config)
        p = fleet.uniform_routing(self.config)
        done = run.done
        mismatch, gap = 0, 0.0
        for m in t["concurrency"]:
            seeds, entries = done[int(rng.integers(len(done)))].output
            picks = rng.choice(len(seeds), int(t["check"][
                "lanes_per_concurrency"]), replace=False)
            for k in picks:
                args = (arrays, p, int(m), int(t["m_max"]), seeds[k],
                        int(t["warmup"]), int(t["updates"]))
                ref = replay(*args)
                if control:
                    prog = replay(*args, dtype=np.float32)
                else:
                    stats = entries[f"m{m}"][k]
                    prog = {f: np.asarray(getattr(stats, f))
                            for f in FLOAT_LEAVES + INT_LEAVES}
                mm, g = compare(prog, ref)
                mismatch += mm
                gap = max(gap, g)
        return {"count_mismatch": float(mismatch), "stats_gap": gap}

    def check(self, run) -> list:
        lim = self.traffic["check"]["limits"]
        return [(k, v, float(lim[k])) for k, v in self.readings(run).items()]
