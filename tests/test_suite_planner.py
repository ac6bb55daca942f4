"""The simulate planner's host-side lane batch.

``ScenarioSuite._run_simulate`` builds each bucket's lane inputs as NumPy
arrays, moves them to the device in one ``jax.device_put``, fetches the
statistics back in one ``jax.device_get`` and slices and unpads lanes on
the host.  These tests hold it to the per-lane device construction it
replaced (``pad_network``/``pad_classes`` per lane, ``_stack_params``,
stacked ``jax.random.PRNGKey``; ``tree_map`` slicing and ``unpad_stats``
on device arrays), bitwise and dtype for dtype, and count the transfers.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LearningConstants
from repro.core.buzen import pad_classes, pad_network
from repro.core.events import unpad_stats
from repro.obs.rings import decode
from repro.scenario import (EXPLICIT, EnergySpec, LearningSpec, NetworkSpec,
                            Scenario, ScenarioSuite, SimSpec, StrategySpec)
from repro.scenario.spec import ClusterSpec, TraceSpec
from repro.scenario.suite import (_host_lanes, _lane_keys, _pad_power,
                                  _stack_params, _stack_power)

CONSTS = LearningConstants(M=2.0, G=5.0)
UPDATES, WARMUP = 60, 10


def _network(n, rng, *, with_cs=False):
    return NetworkSpec(mu_c=rng.uniform(0.5, 3, n),
                       mu_d=rng.uniform(0.5, 3, n),
                       mu_u=rng.uniform(0.5, 3, n),
                       mu_cs=1.5 if with_cs else None)


def _mixed_n(sim, rng):
    """n = 3 and 5 in one bucket (padded to n_max = 5)."""
    return {f"n{n}": Scenario(
        network=_network(n, rng), learning=LearningSpec(consts=CONSTS),
        strategy=StrategySpec(EXPLICIT, p=rng.dirichlet(np.ones(n)),
                              m=2 + i), sim=sim)
        for i, n in enumerate((3, 5))}


def _classes(sim, rng):
    """Two class networks of different class counts (padded to c_max)."""
    rows = (ClusterSpec("A", 1.0, 6.0, 6.0, 4),
            ClusterSpec("B", 2.0, 7.0, 7.0, 2),
            ClusterSpec("C", 3.0, 8.0, 8.0, 6))
    return {f"c{k}": Scenario(
        network=NetworkSpec.from_clusters(rows[:k], aggregate=True),
        learning=LearningSpec(consts=CONSTS),
        strategy=StrategySpec("asyncsgd", m=m), sim=sim)
        for k, m in ((2, 3), (3, 5))}


def _energy(sim, rng):
    """Power profiles (with the CS power term) on a CS-buffer network."""
    out = {}
    for i, n in enumerate((3, 4)):
        out[f"e{n}"] = Scenario(
            network=_network(n, rng, with_cs=True),
            learning=LearningSpec(consts=CONSTS),
            strategy=StrategySpec(EXPLICIT, p=rng.dirichlet(np.ones(n)),
                                  m=3 + i),
            energy=EnergySpec(kappa=rng.uniform(0.1, 1.0, n),
                              P_u=rng.uniform(0.1, 1.0, n),
                              P_d=rng.uniform(0.1, 1.0, n), P_cs=0.7),
            sim=sim)
    return out


TRACED = TraceSpec(events=32)
CASES = {
    "mixed_n-batched": (_mixed_n, SimSpec(backend="batched")),
    "mixed_n-reference": (_mixed_n, SimSpec(backend="reference")),
    "mixed_n-batched-rings": (_mixed_n, SimSpec(backend="batched",
                                                trace=TRACED)),
    "classes-batched": (_classes, SimSpec(backend="batched")),
    "classes-batched-rings": (_classes, SimSpec(backend="batched",
                                                trace=TRACED)),
    "energy-batched": (_energy, SimSpec(backend="batched")),
    "energy-reference-rings": (_energy, SimSpec(backend="reference",
                                                trace=TRACED)),
}


def _per_lane_inputs(suite, names, is_classes, axis_max, has_power):
    """The lane batch as the planner built it one lane at a time on the
    device: the loop reference for :func:`_host_lanes`."""
    strategies = suite.resolve()
    lanes = [(n_, s) for n_ in names for s in suite.seeds]
    if is_classes:
        prm = _stack_params(
            [pad_classes(suite.scenarios[n_].class_params(strategies[n_][0]),
                         axis_max) for n_, _ in lanes])
    else:
        prm = _stack_params(
            [pad_network(suite.scenarios[n_].params(strategies[n_][0]),
                         axis_max) for n_, _ in lanes])
    power = (_stack_power([_pad_power(suite.scenarios[n_].power(), axis_max)
                           for n_, _ in lanes]) if has_power else None)
    m_vec = jnp.asarray([strategies[n_][1] for n_, _ in lanes], jnp.int32)
    keys = jnp.stack([jax.random.PRNGKey(s) for _, s in lanes])
    return prm, m_vec, keys, power


def _assert_trees_bitwise(got, want, what):
    g_leaves, g_def = jax.tree_util.tree_flatten(got)
    w_leaves, w_def = jax.tree_util.tree_flatten(want)
    assert g_def == w_def, what
    for k, (g, w) in enumerate(zip(g_leaves, w_leaves)):
        assert g.dtype == w.dtype and g.shape == w.shape, (what, k)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{what} leaf {k}")


@pytest.mark.parametrize("case", list(CASES))
def test_host_pack_and_unpack_match_per_lane_device_path(case):
    build, sim = CASES[case]
    scns = build(sim, np.random.default_rng(7))
    seeds = (0, 2**32 - 1, 123456789)
    suite = ScenarioSuite(scns, seeds=seeds)
    res = suite.run(mode="simulate", num_updates=UPDATES, warmup=WARMUP)
    assert res.programs == 1

    names = list(scns)
    first = scns[names[0]]
    is_classes = first.is_class_network
    axis_max = max(s.network.classes.C if is_classes else s.n
                   for s in scns.values())
    has_power = first.energy is not None
    strategies = suite.resolve()

    # pack: host lanes == the per-lane device construction, no weak types
    host = _host_lanes([scns[n_] for n_ in names],
                       [strategies[n_] for n_ in names], seeds, axis_max,
                       is_classes, has_power)
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(host))
    moved = jax.device_put(host)
    assert not any(x.weak_type for x in jax.tree_util.tree_leaves(moved))
    want = _per_lane_inputs(suite, names, is_classes, axis_max, has_power)
    for what, g, w in zip(("lane_params", "m_vec", "keys", "power"),
                          moved, want):
        _assert_trees_bitwise(g, w, what)

    # unpack: the suite's host entries == per-lane slicing + unpad_stats of
    # the same program's device output
    (fn,) = suite._jit_cache.values()
    out = fn(*want)
    tr = 0 if first.trace is None else first.trace.events
    stats, rings = out if tr else (out, None)
    S = len(seeds)
    for i, name in enumerate(names):
        n_i = scns[name].network.classes.C if is_classes else scns[name].n
        for j in range(S):
            ref = unpad_stats(jax.tree_util.tree_map(
                lambda a: a[i * S + j], stats), n_i)
            got = res.entries[name][j]
            assert all(isinstance(x, np.ndarray) for x in got)
            _assert_trees_bitwise(got, ref, f"{name}/{j}")
            if tr:
                ring = decode(jax.tree_util.tree_map(
                    lambda a: a[i * S + j], rings))
                assert set(res.traces[name][j]) == set(ring)
                for col, v in ring.items():
                    np.testing.assert_array_equal(
                        res.traces[name][j][col], v,
                        err_msg=f"{name}/{j}/{col}")


@pytest.mark.parametrize("seed", [0, 1, 2**32 - 1, 123456789, 2**40 + 3,
                                  2**63 - 1, -1, -(2**35)])
def test_lane_keys_match_stacked_prngkeys(seed):
    seeds = (seed, 5)
    got = _lane_keys(seeds)
    want = np.asarray(jnp.stack([jax.random.PRNGKey(s) for s in seeds]))
    assert got.dtype == want.dtype == np.uint32
    np.testing.assert_array_equal(got, want)


def test_transfers_one_each_way_per_bucket_and_none_on_cache_hit():
    rng = np.random.default_rng(3)
    scns = _mixed_n(SimSpec(backend="batched"), rng)
    # a second law is a second bucket
    scns["hyper"] = scns["n3"].replace(network=dataclasses.replace(
        scns["n3"].network, law="hyperexponential"))
    suite = ScenarioSuite(scns, seeds=(0, 1))

    def transfers():
        return tuple(suite.metrics.counter("suite.transfers",
                                           mode="simulate", dir=d)
                     for d in ("to_device", "to_host"))

    res = suite.run(mode="simulate", num_updates=UPDATES, warmup=WARMUP)
    assert res.programs == 2 and res.cache_hits == 0
    assert transfers() == (2, 2)
    for per_seed in res.entries.values():
        for stats in per_seed:
            assert all(type(x) is np.ndarray for x in stats)
    again = suite.run(mode="simulate", num_updates=UPDATES, warmup=WARMUP)
    assert again.cache_hits == len(scns)
    assert transfers() == (2, 2)
