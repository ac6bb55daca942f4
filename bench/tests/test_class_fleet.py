"""The harness reads a class-aggregated fleet: its configuration and
traffic are found by name, the class replay follows the program's class
engine, a run with the class engine broken underneath is not correct, and
the cluster fleets read what they read before.  (The float32 control
runs at the cell's own size, in ``test_control.py``: at the small size
its clock stays short enough to pass.)

Sizes a test holds: Table 1 x 100 = 10^4 members in five classes, m 64,
100 + 400 updates per lane, 4 lane seeds, every lane replayed.
"""
import io
import json
import time

import jax
import numpy as np
import pytest

from bench import fleet, harness

CELL = "class1m.sim-m1000"
SMALL = {"scale": 100, "concurrency": [64], "m_max": 64, "warmup": 100,
         "updates": 400, "seeds_per_concurrency": 4,
         "lanes_per_concurrency": 4}


def small_cell():
    cell = harness.load_cell(CELL)
    cell.config = dict(cell.config, scale=SMALL["scale"])
    check = dict(cell.traffic["check"],
                 lanes_per_concurrency=SMALL["lanes_per_concurrency"])
    cell.traffic = dict(cell.traffic, check=check, **{
        k: SMALL[k] for k in ("concurrency", "m_max", "warmup", "updates",
                              "seeds_per_concurrency")})
    return cell


def run_small(seed=2**31 + 11):
    """A whole run of the class cell at the small size, on the CPU."""
    from repro.sim import batched_events

    # programs traced before a fault was planted must not be reused
    jax.clear_caches()
    batched_events._build_class_lanes_fn.cache_clear()
    out, err = io.StringIO(), io.StringIO()
    code, result = harness.run(small_cell(), seed=seed, seconds=0.2,
                               trace=False, t_start=time.perf_counter(),
                               require_chip=False, out=out, err=err)
    assert code == 0, err.getvalue()
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    return result


def test_class_config_and_traffic_are_found_by_name():
    cell = harness.load_cell(CELL)
    assert cell.config["fleet"] == "classes"
    assert cell.traffic["mode"] == "simulate"
    assert cell.traffic["trace_programs"] == ["class_lanes"]
    assert harness.load_mode(cell).Mode.__name__ == "Mode"
    names = {name for name, *_ in cell.metrics}
    assert {"sim_updates_per_s", "setup_s", "device_idle.sim",
            "sim_program_us_per_update", "suite_host_ms.sim",
            "planner_pack_ms.sim", "planner_unpack_ms.sim"} <= names
    assert "analyze_s" not in names


def test_class_fleet_is_table1_times_ten_thousand():
    config = harness.load_cell(CELL).config
    c = fleet.class_arrays(config)
    np.testing.assert_array_equal(
        c["count"], [150_000, 150_000, 200_000, 400_000, 100_000])
    table1 = fleet.arrays(harness.load_cell("table1.sim-msweep").config)
    for k in fleet.RATES:
        np.testing.assert_array_equal(np.unique(c[k]), np.unique(table1[k]))
    p = fleet.uniform_routing(config)
    assert p.shape == (5,)
    assert float(np.sum(c["count"] * p)) == pytest.approx(1.0, abs=1e-12)
    net = fleet.network(config)
    assert net.classes is not None and net.n == 10**6
    np.testing.assert_array_equal(net.classes.count, c["count"])
    np.testing.assert_array_equal(net.classes.mu_c, c["mu_c"])
    with pytest.raises(ValueError):
        fleet.arrays(config)


def test_table1_network_is_still_per_client():
    config = harness.load_cell("table1.sim-msweep").config
    net = fleet.network(config)
    a = fleet.arrays(config)
    assert net.classes is None and net.n == 100
    for k in fleet.RATES:
        np.testing.assert_array_equal(getattr(net, k), a[k])
    assert net.labels == tuple(c["name"] for c in config["clusters"]
                               for _ in range(c["count"]))
    np.testing.assert_array_equal(fleet.uniform_routing(config),
                                  np.full(100, 0.01))
    with pytest.raises(ValueError):
        fleet.class_arrays(config)


def test_class_replay_matches_the_program():
    r = run_small()
    assert r["correct"], r["checks"]
    assert r["checks"]["count_mismatch"]["value"] == 0
    assert r["checks"]["stats_gap"]["value"] <= 1e-10


def _state_unchanged(monkeypatch):
    from repro.core import events

    def step(classes, state, **kw):
        z = jax.numpy.zeros((), jax.numpy.int32)
        return state, events.EventOut(is_update=z > 0, time=state.t,
                                      slot=z, client=z, delay=z)

    monkeypatch.setattr(events, "step_class_event", step)


def _half_lanes(monkeypatch):
    from repro.sim import batched_events

    build = batched_events.build_class_lanes_fn

    def half(*a, **kw):
        fn = build(*a, **kw)

        def run(classes, m, keys, power):
            h = m.shape[0] // 2
            out = fn(jax.tree_util.tree_map(lambda x: x[:h], classes),
                     m[:h], keys[:h], power)
            return jax.tree_util.tree_map(
                lambda x: jax.numpy.concatenate([x, x]), out)
        return run

    monkeypatch.setattr(batched_events, "build_class_lanes_fn", half)


def _answer_altered(monkeypatch):
    from repro.core import events

    final = events.finalize_stats

    def altered(st):
        s = final(st)
        return s._replace(throughput=s.throughput * (1.0 + 1e-4))

    monkeypatch.setattr(events, "finalize_stats", altered)


def _member_shifted_by_one(monkeypatch):
    from repro.core import events

    route = events._route_class

    def shifted(mass, count, key, prefix=None):
        c, mb = route(mass, count, key, prefix)
        return c, (mb + 1) % jax.numpy.maximum(count[c], 1)

    monkeypatch.setattr(events, "_route_class", shifted)


def _member_from_half_the_class(monkeypatch):
    # tasks meet in compute queues that the reference keeps apart
    from repro.core import events

    route = events._route_class

    def half(mass, count, key, prefix=None):
        c, mb = route(mass, count, key, prefix)
        return c, mb // 2

    monkeypatch.setattr(events, "_route_class", half)


def _class_from_unsorted_prefix(monkeypatch):
    from repro.core import events

    route = events._route_class

    def unsorted(mass, count, key, prefix=None):
        return route(mass, count, key, events.seqcumsum(mass)[::-1])

    monkeypatch.setattr(events, "_route_class", unsorted)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_lanes,
                                   _answer_altered, _member_shifted_by_one,
                                   _member_from_half_the_class,
                                   _class_from_unsorted_prefix])
def test_broken_class_run_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    r = run_small()
    assert not r["correct"], r["checks"]
