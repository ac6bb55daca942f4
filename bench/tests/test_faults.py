"""A whole run with the timed path broken underneath must come out with
``correct`` false.  The chip check is skipped; everything else is the run
as the benchmark makes it, at sizes a test can hold.

Faults each cell can have (the cells run on one chip, so no exchange
between chips can be left out):

* a step that returns its state unchanged (the event step; the optimizer's
  Adam step);
* half of the batch left out and the rest standing in for it (half of the
  lanes of the lane program; half of the concurrency rows of the sweep);
* an answer altered where it is produced (a lane's throughput; the
  closed-form throughput).

The analyze cell's faults are planted once more after set-up, where only
the window's requests can show them: a sweep that returns a neighbour of
its optimum, and a closed-form program whose answer is altered.
"""
import io
import json
import time
import types

import jax
import numpy as np
import pytest

from bench import harness

# the cells' traffic at sizes a test holds; the limits stay the cells' own
SIM = {"concurrency": [5, 12], "seeds_per_concurrency": 2, "m_max": 12,
       "warmup": 20, "updates": 80, "lanes_per_concurrency": 2}
ANALYZE = {"m_max": 12, "steps": 40}


def _run(workload, small, seed=2**31 + 11):
    from repro.sim import batched_events

    # programs traced before a fault was planted must not be reused
    jax.clear_caches()
    batched_events._build_lanes_fn.cache_clear()
    cell = harness.load_cell(workload)
    check = dict(cell.traffic["check"])
    if "lanes_per_concurrency" in small:
        check["lanes_per_concurrency"] = small["lanes_per_concurrency"]
    cell.traffic = dict(cell.traffic, check=check, **{
        k: v for k, v in small.items() if k != "lanes_per_concurrency"})
    out, err = io.StringIO(), io.StringIO()
    code, result = harness.run(cell, seed=seed, seconds=0.2, trace=False,
                               t_start=time.perf_counter(),
                               require_chip=False, out=out, err=err)
    assert code == 0, err.getvalue()
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    return result


def _sim_stats_unchanged(monkeypatch):
    from repro.core import events

    def step(params, state, **kw):
        z = jax.numpy.zeros((), jax.numpy.int32)
        return state, events.EventOut(is_update=z > 0, time=state.t,
                                      slot=z, client=z, delay=z)

    monkeypatch.setattr(events, "step_event", step)


def _sim_half_lanes(monkeypatch):
    from repro.sim import batched_events

    build = batched_events.build_lanes_fn

    def half(*a, **kw):
        fn = build(*a, **kw)

        def run(params, m, keys, power):
            h = m.shape[0] // 2
            out = fn(jax.tree_util.tree_map(lambda x: x[:h], params),
                     m[:h], keys[:h], power)
            return jax.tree_util.tree_map(
                lambda x: jax.numpy.concatenate([x, x]), out)
        return run

    monkeypatch.setattr(batched_events, "build_lanes_fn", half)


def _sim_answer_altered(monkeypatch):
    from repro.core import events

    final = events.finalize_stats

    def altered(st):
        s = final(st)
        return s._replace(throughput=s.throughput * (1.0 + 1e-4))

    monkeypatch.setattr(events, "finalize_stats", altered)


def _an_step_unchanged(monkeypatch):
    from repro.core import optimize

    monkeypatch.setattr(optimize, "_adam_minimize",
                        lambda loss, theta0, steps, lr: (theta0, None))


def _an_half_rows(monkeypatch):
    from repro.core import optimize

    sweep = optimize.batched_concurrency_sweep

    def half(objective, params, *, m_grid, **kw):
        m_grid = np.asarray(m_grid)
        return sweep(objective, params, m_grid=m_grid[:len(m_grid) // 2],
                     **kw)

    monkeypatch.setattr(optimize, "batched_concurrency_sweep", half)


def _an_answer_altered(monkeypatch):
    from repro.scenario import suite

    thr = suite.throughput_padded
    monkeypatch.setattr(suite, "throughput_padded",
                        lambda logZ, m: thr(logZ, m) * (1.0 + 1e-4))


def _after_setup(monkeypatch, plant):
    load = harness.load_mode

    def load_planted(cell):
        class Mode(load(cell).Mode):
            def setup(self):
                super().setup()
                plant(monkeypatch, self)
        return types.SimpleNamespace(Mode=Mode)

    monkeypatch.setattr(harness, "load_mode", load_planted)


def _an_sweep_neighbour(monkeypatch, mode):
    from repro.scenario import suite

    resolve = suite.resolve_strategy

    def neighbour(*a, **kw):
        p, m = resolve(*a, **kw)
        return p, m - 1

    monkeypatch.setattr(suite, "resolve_strategy", neighbour)


def _an_program_altered(monkeypatch, mode):
    def altered(fn):
        def run(*a):
            out = dict(fn(*a))
            out["tau"] = out["tau"] * (1.0 + 1e-4)
            return out
        return run

    for sig, fn in list(mode.caches.jit.items()):
        monkeypatch.setitem(mode.caches.jit, sig, altered(fn))


def test_sound_simulate_run_is_correct():
    r = _run("table1.sim-msweep", SIM)
    assert r["correct"], r["checks"]
    assert r["checks"]["stats_gap"]["value"] == 0.0


@pytest.mark.parametrize("plant", [_sim_stats_unchanged, _sim_half_lanes,
                                   _sim_answer_altered])
def test_broken_simulate_run_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    r = _run("table1.sim-msweep", SIM)
    assert not r["correct"], r["checks"]


def test_sound_analyze_run_is_correct():
    r = _run("table1.analyze-timeopt", ANALYZE)
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("plant", [_an_step_unchanged, _an_half_rows,
                                   _an_answer_altered])
def test_broken_analyze_run_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    r = _run("table1.analyze-timeopt", ANALYZE)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("plant", [_an_sweep_neighbour, _an_program_altered])
def test_analyze_fault_after_setup_is_not_correct(monkeypatch, plant):
    _after_setup(monkeypatch, plant)
    r = _run("table1.analyze-timeopt", ANALYZE)
    assert not r["correct"], r["checks"]
