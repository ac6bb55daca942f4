"""Paper-scale event-engine backend sweep (n = 100, m_max = 132).

The ROADMAP's paper-scale open item: simulate the Section-6 EMNIST
population (Table 1 at full scale, m = 132 in-flight tasks) compiled,
multi-lane, through every ``repro.sim`` backend:

  * ``reference`` — lane-at-a-time single-lane scans (the baseline);
  * ``batched``   — all lanes per scan step in ONE vmapped program (the
    row's ``speedup_vs_reference`` is the PR-over-PR tracked number);
  * ``pallas``    — the per-event table transition in the
    ``repro.kernels.events`` TPU kernel (interpret mode on CPU; the row
    asserts bitwise agreement with ``reference`` on its lanes — the
    exponential unit-draw rescale is exact).

Fidelity columns per row: relative throughput error vs the closed form
(Prop. 4) and relative staleness-identity error (Eq. 7:
``sum_i p_i E0[R_i] = m - 1``), both within the tolerances documented in
``tests/test_events.py`` at the default window (600 updates after a
400-update warmup).  A megastep chunk sweep (E in
``CHUNK_SWEEP``) times the batched backend retiring E events per scan
step at a low lane count (vmapping many lanes already amortizes the
per-step dispatch the megastep targets) — bitwise-equal trajectories
(``tests/test_megastep.py``), so the rows are pure dispatch-amortization
numbers, guarded by ``MAX_CHUNK_SLOWDOWN``.  A final row reruns the sweep through
``ScenarioSuite`` to record the suite-level result cache
(``cache_hits``/``programs``).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import jackson
from repro.kernels import default_interpret
from repro.scenario import ScenarioSuite
from repro.sim import simulate_stats_lanes

from .common import row
from .scenarios import events_scale_scenario, record

DEFAULT_BACKENDS = ("reference", "batched", "pallas")

#: megastep sizes for the chunk-sweep rows (1 == the single-step baseline)
CHUNK_SWEEP = (1, 8, 32)
#: regression guard: a megastep program must never land slower than this
#: factor of the single-step baseline at smoke scale (it exists to catch a
#: chunked path that stopped fusing, not to pin the speedup)
MAX_CHUNK_SLOWDOWN = 1.2


def _fidelity(params, m, stats):
    p = np.asarray(params.p)
    p = p / p.sum()
    lam = float(jackson.throughput(params, m))
    thr = float(np.mean(np.asarray(stats.throughput)))
    stale = float(np.mean([
        np.sum(p * np.asarray(stats.mean_delay[i]))
        for i in range(stats.throughput.shape[0])]))
    return (abs(thr - lam) / lam, abs(stale - (m - 1)) / (m - 1))


def run(scale: int = 1, m: int = 132, lanes: int = 6,
        num_updates: int = 600, warmup: int = 400,
        backends=DEFAULT_BACKENDS, pallas_lanes: int = 2,
        chunk_lanes: int = 2) -> list[str]:
    out = []
    # canonical order: reference first, so the batched speedup and pallas
    # bitwise comparison columns exist regardless of how --backends was
    # spelled; unknown names were already rejected by the CLI
    backends = [b for b in DEFAULT_BACKENDS if b in backends]
    scn = record("events_scale", events_scale_scenario(scale, m))
    params = scn.params(scn.strategy.p)
    n = scn.n

    def sweep(backend, L):
        def go():
            st = simulate_stats_lanes([params] * L, [m] * L, num_updates,
                                      warmup=warmup, m_max=m,
                                      backend=backend, seeds=range(L))
            jax.block_until_ready(st.throughput)
            return st

        go()  # compile
        t0 = time.perf_counter()
        st = go()
        return st, (time.perf_counter() - t0) * 1e6

    ref_us = None
    ref_small = None
    for backend in backends:
        L = pallas_lanes if backend == "pallas" else lanes
        st, us = sweep(backend, L)
        thr_err, stale_err = _fidelity(params, m, st)
        derived = (f"n={n}_m={m}_lanes={L}_updates={num_updates}"
                   f"_thr_err={thr_err:.3f}_stale_err={stale_err:.3f}")
        if backend == "reference":
            ref_us = us
            if "pallas" in backends:
                # reference stats on the pallas lane subset, bitwise check
                ref_small, _ = sweep("reference", pallas_lanes)
        elif ref_us is not None and backend == "batched":
            derived += f"_speedup_vs_reference={ref_us / us:.2f}x"
        elif backend == "pallas":
            derived += f"_interpret={default_interpret()}"
            if ref_small is not None:
                bitwise = all(
                    np.array_equal(np.asarray(getattr(ref_small, f)),
                                   np.asarray(getattr(st, f)))
                    for f in st._fields)
                derived += f"_bitwise_vs_reference={bitwise}"
        out.append(row(f"events_scale_{backend}", us, derived))

    # -- megastep chunk sweep (batched backend): E events per scan step,
    # same trajectories bitwise (tests/test_megastep.py), so the delta is
    # pure per-step dispatch amortization.  The guard fails the bench run
    # (and CI's smoke job) if any chunked program regresses past
    # MAX_CHUNK_SLOWDOWN x single-step.
    chunk_us = {}
    for chunk in CHUNK_SWEEP:
        def go_chunk(E=chunk):
            st = simulate_stats_lanes([params] * chunk_lanes,
                                      [m] * chunk_lanes,
                                      num_updates, warmup=warmup, m_max=m,
                                      backend="batched",
                                      seeds=range(chunk_lanes), chunk=E)
            jax.block_until_ready(st.throughput)
            return st

        go_chunk()  # compile
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            go_chunk()
            us = (time.perf_counter() - t0) * 1e6
            best = us if best is None else min(best, us)
        chunk_us[chunk] = best
        derived = (f"n={n}_m={m}_lanes={chunk_lanes}_updates={num_updates}"
                   f"_backend=batched")
        if chunk != 1:
            derived += (f"_speedup_vs_single={chunk_us[1] / best:.2f}x"
                        f";guard={MAX_CHUNK_SLOWDOWN:.1f}")
        out.append(row(f"events_scale_chunk_E{chunk}", best, derived))
    worst = max(us / chunk_us[1] for E, us in chunk_us.items() if E != 1)
    if worst > MAX_CHUNK_SLOWDOWN:
        raise AssertionError(
            f"megastep wall-clock {worst:.2f}x the single-step baseline "
            f"exceeds the {MAX_CHUNK_SLOWDOWN:.1f}x guard — the chunked "
            f"scan body likely stopped fusing (or the block draws went "
            f"sequential on a unit-factorized law)")

    # the loop-invariant routing-CDF hoist: "before" rebuilds the O(n)
    # sequential seqcumsum inside every scan step (route_prefix=None),
    # "after" computes it once outside and passes it in — everything else
    # about the two programs is identical, and the trajectories are the
    # same seqcumsum of the same p, so the work compared is bitwise-equal.
    # On CPU the XLA scan already hoists the loop-invariant cumsum, so this
    # row sits near 1.0x here — it exists to catch the compiled-TPU path
    # (no LICM across a pallas_call boundary) and any regression that makes
    # the prefix loop-variant again
    from repro.core import events as ev
    from repro.core.numerics import seqcumsum

    mult = 4 if params.mu_cs is not None else 3
    num_events = mult * (num_updates + warmup) + mult * m + 8

    def build(hoisted):
        @jax.jit
        def go(prm, key):
            st = ev.init_state(prm, m, key, m_max=m, warmup=warmup,
                               cap=warmup + num_updates)
            prefix = seqcumsum(prm.p) if hoisted else None

            def body(s, _):
                s, _o = ev.step_event(prm, s, route_prefix=prefix)
                return s, None

            st, _ = jax.lax.scan(body, st, None, length=num_events)
            return ev.finalize_stats(st)

        return go

    before_fn, after_fn = build(False), build(True)
    key0 = jax.random.PRNGKey(0)

    def t(fn):
        jax.block_until_ready(fn().throughput)  # compile
        min_us = None
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn().throughput)
            us = (time.perf_counter() - t0) * 1e6
            min_us = us if min_us is None else min(min_us, us)
        return min_us

    us_before = t(lambda: before_fn(params, key0))
    us_after = t(lambda: after_fn(params, key0))
    out.append(row("events_scale_cdf_hoist", us_after,
                   f"n={n}_before_us={us_before:.0f}"
                   f"_speedup={us_before / us_after:.2f}x"))

    # the same workload through the Scenario layer: one bucketed program,
    # then a re-run served entirely from the suite-level result cache
    suite = ScenarioSuite(scn, seeds=tuple(range(lanes)))
    t0 = time.perf_counter()
    res = suite.run(mode="simulate", num_updates=num_updates, warmup=warmup,
                    m_max=m, backend="batched")
    us_first = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    res2 = suite.run(mode="simulate", num_updates=num_updates,
                     warmup=warmup, m_max=m, backend="batched")
    us_cached = (time.perf_counter() - t0) * 1e6
    out.append(row(
        "events_scale_suite", us_first,
        f"programs={res.programs}_rerun_cache_hits={res2.cache_hits}"
        f"_rerun_us={us_cached:.0f}"))
    return out
