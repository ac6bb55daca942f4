"""Batched (p, m) optimizer engine vs the static per-m reference paths.

Covers the acceptance criteria of the batched-sweep refactor:
  * padded closed forms == static closed forms for every m;
  * batched sweep rows == per-m ``optimize_routing`` (n=4, m <= 8);
  * batched sweep optimum == seed sequential warm-start search on a
    reference n=8 network (values within 1e-6 relative);
  * ONE trace of the objective per sweep — no per-m recompilation;
  * batched Pallas Buzen kernel == ``repro.core.buzen`` in interpret mode,
    including the gradient (custom-VJP through the float64 reference).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LearningConstants, NetworkParams, PowerProfile,
                        batch_log_normalizing_constants,
                        batched_concurrency_sweep, energy_complexity,
                        energy_complexity_padded, expected_relative_delay,
                        expected_relative_delay_padded, joint_optimal,
                        log_normalizing_constants, make_round_objective,
                        make_time_objective, make_time_objective_padded,
                        optimize_routing, round_complexity,
                        round_complexity_padded, make_round_objective_padded,
                        sequential_concurrency_search, throughput,
                        throughput_padded, wallclock_time,
                        wallclock_time_padded)


def reference_params(rng, n, with_cs=False):
    p = rng.dirichlet(np.ones(n))
    params = NetworkParams(
        p=jnp.asarray(p),
        mu_c=jnp.asarray(rng.uniform(0.3, 8.0, n)),
        mu_d=jnp.asarray(rng.uniform(0.3, 8.0, n)),
        mu_u=jnp.asarray(rng.uniform(0.3, 8.0, n)))
    if with_cs:
        params = params.with_cs(rng.uniform(0.5, 8.0))
    return params


CONSTS = LearningConstants(M=2.0, G=5.0)


# ---------------------------------------------------------------------------
# padded closed forms == static closed forms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_cs", [False, True])
def test_padded_forms_match_static(with_cs):
    rng = np.random.default_rng(3)
    params = reference_params(rng, 5, with_cs)
    m_max = 9
    logZ = log_normalizing_constants(params, m_max)
    power = PowerProfile.from_dvfs(
        jnp.asarray(rng.uniform(0.1, 2.0, 5)), params.mu_c,
        jnp.asarray(rng.uniform(1.0, 5.0, 5)),
        jnp.asarray(rng.uniform(1.0, 5.0, 5)))
    for m in range(1, m_max + 1):
        mt = jnp.asarray(m)
        np.testing.assert_allclose(
            np.asarray(expected_relative_delay_padded(params, mt, logZ, m_max)),
            np.asarray(expected_relative_delay(params, m)), rtol=1e-10,
            atol=1e-12)
        np.testing.assert_allclose(
            float(throughput_padded(logZ, mt)),
            float(throughput(params, m)), rtol=1e-10)
        np.testing.assert_allclose(
            float(round_complexity_padded(params, mt, CONSTS, logZ, m_max)),
            float(round_complexity(params, m, CONSTS)), rtol=1e-10)
        np.testing.assert_allclose(
            float(wallclock_time_padded(params, mt, CONSTS, logZ, m_max)),
            float(wallclock_time(params, m, CONSTS)), rtol=1e-10)
        np.testing.assert_allclose(
            float(energy_complexity_padded(params, mt, CONSTS, power, logZ,
                                           m_max)),
            float(energy_complexity(params, m, CONSTS, power)), rtol=1e-10)


def test_padded_gradients_finite_at_m1():
    """The masked staleness sqrt must have a finite gradient at m = 1."""
    rng = np.random.default_rng(4)
    params = reference_params(rng, 4)
    logZ = log_normalizing_constants(params, 4)

    def f(p):
        return round_complexity_padded(params._replace(p=p), jnp.asarray(1),
                                       CONSTS, logZ, 4)

    g = jax.grad(f)(params.p)
    assert np.all(np.isfinite(np.asarray(g)))


# ---------------------------------------------------------------------------
# batched sweep rows == per-m optimize_routing (n=4, m <= 8)
# ---------------------------------------------------------------------------

def test_sweep_rows_match_per_m_optimize_routing():
    rng = np.random.default_rng(11)
    n, m_hi, steps = 4, 8, 300
    params = reference_params(rng, n)
    obj_static = make_time_objective(params, CONSTS)
    sweep = batched_concurrency_sweep(
        make_time_objective_padded(params, CONSTS, m_hi), params,
        m_grid=jnp.arange(1, m_hi + 1), steps=steps)
    for b, m in enumerate(range(1, m_hi + 1)):
        ref = optimize_routing(obj_static, n, m, steps=steps)
        np.testing.assert_allclose(sweep.values[b], ref.value, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(sweep.p[b]), np.asarray(ref.p),
                                   atol=1e-6)


def test_sweep_round_objective_matches():
    rng = np.random.default_rng(12)
    n, m = 4, 6
    params = reference_params(rng, n)
    ref = optimize_routing(make_round_objective(params, CONSTS), n, m,
                           steps=250)
    got = batched_concurrency_sweep(
        make_round_objective_padded(params, CONSTS, m), params,
        m_grid=jnp.asarray([m]), steps=250).best
    np.testing.assert_allclose(got.value, ref.value, rtol=1e-6)


# ---------------------------------------------------------------------------
# batched sweep == seed sequential search (reference n=8 network)
# ---------------------------------------------------------------------------

def test_sweep_matches_sequential_search_n8():
    rng = np.random.default_rng(42)
    n = 8
    params = reference_params(rng, n)
    m_max = n + 8
    seq = sequential_concurrency_search(
        make_time_objective(params, CONSTS), n, m_start=2, m_max=m_max,
        steps=400)
    bat = batched_concurrency_sweep(
        make_time_objective_padded(params, CONSTS, m_max), params,
        m_grid=jnp.arange(2, m_max + 1), steps=400).best
    assert bat.m == seq.m
    np.testing.assert_allclose(bat.value, seq.value, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(bat.p), np.asarray(seq.p),
                               atol=1e-6)


def test_joint_optimal_batched_matches_sequential():
    rng = np.random.default_rng(13)
    n = 4
    params = reference_params(rng, n)
    power = PowerProfile.from_dvfs(
        jnp.asarray(rng.uniform(0.1, 2.0, n)), params.mu_c,
        jnp.asarray(rng.uniform(1.0, 5.0, n)),
        jnp.asarray(rng.uniform(1.0, 5.0, n)))
    kw = dict(m_max=n + 4, steps=250)
    seq = joint_optimal(params, CONSTS, power, 0.3, 10.0, 100.0,
                        search="sequential", patience=100, **kw)
    bat = joint_optimal(params, CONSTS, power, 0.3, 10.0, 100.0, **kw)
    assert bat.m == seq.m
    np.testing.assert_allclose(bat.value, seq.value, rtol=1e-6)


# ---------------------------------------------------------------------------
# no per-m recompilation: ONE trace of the objective per sweep
# ---------------------------------------------------------------------------

def test_sweep_traces_objective_once(tracecheck):
    rng = np.random.default_rng(5)
    n, m_hi = 4, 8
    params = reference_params(rng, n)
    counted = tracecheck.counting(
        make_time_objective_padded(params, CONSTS, m_hi))
    batched_concurrency_sweep(counted, params,
                              m_grid=jnp.arange(1, m_hi + 1), steps=30)
    # scan + value_and_grad trace the loss a few times, plus one final
    # row_values evaluation — but never once per m (the B=8 grid rows all
    # share a single vmapped trace)
    assert counted.traces < m_hi, \
        f"objective traced {counted.traces}x for B={m_hi}"


# ---------------------------------------------------------------------------
# batched Pallas kernel vs core reference (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_cs", [False, True])
def test_batch_logZ_pallas_matches_jnp(with_cs):
    rng = np.random.default_rng(21)
    n, m_max, B = 6, 14, 5
    params = reference_params(rng, n, with_cs)
    ps = jnp.asarray(rng.dirichlet(np.ones(n), size=B))
    want = batch_log_normalizing_constants(params, ps, m_max, backend="jnp")
    got = batch_log_normalizing_constants(params, ps, m_max, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5,
                               atol=3e-5)


@pytest.mark.parametrize("with_cs", [False, True])
def test_single_logZ_pallas_dispatch(with_cs):
    rng = np.random.default_rng(22)
    params = reference_params(rng, 7, with_cs)
    want = np.asarray(log_normalizing_constants(params, 11))
    got = np.asarray(log_normalizing_constants(params, 11, backend="pallas"))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_pallas_backend_rejects_literal_method():
    rng = np.random.default_rng(24)
    params = reference_params(rng, 3)
    with pytest.raises(ValueError, match="aggregate"):
        log_normalizing_constants(params, 4, method="literal",
                                  backend="pallas")


def test_pallas_backend_gradient_matches_reference():
    """custom-VJP: grads through the Pallas forward equal the float64 path."""
    rng = np.random.default_rng(23)
    n, m_max = 5, 8
    params = reference_params(rng, n)

    def val(p, backend):
        logZ = batch_log_normalizing_constants(params, p[None], m_max,
                                               backend=backend)[0]
        return wallclock_time_padded(params._replace(p=p), jnp.asarray(m_max),
                                     CONSTS, logZ, m_max)

    g_ref = jax.grad(lambda p: val(p, "jnp"))(params.p)
    g_pal = jax.grad(lambda p: val(p, "pallas"))(params.p)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               rtol=2e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# the staged jit (lower / compile / run spans) == a plain jit call
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_ctx", [False, True])
def test_staged_sweep_bitwise_equals_plain_jit(monkeypatch, with_ctx):
    from repro.core import optimize

    rng = np.random.default_rng(17)
    n, m_max = 3, 5
    params = reference_params(rng, n)
    calls = []
    staged = optimize._run_staged

    def spy(fn, *args):
        calls.append((fn, args))
        return staged(fn, *args)

    monkeypatch.setattr(optimize, "_run_staged", spy)
    if with_ctx:
        power = PowerProfile.from_dvfs(
            jnp.asarray(rng.uniform(0.1, 2.0, n)), params.mu_c,
            jnp.asarray(rng.uniform(1.0, 5.0, n)),
            jnp.asarray(rng.uniform(1.0, 5.0, n)))
        joint_optimal(params, CONSTS, power, 0.3, 10.0, 100.0, m_max=m_max,
                      steps=20)
    else:
        batched_concurrency_sweep(
            make_time_objective_padded(params, CONSTS, m_max), params,
            m_grid=jnp.arange(2, m_max + 1), steps=20)
    (fn, args), = calls
    assert fn.__name__ == "concurrency_sweep"
    assert (args[2] is not None) == with_ctx
    got = jax.tree_util.tree_leaves(staged(fn, *args))
    want = jax.tree_util.tree_leaves(jax.jit(fn)(*args))
    assert len(got) == len(want) == 2
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
