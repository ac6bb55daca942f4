"""Device event engine (repro.core.events) vs theory and the host reference.

``AsyncNetworkSim`` is the exact per-task-identity reference; the device
engine consumes randomness differently, so cross-checks are distributional
(documented tolerances: throughput within ~5%, per-client conditional mean
delays within ~10% + small absolute slack at CI sample sizes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (NetworkParams, PowerProfile, energy_per_round,
                        expected_relative_delay, throughput)
from repro.core.events import init_state, next_update, simulate_stats, step_event
from repro.core.simulator import AsyncNetworkSim, make_sampler


def random_params(seed, n, with_cs=False):
    rng = np.random.default_rng(seed)
    params = NetworkParams(
        p=jnp.asarray(rng.dirichlet(np.ones(n) * 2.0)),
        mu_c=jnp.asarray(rng.uniform(0.5, 4.0, n)),
        mu_d=jnp.asarray(rng.uniform(0.5, 4.0, n)),
        mu_u=jnp.asarray(rng.uniform(0.5, 4.0, n)))
    return params.with_cs(1.5) if with_cs else params


# ---------------------------------------------------------------------------
# stationary statistics vs closed forms (Prop. 4 / Thm 2) and the host sim
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_cs", [False, True])
def test_throughput_matches_prop4_and_host(with_cs):
    params = random_params(8, 4, with_cs)
    m = 6
    lam_th = float(throughput(params, m))
    for seed in (0, 1):
        st = simulate_stats(params, m, 20_000, warmup=3_000, seed=seed)
        np.testing.assert_allclose(float(st.throughput), lam_th, rtol=0.05)
    sim = AsyncNetworkSim(params, m, seed=0)
    host = sim.run(20_000, warmup=3_000)
    np.testing.assert_allclose(float(st.throughput), host.throughput,
                               rtol=0.06)
    # closed network: time-averaged occupancy sums to m exactly
    np.testing.assert_allclose(float(jnp.sum(st.mean_queue_counts)), m,
                               rtol=1e-9)


def test_mean_delay_matches_host_and_thm2():
    params = random_params(3, 4)
    m = 6
    st = simulate_stats(params, m, 30_000, warmup=4_000, seed=0)
    sim = AsyncNetworkSim(params, m, seed=1)
    host = sim.run(30_000, warmup=4_000)
    # same estimator (unscaled per-client conditional mean E0[R_i])
    np.testing.assert_allclose(np.asarray(st.mean_delay), host.mean_delay,
                               rtol=0.10, atol=0.05)
    d_th = np.asarray(expected_relative_delay(params, m))
    d_dev = np.asarray(params.p) * np.asarray(st.mean_delay)
    np.testing.assert_allclose(d_dev, d_th, rtol=0.08, atol=0.03)
    # staleness identity (Eq. 7): sum_i p_i E0[R_i] = m - 1
    np.testing.assert_allclose(d_dev.sum(), m - 1, rtol=0.03)


def test_energy_matches_formula_and_host():
    params = random_params(7, 4)
    rng = np.random.default_rng(2)
    power = PowerProfile(P_c=jnp.asarray(rng.uniform(1, 5, 4)),
                         P_u=jnp.asarray(rng.uniform(0.5, 2, 4)),
                         P_d=jnp.asarray(rng.uniform(0.2, 1, 4)))
    m = 5
    st = simulate_stats(params, m, 20_000, warmup=2_000, seed=1, power=power)
    per_round = float(st.energy) / int(st.updates)
    np.testing.assert_allclose(per_round, float(energy_per_round(params, power)),
                               rtol=0.05)
    host = AsyncNetworkSim(params, m, seed=3, power=power).run(20_000,
                                                              warmup=2_000)
    np.testing.assert_allclose(per_round, host.energy / host.updates,
                               rtol=0.08)


@pytest.mark.parametrize("dist", ["deterministic", "lognormal"])
def test_nonexponential_agrees_with_host(dist):
    params = random_params(10, 3)
    m = 4
    st = simulate_stats(params, m, 10_000, warmup=1_000, seed=0,
                        distribution=dist)
    host = AsyncNetworkSim(params, m, distribution=dist, seed=0).run(
        10_000, warmup=1_000)
    np.testing.assert_allclose(float(st.throughput), host.throughput,
                               rtol=0.06)
    np.testing.assert_allclose(np.asarray(st.mean_delay), host.mean_delay,
                               rtol=0.15, atol=0.1)
    assert np.isfinite(np.asarray(st.mean_delay)).all()


# ---------------------------------------------------------------------------
# batching semantics (vmap over seeds, padded (p, m) lanes)
# ---------------------------------------------------------------------------

def test_vmapped_seed_batch_bitwise_equals_stacked_singles():
    params = random_params(5, 3)
    keys = jax.random.split(jax.random.PRNGKey(42), 4)

    def run(k):
        return simulate_stats(params, 5, 800, warmup=100, key=k, m_max=5)

    batched = jax.vmap(run)(keys)
    singles = [run(k) for k in keys]
    for field in ("throughput", "mean_delay", "delay_counts", "energy",
                  "mean_queue_counts", "time"):
        b = np.asarray(getattr(batched, field))
        s = np.stack([np.asarray(getattr(r, field)) for r in singles])
        np.testing.assert_array_equal(b, s, err_msg=field)


def test_padded_pm_batch_equals_singles():
    params = random_params(6, 4)
    rng = np.random.default_rng(1)
    ps = jnp.stack([params.p, jnp.asarray(rng.dirichlet(np.ones(4)))])
    ms = jnp.asarray([3, 6])

    def run(p, m):
        return simulate_stats(params._replace(p=p), m, 3_000, warmup=400,
                              seed=7, m_max=6)

    batched = jax.vmap(run)(ps, ms)
    for i in range(2):
        single = run(ps[i], ms[i])
        np.testing.assert_array_equal(np.asarray(batched.throughput[i]),
                                      np.asarray(single.throughput))
        lam_th = float(throughput(params._replace(p=ps[i]), int(ms[i])))
        np.testing.assert_allclose(float(batched.throughput[i]), lam_th,
                                   rtol=0.08)


def test_inactive_slots_stay_inactive():
    """Padded slots never enter the dynamics: with m < m_max the total
    occupancy is m and padded slots keep phase INACTIVE."""
    from repro.core import events as E

    params = random_params(4, 3)
    st = init_state(params, 2, jax.random.PRNGKey(0), m_max=5)
    for _ in range(50):
        st, _ = step_event(params, st)
    phase = np.asarray(st.phase)
    assert (phase == E.INACTIVE).sum() == 3
    assert float(jnp.sum(st.occ_int)) <= st.t * 2 + 1e-9


def test_next_update_emits_every_update_once():
    """Scanning next_update k times yields k strictly increasing update
    times and round counter k."""
    params = random_params(2, 3)
    st = init_state(params, 4, jax.random.PRNGKey(3), m_max=4)

    def body(st, _):
        st, upd = next_update(params, st)
        return st, upd.time

    st, times = jax.lax.scan(body, st, None, length=200)
    times = np.asarray(times)
    assert int(st.round) == 200
    assert np.all(np.diff(times) > 0)


# ---------------------------------------------------------------------------
# guards (satellite: sampler validation, TrainLog robustness)
# ---------------------------------------------------------------------------

def test_make_sampler_rejects_nonpositive_rate():
    rng = np.random.default_rng(0)
    for kind in ("exponential", "deterministic", "lognormal"):
        sample = make_sampler(kind, rng)
        assert sample(1.0) > 0
        with pytest.raises(ValueError, match="positive"):
            sample(0.0)
        with pytest.raises(ValueError, match="positive"):
            sample(-1.0)


def test_simulate_stats_rejects_unknown_distribution():
    params = random_params(0, 3)
    with pytest.raises(ValueError, match="distribution"):
        simulate_stats(params, 3, 10, distribution="weibull")


def test_time_to_accuracy_guards():
    from repro.fl import TrainLog

    empty = TrainLog(times=[], accuracies=[], losses=[], updates=[])
    assert empty.time_to_accuracy(0.5) == float("inf")
    nan_log = TrainLog(times=[0.0, 1.0, 2.0],
                       accuracies=[float("nan"), 0.3, 0.7],
                       losses=[1.0, 1.0, 1.0], updates=[0, 1, 2])
    assert nan_log.time_to_accuracy(0.5) == 2.0
    assert nan_log.time_to_accuracy(0.9) == float("inf")


# ---------------------------------------------------------------------------
# fused trainer (repro.fl.engine) vs the host reference loop
# ---------------------------------------------------------------------------

def _tiny_fl_problem(n=4, seed=0):
    from repro.data import (iid_partition, make_synthetic_image_dataset,
                            train_test_split)

    full = make_synthetic_image_dataset(num_classes=4, samples_per_class=40,
                                        seed=seed)
    ds, test = train_test_split(full, 0.25, seed=seed + 1)
    parts = iid_partition(ds.y, n, seed=seed)
    clients = [(ds.x[i], ds.y[i]) for i in parts]
    rng = np.random.default_rng(seed)
    net = NetworkParams(
        p=jnp.full((n,), 1.0 / n),
        mu_c=jnp.asarray(rng.uniform(0.5, 3.0, n)),
        mu_d=jnp.asarray(rng.uniform(1.0, 5.0, n)),
        mu_u=jnp.asarray(rng.uniform(1.0, 5.0, n)))
    return clients, (test.x, test.y), net


def test_device_trainer_matches_host_statistics():
    """Fused-scan training run: queueing statistics agree with the host
    reference loop in distribution, the eval grid is complete and the
    staleness identity holds."""
    from repro.fl import AsyncFLConfig, AsyncFLTrainer, mlp_classifier

    clients, test, net = _tiny_fl_problem()
    m = 4
    # ~700 updates per run: one run's update count then fluctuates by
    # ~8% around Prop. 4's rate, well inside the 0.35 device-vs-host band
    horizon = 480.0
    kw = dict(eta=0.05, batch_size=16, eval_every_time=120.0, seed=0)
    model = mlp_classifier(28 * 28, 4, hidden=(16,))
    dev = AsyncFLTrainer(model, clients, net, m,
                         config=AsyncFLConfig(backend="device", **kw),
                         test_data=test)
    dlog = dev.run(horizon_time=horizon)
    host = AsyncFLTrainer(model, clients, net, m,
                          config=AsyncFLConfig(backend="host", **kw),
                          test_data=test)
    hlog = host.run(horizon_time=horizon)

    # same eval grid shape: 0, 120, ..., 360 < t_end plus the final point
    assert dlog.times == hlog.times
    assert dlog.updates[-1] == pytest.approx(hlog.updates[-1], rel=0.35)
    assert np.isfinite(dlog.losses).all()
    # update counters at grid times are non-decreasing and end at the total
    assert all(a <= b for a, b in zip(dlog.updates, dlog.updates[1:]))
    p = np.asarray(net.p)
    staleness = float(np.sum(p * dlog.mean_delay))
    assert abs(staleness - (m - 1)) < 1.0
    np.testing.assert_allclose(dlog.throughput, hlog.throughput, rtol=0.35)
    assert dlog.accuracies[-1] > 0.4   # learns well above 1/4 chance


@pytest.mark.slow
def test_device_trainer_multiseed_close_to_host_mean():
    """Multi-seed Monte-Carlo: seed-averaged device throughput and staleness
    match the host loop tightly (slow tier: many full runs)."""
    from repro.fl import AsyncFLConfig, AsyncFLTrainer, mlp_classifier

    clients, test, net = _tiny_fl_problem(seed=2)
    m, horizon = 4, 200.0
    kw = dict(eta=0.05, batch_size=16, eval_every_time=100.0)
    model = mlp_classifier(28 * 28, 4, hidden=(16,))
    dev = AsyncFLTrainer(model, clients, net, m,
                         config=AsyncFLConfig(backend="device", **kw),
                         test_data=test)
    dlogs = dev.run_seeds(horizon, seeds=range(8))
    thr_dev = np.mean([l.throughput for l in dlogs])
    host_thr = []
    for seed in range(4):
        h = AsyncFLTrainer(model, clients, net, m,
                           config=AsyncFLConfig(backend="host", seed=seed,
                                                **kw),
                           test_data=test)
        host_thr.append(h.run(horizon_time=horizon).throughput)
    np.testing.assert_allclose(thr_dev, np.mean(host_thr), rtol=0.10)
    for l in dlogs:
        assert abs(float(np.sum(np.asarray(net.p) * l.mean_delay)) - (m - 1)) < 1.0


def test_device_trainer_max_updates_binds():
    from repro.fl import AsyncFLConfig, AsyncFLTrainer, mlp_classifier

    clients, test, net = _tiny_fl_problem(seed=1)
    model = mlp_classifier(28 * 28, 4, hidden=(16,))
    tr = AsyncFLTrainer(model, clients, net, 3,
                        config=AsyncFLConfig(backend="device", eta=0.05,
                                             batch_size=16,
                                             eval_every_time=1e9),
                        test_data=test)
    log = tr.run(horizon_time=1e9, max_updates=50)
    assert log.updates[-1] == 50
    assert int(np.sum(log.mean_delay >= 0)) == net.n
