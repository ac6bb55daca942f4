"""Client-class aggregation: O(#classes) forms vs the expanded oracle.

The contract (``repro.core.buzen.ClassParams`` / ``repro.core.batched``
class forms / the class-aggregated event engine in ``repro.core.events``):

  * closed forms agree with the padded per-client forms evaluated on
    ``classes.expand()`` to f64 roundoff (the DP fold order differs, so
    the two representations are not bitwise against each other);
  * everything is **bitwise** invariant to class padding
    (``pad_classes`` count-0 classes), mirroring the traced-``n``
    convention of ``tests/test_padded_n.py``;
  * the class event engine matches the expanded per-client engine
    distributionally (the PRNG key-split trees differ, so trajectories
    are not comparable draw-by-draw);
  * the Scenario layer round-trips ``ClassSpec`` and plans class suites
    against the same numbers as the expanded per-client suites.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched import (energy_complexity_classes,
                                energy_complexity_padded,
                                expected_relative_delay_classes,
                                expected_relative_delay_padded,
                                round_complexity_classes,
                                round_complexity_padded, throughput_padded,
                                wallclock_time_classes)
from repro.core.buzen import (ClassParams, class_log_normalizing_constants,
                              classes_from_network,
                              log_normalizing_constants, pad_classes)
from repro.core.complexity import LearningConstants
from repro.core.energy import PowerProfile
from repro.core.events import (expand_class_stats, simulate_stats,
                               simulate_stats_classes)

CONSTS = LearningConstants(L=1.0, delta=1.0, sigma=1.0, M=2.0, G=5.0,
                           eps=0.5)


def example_classes(with_cs=False, normalized=True):
    cls = ClassParams(
        p=jnp.asarray([0.05, 0.1, 0.025]),
        mu_c=jnp.asarray([1.0, 2.0, 3.0]),
        mu_d=jnp.asarray([6.0, 7.0, 8.0]),
        mu_u=jnp.asarray([6.0, 7.0, 8.0]),
        count=jnp.asarray([4, 2, 8]))
    if normalized:
        mass = float(jnp.sum(cls.count * cls.p))
        cls = cls._replace(p=cls.p / mass)
    return cls._replace(mu_cs=jnp.asarray(5.0)) if with_cs else cls


# ---------------------------------------------------------------------------
# closed forms vs the expanded per-client oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_cs", [False, True])
def test_class_log_Z_matches_expanded(with_cs):
    cls = example_classes(with_cs)
    prm = cls.expand()
    m_max = 10
    logZ_c = class_log_normalizing_constants(cls, m_max)
    logZ_p = log_normalizing_constants(prm, m_max)
    np.testing.assert_allclose(np.asarray(logZ_c), np.asarray(logZ_p),
                               rtol=1e-12)


@pytest.mark.parametrize("count", [0, 1, 3, 700_000])
def test_negbinom_log_weights_match_lgamma(count):
    """The class series' ``log C(j + count - 1, j)`` running sum equals the
    lgamma form; ``count = 1`` is exactly the geometric series and
    ``count = 0`` the convolution identity."""
    from jax.scipy.special import gammaln

    from repro.core.buzen import _log_multisets

    m_max = 64
    got = np.asarray(_log_multisets(count, m_max))
    assert got[0] == 0.0
    if count == 0:
        assert np.all(np.isneginf(got[1:]))
        return
    j = np.arange(m_max + 1, dtype=np.float64)
    want = np.asarray(gammaln(j + count) - gammaln(j + 1.0)
                      - gammaln(float(count)))
    # the lgamma form cancels terms as large as lgamma(count + m_max): its
    # own error is a few ulps of that
    scale = float(gammaln(float(count + m_max)))
    np.testing.assert_allclose(got, want, rtol=1e-12,
                               atol=16 * np.finfo(np.float64).eps * scale)
    if count == 1:
        assert np.all(got == 0.0)


@pytest.mark.parametrize("with_cs", [False, True])
def test_class_closed_forms_match_expanded(with_cs):
    cls = example_classes(with_cs)
    prm = cls.expand()
    m_max = 10
    m = jnp.asarray(6)
    logZ_c = class_log_normalizing_constants(cls, m_max)
    logZ_p = log_normalizing_constants(prm, m_max)

    np.testing.assert_allclose(
        float(throughput_padded(logZ_c, m)),
        float(throughput_padded(logZ_p, m)), rtol=1e-12)

    # per-class delays repeat across each class's members
    d_c = np.asarray(expected_relative_delay_classes(cls, m, logZ_c, m_max))
    d_p = np.asarray(expected_relative_delay_padded(prm, m, logZ_p, m_max))
    np.testing.assert_allclose(np.repeat(d_c, np.asarray(cls.count)), d_p,
                               rtol=1e-10)

    np.testing.assert_allclose(
        float(round_complexity_classes(cls, m, CONSTS, logZ_c, m_max)),
        float(round_complexity_padded(prm, m, CONSTS, logZ_p, m_max)),
        rtol=1e-10)

    np.testing.assert_allclose(
        float(wallclock_time_classes(cls, m, CONSTS, logZ_c, m_max)),
        float(round_complexity_padded(prm, m, CONSTS, logZ_p, m_max)
              / throughput_padded(logZ_p, m)), rtol=1e-10)


def test_class_energy_matches_expanded():
    cls = example_classes()
    prm = cls.expand()
    m_max = 10
    m = jnp.asarray(5)
    pw_c = PowerProfile(P_c=jnp.asarray([2.0, 3.0, 4.0]),
                        P_u=jnp.asarray([0.5, 0.6, 0.7]),
                        P_d=jnp.asarray([0.3, 0.4, 0.5]))
    cnt = np.asarray(cls.count)
    pw_p = PowerProfile(P_c=jnp.asarray(np.repeat(pw_c.P_c, cnt)),
                        P_u=jnp.asarray(np.repeat(pw_c.P_u, cnt)),
                        P_d=jnp.asarray(np.repeat(pw_c.P_d, cnt)))
    logZ_c = class_log_normalizing_constants(cls, m_max)
    logZ_p = log_normalizing_constants(prm, m_max)
    np.testing.assert_allclose(
        float(energy_complexity_classes(cls, m, CONSTS, pw_c, logZ_c,
                                        m_max)),
        float(energy_complexity_padded(prm, m, CONSTS, pw_p, logZ_p,
                                       m_max)), rtol=1e-10)


@pytest.mark.parametrize("with_cs", [False, True])
def test_class_forms_bitwise_invariant_to_padding(with_cs):
    cls = example_classes(with_cs)
    pad = pad_classes(cls, 6)
    m_max = 10
    m = jnp.asarray(6)
    logZ = class_log_normalizing_constants(cls, m_max)
    logZ_pad = class_log_normalizing_constants(pad, m_max)
    np.testing.assert_array_equal(np.asarray(logZ), np.asarray(logZ_pad))
    a = round_complexity_classes(cls, m, CONSTS, logZ, m_max)
    b = round_complexity_classes(pad, m, CONSTS, logZ_pad, m_max)
    assert float(a) == float(b)
    d = expected_relative_delay_classes(pad, m, logZ_pad, m_max)
    np.testing.assert_array_equal(
        np.asarray(d)[:cls.C],
        np.asarray(expected_relative_delay_classes(cls, m, logZ, m_max)))


def test_classes_from_network_round_trip():
    cls = example_classes()
    prm = cls.expand()
    back = classes_from_network(prm)
    # expanding the recovered classes reproduces the per-client arrays
    re = back.expand()
    for f in ("p", "mu_c", "mu_d", "mu_u"):
        np.testing.assert_array_equal(np.asarray(getattr(re, f)),
                                      np.asarray(getattr(prm, f)))


# ---------------------------------------------------------------------------
# class-aggregated event engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_cs", [False, True])
def test_class_events_bitwise_invariant_to_class_padding(with_cs):
    cls = example_classes(with_cs)
    pad = pad_classes(cls, 5)
    m, nu, wu = 5, 300, 100
    a = simulate_stats_classes(cls, m, nu, warmup=wu, seed=0)
    b = simulate_stats_classes(pad, m, nu, warmup=wu, seed=0)
    C = cls.C
    for f in ("updates", "time", "throughput", "energy"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    np.testing.assert_array_equal(np.asarray(a.mean_delay),
                                  np.asarray(b.mean_delay)[:C])
    np.testing.assert_array_equal(np.asarray(a.delay_counts),
                                  np.asarray(b.delay_counts)[:C])
    # occupancy: [3C+1] segments; padded classes contribute empty segments
    occ_a = np.asarray(a.mean_queue_counts)
    occ_b = np.asarray(b.mean_queue_counts)
    Cp = pad.C
    for s in range(3):
        np.testing.assert_array_equal(occ_a[s * C:(s + 1) * C],
                                      occ_b[s * Cp:s * Cp + C])
    np.testing.assert_array_equal(occ_a[-1], occ_b[-1])


def test_class_events_match_expanded_distributionally():
    cls = example_classes()
    prm = cls.expand()
    m, nu, wu = 6, 2500, 500
    st_c = simulate_stats_classes(cls, m, nu, warmup=wu, seed=0)
    st_p = simulate_stats(prm, m, nu, warmup=wu, seed=1)
    thr_c = float(st_c.throughput)
    thr_p = float(st_p.throughput)
    assert abs(thr_c - thr_p) / thr_p < 0.1
    # per-class mean delays vs the class-averaged expanded ones
    d_p = np.asarray(st_p.mean_delay)
    cnt = np.asarray(cls.count)
    edges = np.concatenate([[0], np.cumsum(cnt)])
    d_p_cls = np.asarray([d_p[edges[i]:edges[i + 1]].mean()
                          for i in range(cls.C)])
    np.testing.assert_allclose(np.asarray(st_c.mean_delay), d_p_cls,
                               rtol=0.25)


def test_class_events_staleness_identity():
    # Eq. 7 in class space: sum_c massfrac_c E0[R_c] = m - 1
    cls = example_classes()
    m = 8
    st = simulate_stats_classes(cls, m, 4000, warmup=500, seed=0)
    mass = np.asarray(cls.mass)
    frac = mass / mass.sum()
    stale = float(np.sum(frac * np.asarray(st.mean_delay)))
    assert abs(stale - (m - 1)) / (m - 1) < 0.05


def test_expand_class_stats_shapes_and_weights():
    cls = example_classes()
    st = simulate_stats_classes(cls, 5, 300, warmup=100, seed=0)
    ex = expand_class_stats(st, cls.count)
    n = int(np.asarray(cls.count).sum())
    assert ex.mean_delay.shape == (n,)
    assert ex.mean_queue_counts.shape == (3 * n + 1,)
    # class means repeat across members
    cnt = np.asarray(cls.count)
    np.testing.assert_array_equal(
        np.asarray(ex.mean_delay),
        np.repeat(np.asarray(st.mean_delay), cnt))
    # per-member delay counts average the class total
    np.testing.assert_allclose(
        np.asarray(ex.delay_counts),
        np.repeat(np.asarray(st.delay_counts) / cnt, cnt))


def test_class_events_power_accounting():
    cls = example_classes()
    pw = PowerProfile(P_c=jnp.asarray([2.0, 3.0, 4.0]),
                      P_u=jnp.asarray([0.5, 0.6, 0.7]),
                      P_d=jnp.asarray([0.3, 0.4, 0.5]))
    st = simulate_stats_classes(cls, 5, 300, warmup=100, seed=0, power=pw)
    assert float(st.energy) > 0.0


# ---------------------------------------------------------------------------
# kernel backend (interpret mode off-TPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_cs", [False, True])
def test_class_pallas_kernel_matches_jnp(with_cs):
    cls = example_classes(with_cs)
    m_max = 10
    ref = np.asarray(class_log_normalizing_constants(cls, m_max,
                                                     backend="jnp"))
    pal = np.asarray(class_log_normalizing_constants(cls, m_max,
                                                     backend="pallas"))
    np.testing.assert_allclose(pal, ref, rtol=2e-5, atol=2e-5)


def test_class_pallas_kernel_bitwise_class_padding():
    cls = example_classes()
    m_max = 10
    a = np.asarray(class_log_normalizing_constants(cls, m_max,
                                                   backend="pallas"))
    b = np.asarray(class_log_normalizing_constants(pad_classes(cls, 6),
                                                   m_max, backend="pallas"))
    np.testing.assert_array_equal(a, b)


def test_class_pallas_gradients_match_jnp():
    from repro.core.batched import batch_class_log_normalizing_constants

    cls = example_classes()
    ps = jnp.stack([cls.p, cls.p * jnp.asarray([1.2, 0.9, 0.95])])

    def total(p, backend):
        return batch_class_log_normalizing_constants(cls, p, 8,
                                                     backend=backend).sum()

    g_p = jax.grad(lambda p: total(p, "pallas"))(ps)
    g_j = jax.grad(lambda p: total(p, "jnp"))(ps)
    assert bool(jnp.all(jnp.isfinite(g_p)))
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_j), rtol=1e-10)


# ---------------------------------------------------------------------------
# Scenario layer: ClassSpec round-trip + class suite planning
# ---------------------------------------------------------------------------

def _cluster_rows():
    from repro.scenario.spec import ClusterSpec

    return (ClusterSpec("A", 1.0, 6.0, 6.0, 4),
            ClusterSpec("B", 2.0, 7.0, 7.0, 2),
            ClusterSpec("C", 3.0, 8.0, 8.0, 6))


def test_classspec_json_round_trip_and_hash_stability():
    from repro.scenario import NetworkSpec, Scenario
    from repro.scenario.spec import LearningSpec

    net = NetworkSpec.from_clusters(_cluster_rows(), aggregate=True)
    scn = Scenario(network=net, learning=LearningSpec())
    again = Scenario.from_json(scn.to_json())
    assert again.hash() == scn.hash()
    assert again.network.classes.C == net.classes.C
    np.testing.assert_array_equal(again.network.classes.count,
                                  net.classes.count)
    # per-client scenarios don't grow a "classes" key (hash stability)
    plain = Scenario(network=NetworkSpec.from_clusters(_cluster_rows()),
                     learning=LearningSpec())
    assert "classes" not in plain.to_dict()["network"]


def test_aggregate_expands_to_per_client_network():
    from repro.scenario import NetworkSpec

    agg = NetworkSpec.from_clusters(_cluster_rows(), aggregate=True)
    plain = NetworkSpec.from_clusters(_cluster_rows())
    assert agg.n == plain.n
    pa, pp = agg.params(), plain.params()
    for f in ("p", "mu_c", "mu_d", "mu_u"):
        np.testing.assert_array_equal(np.asarray(getattr(pa, f)),
                                      np.asarray(getattr(pp, f)))


def test_class_suite_analyze_matches_expanded_suite():
    from repro.scenario import NetworkSpec, Scenario, ScenarioSuite
    from repro.scenario.spec import LearningSpec

    net_c = NetworkSpec.from_clusters(_cluster_rows(), aggregate=True)
    net_p = NetworkSpec.from_clusters(_cluster_rows())
    base_c = Scenario(network=net_c, learning=LearningSpec())
    base_p = Scenario(network=net_p, learning=LearningSpec())
    sc = ScenarioSuite({
        "a": base_c.with_strategy("asyncsgd", m=6),
        "t": base_c.with_strategy("time_opt", m_max=16)})
    sp = ScenarioSuite({
        "a": base_p.with_strategy("asyncsgd", m=6),
        "t": base_p.with_strategy("time_opt", m_max=16)})
    rc = sc.run(mode="analyze")
    rp = sp.run(mode="analyze")
    assert rc.programs == 1  # both class scenarios share one bucket
    for k in ("a", "t"):
        np.testing.assert_allclose(rc.entries[k]["throughput"],
                                   rp.entries[k]["throughput"], rtol=1e-9)
        np.testing.assert_allclose(rc.entries[k]["K_eps"],
                                   rp.entries[k]["K_eps"], rtol=1e-4)
    # the class-space optimizer lands on the per-client optimum
    assert rc.strategies["t"][1] == rp.strategies["t"][1]
    # asyncsgd class delays repeat to the per-client ones
    np.testing.assert_allclose(
        np.repeat(rc.entries["a"]["delays"], [4, 2, 6]),
        rp.entries["a"]["delays"], rtol=1e-9)


def test_class_suite_simulate_runs_and_unpads_to_classes():
    from repro.scenario import NetworkSpec, Scenario, ScenarioSuite
    from repro.scenario.spec import LearningSpec

    net = NetworkSpec.from_clusters(_cluster_rows(), aggregate=True)
    base = Scenario(network=net, learning=LearningSpec())
    suite = ScenarioSuite(base.with_strategy("asyncsgd", m=5), seeds=(0, 1))
    res = suite.run(mode="simulate", num_updates=300, warmup=100)
    (stats_list,) = res.entries.values()
    assert len(stats_list) == 2
    assert stats_list[0].mean_delay.shape == (net.classes.C,)


def test_class_strategy_guards():
    from repro.scenario import NetworkSpec, Scenario
    from repro.scenario.spec import LearningSpec
    from repro.scenario.suite import resolve_strategy

    net = NetworkSpec.from_clusters(_cluster_rows(), aggregate=True)
    base = Scenario(network=net, learning=LearningSpec())
    with pytest.raises(ValueError, match="m_max"):
        resolve_strategy(base.with_strategy("time_opt"))
    with pytest.raises(ValueError, match="class-space resolver"):
        resolve_strategy(base.with_strategy("round_opt"))
