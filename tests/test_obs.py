"""The observability subsystem (``repro.obs``).

Contracts under test:

* **bitwise non-invasive** — with telemetry rings on, simulation
  statistics and training trajectories are bit-identical to rings off,
  on every sim backend (the traced scan is a separate program; the
  untraced one is untouched);
* ring wraparound keeps exactly the most recent records, in order;
* the Perfetto exporter emits the golden schema pinned by
  ``tests/data/trace_schema.json`` and a consistent span decomposition;
* the drift monitor accepts a healthy smoke-scale run, flags a
  corrupted ring, and restricts itself to conservation off the
  product-form domain;
* the serve layer exposes the shared registry (``metrics`` verb) and a
  drift summary (``stats``);
* the registry's spans nest as the planner and the optimizer run them
  (``suite.resolve`` / ``pack`` / ``dispatch`` / ``unpack`` inside
  ``suite.run``; ``optimize.lower`` / ``compile`` / ``run`` inside
  ``suite.resolve``), and each is also a profiler annotation.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.buzen import NetworkParams
from repro.obs.rings import (EventRing, decode, decode_lane,
                             event_ring_append, event_ring_init,
                             update_ring_append, update_ring_init)

DATA_DIR = os.path.join(os.path.dirname(__file__), "data")


def _net(n=3, seed=0):
    rng = np.random.default_rng(seed)
    return NetworkParams(
        p=jnp.asarray(rng.dirichlet(np.ones(n))),
        mu_c=jnp.asarray(rng.uniform(0.5, 4.0, n)),
        mu_d=jnp.asarray(rng.uniform(2.0, 6.0, n)),
        mu_u=jnp.asarray(rng.uniform(2.0, 6.0, n)))


def _tree_bitwise_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# rings (unit)
# ---------------------------------------------------------------------------

def _append_n(ring, k, t0=0.0):
    for i in range(k):
        ring = event_ring_append(
            ring, time=t0 + i, station=i % 5, station_to=(i + 1) % 5,
            kind=i % 4, slot=i % 3, client=i % 2, delay=i, update=i % 2)
    return ring


def test_event_ring_wraparound_keeps_latest_in_order():
    ring = _append_n(event_ring_init(8), 12)
    dec = decode(ring)
    assert dec["count"] == 12 and dec["capacity"] == 8
    assert dec["dropped"] == 4
    np.testing.assert_array_equal(dec["time"], np.arange(4.0, 12.0))
    np.testing.assert_array_equal(dec["delay"], np.arange(4, 12))


def test_event_ring_not_full_decodes_prefix():
    dec = decode(_append_n(event_ring_init(8), 5))
    assert dec["count"] == 5 and dec["dropped"] == 0
    np.testing.assert_array_equal(dec["time"], np.arange(5.0))


def test_event_ring_capacity_zero_is_static_noop():
    ring = event_ring_init(0)
    out = _append_n(ring, 3)
    assert out is ring  # the append is DCE'd before jax ever runs
    dec = decode(ring)
    assert dec["count"] == 0 and dec["capacity"] == 0
    assert dec["time"].shape == (0,)


def test_ring_append_valid_gate_blocks_record_and_count():
    ring = event_ring_init(4)
    ring = event_ring_append(ring, time=1.0, station=0, station_to=1,
                             kind=0, slot=0, client=0, delay=0, update=1,
                             valid=jnp.asarray(False))
    assert int(ring.count) == 0
    ring = event_ring_append(ring, time=2.0, station=0, station_to=1,
                             kind=0, slot=0, client=0, delay=0, update=1,
                             valid=jnp.asarray(True))
    dec = decode(ring)
    assert dec["count"] == 1
    np.testing.assert_array_equal(dec["time"], [2.0])


def test_update_ring_roundtrip_dtypes():
    ring = update_ring_init(4)
    ring = update_ring_append(ring, time=1.5, client=2, staleness=3,
                              grad_norm=0.25, snapshot_age=0.5)
    dec = decode(ring)
    assert dec["time"].dtype == np.float64
    assert dec["staleness"].dtype == np.int32
    np.testing.assert_allclose(dec["grad_norm"], [0.25])


def test_ring_append_inside_jit_and_decode_lane():
    @jax.jit
    def fill(_):
        ring = event_ring_init(4)
        for i in range(3):
            ring = event_ring_append(
                ring, time=float(i), station=i, station_to=i + 1, kind=0,
                slot=i, client=i, delay=i, update=0)
        return ring

    stacked = jax.vmap(fill)(jnp.arange(2))
    dec = decode_lane(stacked, 1)
    assert dec["count"] == 3
    np.testing.assert_array_equal(dec["slot"], [0, 1, 2])


# ---------------------------------------------------------------------------
# bitwise non-invasiveness (the padding-contract-style property)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "batched", "pallas",
                                     "sharded"])
def test_simulate_stats_bitwise_with_rings_on(backend):
    from repro.sim.batched_events import simulate_stats_lanes

    params = [_net(3, seed=1), _net(3, seed=2)]
    kw = dict(warmup=20, m_max=3, backend=backend,
              interpret=True if backend == "pallas" else None)
    base = simulate_stats_lanes(params, [2, 3], 150, **kw)
    traced, rings = simulate_stats_lanes(params, [2, 3], 150,
                                         trace_events=256, **kw)
    assert _tree_bitwise_equal(base, traced)
    for lane in range(2):
        dec = decode_lane(rings, lane)
        assert dec["count"] > 0
        assert np.all(np.diff(dec["time"]) >= 0)  # chronological


def test_trainer_bitwise_with_update_ring_on():
    from repro.fl.engine import DeviceTrainer
    from repro.fl.models import mlp_classifier
    from repro.fl.trainer import AsyncFLConfig

    rng = np.random.default_rng(5)
    n = 3
    net = _net(n, seed=5)
    clients = [(rng.normal(size=(6, 4)).astype(np.float32),
                rng.integers(0, 2, size=6).astype(np.int32))
               for _ in range(n)]
    test = (rng.normal(size=(8, 4)).astype(np.float32),
            rng.integers(0, 2, size=8).astype(np.int32))
    model = mlp_classifier(4, 2, hidden=(4,))
    cfg = AsyncFLConfig(eta=0.05, batch_size=2, eval_every_time=2.0)

    def run(trace_updates):
        tr = DeviceTrainer(model, clients, net, cfg, test_data=test,
                           trace_updates=trace_updates)
        ps = jnp.stack([jnp.asarray(net.p)] * 2)
        logs, _ = tr.run_lanes(ps, [2, 2], [0.05, 0.05], [0, 1], 8.0)
        return logs, tr.last_update_rings

    base_logs, base_rings = run(0)
    traced_logs, rings = run(128)
    assert base_rings is None and rings is not None
    assert len(base_logs) == len(traced_logs)
    for a, b in zip(base_logs, traced_logs):  # TrainLog is not a pytree
        for field in a.__dataclass_fields__:
            assert _tree_bitwise_equal(getattr(a, field),
                                       getattr(b, field)), field
    dec = decode(rings[0])
    assert dec["count"] > 0
    assert np.all(dec["staleness"] >= 0)
    assert np.all(dec["grad_norm"] > 0)
    assert np.all(dec["snapshot_age"] >= 0)


def test_suite_simulate_traced_bitwise_and_cache_roundtrip():
    from repro.scenario import (NetworkSpec, Scenario, ScenarioSuite,
                                SimSpec, TraceSpec)

    rng = np.random.default_rng(7)
    n = 3
    net = NetworkSpec(mu_c=list(rng.uniform(0.8, 1.2, n)),
                      mu_d=[4.0] * n, mu_u=[4.0] * n)
    plain = Scenario(network=net, name="s")
    traced = Scenario(network=net, name="s",
                      sim=SimSpec(trace=TraceSpec(events=1024)))
    r0 = ScenarioSuite({"s": plain}, seeds=(0, 1)).run(
        mode="simulate", num_updates=300, warmup=30)
    suite = ScenarioSuite({"s": traced}, seeds=(0, 1))
    r1 = suite.run(mode="simulate", num_updates=300, warmup=30)
    assert r0.traces is None and r0.drift is None
    assert _tree_bitwise_equal(r0.entries["s"], r1.entries["s"])
    assert len(r1.traces["s"]) == 2 and len(r1.drift["s"]) == 2
    assert all(r["ok"] for r in r1.drift["s"])
    # cache hit must round-trip traces and drift too
    r2 = suite.run(mode="simulate", num_updates=300, warmup=30)
    assert r2.cache_hits == 1
    assert _tree_bitwise_equal(r1.traces["s"], r2.traces["s"])
    assert r2.drift["s"] == r1.drift["s"]


def test_suite_traces_class_network():
    """Class rings (per-class station indexing) through ScenarioSuite.run:
    traced class lanes return stats bitwise equal to the untraced run,
    decoded rings, and drift reports whose delay predictions are folded
    onto the class axis."""
    from repro.scenario import (ClassSpec, NetworkSpec, Scenario,
                                ScenarioSuite, SimSpec, StrategySpec,
                                TraceSpec)

    cls = ClassSpec(mu_c=[1.0, 2.0], mu_d=[4.0, 4.0], mu_u=[4.0, 4.0],
                    count=[3, 2])
    scn = Scenario(
        network=NetworkSpec(classes=cls),
        strategy=StrategySpec("explicit", p=[0.1, 0.1], m=2))
    traced = scn.replace(sim=SimSpec(trace=TraceSpec(events=2048)))
    r0 = ScenarioSuite({"c": scn}, seeds=(0, 1)).run(
        mode="simulate", num_updates=400, warmup=40)
    suite = ScenarioSuite({"c": traced}, seeds=(0, 1))
    r1 = suite.run(mode="simulate", num_updates=400, warmup=40)
    assert r0.traces is None and r0.drift is None
    assert _tree_bitwise_equal(r0.entries["c"], r1.entries["c"])
    assert len(r1.traces["c"]) == 2 and len(r1.drift["c"]) == 2
    C = 2
    for dec, rep in zip(r1.traces["c"], r1.drift["c"]):
        # the "client" channel carries the CLASS index in class lanes
        assert int(np.asarray(dec["client"]).max()) < C
        delays = [c for c in rep["checks"] if c["metric"] == "staleness"]
        assert delays and all(r["ok"] for r in rep["checks"]
                              if r["metric"] == "occupancy")
    # cache hit round-trips traces and drift
    r2 = suite.run(mode="simulate", num_updates=400, warmup=40)
    assert r2.cache_hits == 1
    assert _tree_bitwise_equal(r1.traces["c"], r2.traces["c"])
    assert r2.drift["c"] == r1.drift["c"]


def test_tracespec_roundtrip_and_hash_stability():
    from repro.scenario import (NetworkSpec, Scenario, SimSpec, TraceSpec)

    net = NetworkSpec(mu_c=[1.0, 2.0], mu_d=[3.0] * 2, mu_u=[3.0] * 2)
    plain = Scenario(network=net)
    traced = Scenario(network=net,
                      sim=SimSpec(trace=TraceSpec(events=64, updates=32,
                                                  tolerance=0.1)))
    # absent-when-unset: pre-obs hashes must not move
    assert "trace" not in SimSpec().to_dict()
    assert plain.hash() != traced.hash()
    rt = Scenario.from_dict(traced.to_dict())
    assert rt.hash() == traced.hash()
    assert rt.trace.events == 64 and rt.trace.updates == 32
    assert rt.trace.tolerance == 0.1
    with pytest.raises(ValueError):
        TraceSpec(events=-1)


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_lane():
    from repro.sim.batched_events import simulate_stats_lanes

    _, rings = simulate_stats_lanes([_net(3, seed=11)], [3], 200,
                                    warmup=20, trace_events=1024,
                                    backend="batched")
    return decode_lane(rings, 0)


def test_station_spans_partition_the_window(traced_lane):
    from repro.obs.trace import station_spans

    spans = station_spans(traced_lane)
    assert spans
    t1 = float(traced_lane["time"][-1])
    per_slot: dict = {}
    for s in spans:
        assert s["duration"] >= 0
        per_slot.setdefault(s["slot"], []).append(s)
    # per slot: contiguous coverage of [0, t1] (no ring wrap here)
    for slot, ss in per_slot.items():
        ss.sort(key=lambda s: s["start"])
        assert ss[0]["start"] == 0.0
        for a, b in zip(ss, ss[1:]):
            assert a["start"] + a["duration"] == pytest.approx(b["start"])
        last = ss[-1]
        assert last["start"] + last["duration"] == pytest.approx(t1)
    assert len(per_slot) == 3  # every in-flight slot shows up (m = 3)


def test_station_occupancy_sums_to_m(traced_lane):
    from repro.obs.trace import station_occupancy

    occ = station_occupancy(traced_lane, 3)
    assert occ.shape == (3 * 3 + 1,)
    assert float(occ.sum()) == pytest.approx(3.0, rel=1e-6)


def test_station_label_layout():
    from repro.obs.trace import station_label

    assert station_label(0, 3) == "down/0"
    assert station_label(4, 3) == "comp/1"
    assert station_label(8, 3) == "up/2"
    assert station_label(9, 3) == "cs"


_SCHEMA_TYPES = {"str": str, "int": int, "number": (int, float),
                 "bool": bool, "any": object}


def _check_schema(spec, value, path="doc"):
    if isinstance(spec, str):
        assert isinstance(value, _SCHEMA_TYPES[spec]), \
            f"{path}: {value!r} is not {spec}"
        if spec in ("int", "number"):
            assert not isinstance(value, bool), f"{path}: bool is not {spec}"
    elif isinstance(spec, list):
        assert isinstance(value, list), f"{path}: {type(value)} != list"
        for i, item in enumerate(value):
            _check_schema(spec[0], item, f"{path}[{i}]")
    elif isinstance(spec, dict):
        assert isinstance(value, dict), f"{path}: {type(value)} != dict"
        if "__each__" in spec:
            for k, v in value.items():
                _check_schema(spec["__each__"], v, f"{path}.{k}")
        else:
            missing = set(spec) - set(value)
            extra = set(value) - set(spec)
            assert not missing, f"{path}: missing keys {sorted(missing)}"
            assert not extra, f"{path}: extra keys {sorted(extra)}"
            for k in spec:
                _check_schema(spec[k], value[k], f"{path}.{k}")


def test_perfetto_trace_matches_golden_schema(traced_lane):
    from repro.obs.trace import perfetto_trace

    with open(os.path.join(DATA_DIR, "trace_schema.json")) as fh:
        golden = json.load(fh)
    doc = perfetto_trace(traced_lane, 3)
    _check_schema(golden, doc)
    json.dumps(doc)  # must serialize without a custom encoder
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert phases == {"M", "X", "i"}
    # updates are instants at their span's end
    upd = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert upd and all(e["name"] == "update" for e in upd)


def test_perfetto_trace_carries_host_and_compile_tracks(traced_lane):
    from repro.obs.trace import (PID_HOST, TID_COMPILES, TID_HOST_SPANS,
                                 perfetto_trace)

    host = [{"name": "suite.dispatch", "labels": {"mode": "simulate"},
             "start": 100.0, "duration": 0.5}]
    compiles = [("lanes", 100.8, 0.3)]
    doc = perfetto_trace(traced_lane, 3, host_spans=host,
                         compile_spans=compiles,
                         metadata={"extra": 1})
    rows = [e for e in doc["traceEvents"]
            if e["pid"] == PID_HOST and e["ph"] == "X"]
    tids = {e["tid"] for e in rows}
    assert tids == {TID_HOST_SPANS, TID_COMPILES}
    # both tracks rebased to the common earliest start (host at 100.0)
    assert min(e["ts"] for e in rows) == 0.0
    comp = next(e for e in rows if e["tid"] == TID_COMPILES)
    assert comp["ts"] == pytest.approx((100.8 - 0.3 - 100.0) * 1e6)
    assert doc["metadata"]["extra"] == 1


# ---------------------------------------------------------------------------
# drift monitors
# ---------------------------------------------------------------------------

def test_drift_report_accepts_healthy_run(traced_lane):
    from repro.obs.drift import drift_report

    rep = drift_report(traced_lane, params=_net(3, seed=11), m=3)
    assert rep["ok"], rep
    assert {c["metric"] for c in rep["checks"]} == {"throughput",
                                                    "staleness",
                                                    "occupancy"}
    occ = next(c for c in rep["checks"] if c["metric"] == "occupancy")
    assert occ["rel_err"] == pytest.approx(0.0, abs=1e-9)  # conservation


def test_drift_report_flags_corrupted_ring(traced_lane):
    from repro.obs.drift import drift_report

    bad = dict(traced_lane)
    bad["time"] = np.asarray(bad["time"]) * 3.0  # clock stretched 3x
    rep = drift_report(bad, params=_net(3, seed=11), m=3)
    assert not rep["ok"]
    thr = next(c for c in rep["checks"] if c["metric"] == "throughput")
    assert not thr["ok"] and thr["rel_err"] > 0.25


def test_drift_non_exponential_law_keeps_conservation_only(traced_lane):
    from repro.obs.drift import drift_report

    rep = drift_report(traced_lane, params=_net(3, seed=11), m=3,
                       law="lognormal")
    assert [c["metric"] for c in rep["checks"]] == ["occupancy"]
    assert rep["ok"]


def test_drift_report_needs_predictions_or_params():
    from repro.obs.drift import drift_report

    with pytest.raises(ValueError, match="predictions"):
        drift_report({"time": np.zeros(0)})


def test_predict_delays_profile_sums_to_m_minus_one():
    from repro.obs.drift import predict

    preds = predict(_net(4, seed=3), 5)
    # conservation identity: sum_i E0[D_i] = m - 1 for any timing law
    assert sum(preds["delays"]) == pytest.approx(4.0, rel=1e-9)
    assert preds["occupancy"] == 5.0


# ---------------------------------------------------------------------------
# metrics / serve integration
# ---------------------------------------------------------------------------

def test_prometheus_exposition_format():
    from repro.obs.metrics import Metrics

    m = Metrics()
    m.inc("serve.requests", mode="simulate")
    m.inc("serve.requests", mode="simulate")
    m.observe("suite.dispatch", 0.5, mode="simulate")
    text = m.exposition()
    lines = text.splitlines()
    assert "# TYPE serve_requests counter" in lines
    assert 'serve_requests{mode="simulate"} 2.0' in lines
    assert "# TYPE suite_dispatch summary" in lines
    assert any(l.startswith('suite_dispatch{mode="simulate",quantile="0.5"}')
               for l in lines)
    assert 'suite_dispatch_count{mode="simulate"} 1' in lines
    # every sample line is NAME{LABELS} VALUE or NAME VALUE
    for line in lines:
        if line.startswith("#") or not line:
            continue
        name, _, value = line.rpartition(" ")
        float(value)
        assert name and " " not in name.split("{")[0]


def test_metrics_records_spans_for_the_host_track():
    from repro.obs.metrics import Metrics

    m = Metrics()
    with m.timed("suite.plan", mode="simulate"):
        pass
    rows = m.spans()
    assert rows and rows[0]["name"] == "suite.plan"
    assert rows[0]["labels"] == {"mode": "simulate"}
    assert rows[0]["duration"] >= 0.0


def test_span_without_an_open_registry_records_nothing():
    from repro.obs.metrics import Metrics, span

    m = Metrics()
    with span("optimize.lower", stage="x"):
        pass
    assert m.spans() == [] and m.snapshot()["latency"] == {}


def test_span_records_into_the_innermost_open_registry():
    from repro.obs.metrics import Metrics, span

    outer, inner = Metrics(), Metrics()
    with outer.timed("suite.run", mode="analyze"):
        with span("optimize.lower"):
            pass
        with inner.timed("suite.resolve"):
            with span("optimize.compile"):
                pass
        with span("optimize.run"):
            pass
    with span("optimize.lower"):  # every block closed: recorded nowhere
        pass
    assert [s["name"] for s in outer.spans()] == [
        "optimize.lower", "optimize.run", "suite.run"]
    assert [s["name"] for s in inner.spans()] == [
        "optimize.compile", "suite.resolve"]
    assert outer.snapshot()["latency"]["optimize.run"]["count"] == 1


def _tiny_network(n=3, seed=21):
    from repro.scenario import NetworkSpec

    rng = np.random.default_rng(seed)
    return NetworkSpec(mu_c=list(rng.uniform(0.8, 2.0, n)),
                       mu_d=[4.0] * n, mu_u=[4.0] * n)


def _tiny_time_opt_suite():
    from repro.scenario import Scenario, ScenarioSuite, StrategySpec

    scn = Scenario(network=_tiny_network(),
                   strategy=StrategySpec("time_opt", m_max=4, steps=5),
                   name="opt")
    return ScenarioSuite(scn)


def _inside(inner, outer) -> bool:
    return (outer["start"] <= inner["start"]
            and inner["start"] + inner["duration"]
            <= outer["start"] + outer["duration"])


def _in_order_without_overlap(spans) -> bool:
    return all(a["start"] + a["duration"] <= b["start"]
               for a, b in zip(spans, spans[1:]))


def test_simulate_suite_records_planner_phases_in_order():
    from repro.scenario import Scenario, ScenarioSuite, StrategySpec

    net = _tiny_network()
    suite = ScenarioSuite(
        {f"m{m}": Scenario(network=net,
                           strategy=StrategySpec("explicit",
                                                 p=[1 / 3] * 3, m=m),
                           name=f"m{m}") for m in (2, 3)},
        seeds=(0, 1))
    suite.run(mode="simulate", num_updates=40, warmup=5)
    spans = suite.metrics.spans()
    (run,) = [s for s in spans if s["name"] == "suite.run"]
    assert run["labels"] == {"mode": "simulate"}
    phases = sorted((s for s in spans if s["name"] != "suite.run"),
                    key=lambda s: s["start"])
    # one bucket: the structure planner puts alike lanes in one program
    assert [s["name"] for s in phases] == [
        "suite.resolve", "suite.pack", "suite.dispatch", "suite.unpack"]
    assert all(_inside(s, run) for s in phases)
    assert _in_order_without_overlap(phases)
    assert all(s["labels"] == {"mode": "simulate"} for s in phases[1:])


def test_time_opt_records_optimizer_stages_inside_resolve():
    suite = _tiny_time_opt_suite()
    suite.run(mode="analyze")
    spans = suite.metrics.spans()
    resolve = [s for s in spans if s["name"] == "suite.resolve"]
    stages = sorted((s for s in spans if s["name"].startswith("optimize.")),
                    key=lambda s: s["start"])
    assert [s["name"] for s in stages] == [
        "optimize.lower", "optimize.compile", "optimize.run"]
    assert len(resolve) == 1 and all(_inside(s, resolve[0]) for s in stages)
    assert _in_order_without_overlap(stages)
    names = [s["name"] for s in sorted(spans, key=lambda s: s["start"])]
    assert names[names.index("suite.run"):] == [
        "suite.run", "suite.resolve", "optimize.lower", "optimize.compile",
        "optimize.run", "suite.pack", "suite.dispatch", "suite.unpack"]


def test_profiler_trace_holds_program_spans(tmp_path):
    import glob

    from jax.profiler import ProfileData

    suite = _tiny_time_opt_suite()
    with jax.profiler.trace(str(tmp_path)):
        suite.run(mode="analyze")
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {ev.name
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert {"suite.run", "suite.resolve", "suite.pack", "suite.dispatch",
            "suite.unpack", "optimize.lower", "optimize.compile",
            "optimize.run"} <= host


def test_server_metrics_verb_and_drift_stats(tmp_path):
    import time as _time

    from repro.scenario import (NetworkSpec, Scenario, SimSpec, TraceSpec)
    from repro.serve.client import ServeClient
    from repro.serve.server import ServeConfig, Server

    sock = str(tmp_path / "obs.sock")
    server = Server(ServeConfig(socket_path=sock, max_wait=0.05))
    server.start()
    try:
        _time.sleep(0.1)
        rng = np.random.default_rng(13)
        scn = Scenario(
            network=NetworkSpec(mu_c=list(rng.uniform(0.8, 1.2, 2)),
                                mu_d=[4.0] * 2, mu_u=[4.0] * 2),
            sim=SimSpec(trace=TraceSpec(events=512)))
        with ServeClient(sock, timeout=300) as c:
            c.run(scn, mode="simulate", seeds=(0,), num_updates=200,
                  warmup=20)
            st = c.stats()
            assert st["drift"]["checked"] == 1
            assert st["drift"]["breaches"] == 0
            assert st["drift"]["last"]["ok"] is True
            text = c.metrics()
        assert "# TYPE serve_requests counter" in text
        assert 'serve_requests{mode="simulate"} 1.0' in text
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# the CLI (smoke -> check -> report round-trip)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_obs_cli_roundtrip(tmp_path, capsys):
    from repro.obs.__main__ import main

    out = str(tmp_path / "trace.json")
    assert main(["smoke", "--out", out, "--updates", "600",
                 "--warmup", "60", "--seeds", "1"]) == 0
    assert main(["check", out]) == 0
    assert main(["report", out]) == 0
    doc = json.load(open(out))
    assert doc["metadata"]["ring_data"]
    assert all(r["ok"] for r in doc["metadata"]["drift"])
    # tamper with the embedded ring: check must re-verify, not trust
    doc["metadata"]["ring_data"]["time"] = [
        t * 3.0 for t in doc["metadata"]["ring_data"]["time"]]
    bad = str(tmp_path / "bad.json")
    json.dump(doc, open(bad, "w"))
    capsys.readouterr()
    assert main(["check", bad]) == 1
