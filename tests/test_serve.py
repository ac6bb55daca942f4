"""The serve subsystem: wire protocol, micro-batching, bitwise equality.

The server's contract is that it is a *transport*, not a different
engine: every response payload must be bitwise-equal to the
``encode_entry`` of a direct ``ScenarioSuite.run`` on the same scenario
and seeds — coalescing concurrent requests into spare lanes must never
change a bit.  The error contract is that every failure is a structured
``error`` event and the server keeps serving afterwards (no resident
program is poisoned by a bad request).
"""
import json
import os
import queue
import subprocess
import sys
import tempfile
import threading
import time
import types

import numpy as np
import pytest

from repro.core.complexity import LearningConstants
from repro.scenario import (DataSpec, LearningSpec, NetworkSpec, Scenario,
                            ScenarioSuite, StrategySpec)
from repro.serve.batcher import MicroBatcher
from repro.serve.client import ServeClient, ServeError
from repro.obs.metrics import Histogram, Metrics
from repro.serve.protocol import (MAX_M, WireError, encode_entry,
                                  parse_request)
from repro.serve.server import ServeConfig, Server

CONSTS = LearningConstants(L=1.0, delta=1.0, sigma=1.0, M=2.0, G=5.0,
                           eps=1.0)
DATA = dict(dataset="synthetic", num_classes=2, samples_per_class=6)
MODEL_SPEC = {"kind": "mlp", "input_dim": 28 * 28, "num_classes": 2,
              "hidden": [4]}
TRAIN_OPTS = dict(horizon_time=4.0, batch_size=4, eval_every_time=2.0)


def make_scenario(n, seed=0, m=2, data=True):
    """A small explicit-strategy scenario; ``seed`` varies the rates so
    each test gets distinct response-cache keys."""
    rng = np.random.default_rng(seed)
    return Scenario(
        network=NetworkSpec(mu_c=list(rng.uniform(1.0, 2.0, n)),
                            mu_d=[2.0] * n, mu_u=[2.0] * n),
        learning=LearningSpec(consts=CONSTS),
        strategy=StrategySpec("explicit", p=list(np.full(n, 1.0 / n)), m=m),
        data=DataSpec(**DATA) if data else None)


def direct_payload(scn, mode, seeds=(0,), **options):
    """What the server must produce, computed without the server."""
    if mode == "train":
        from repro.fl.models import mlp_classifier

        options = dict(options)
        spec = options.pop("model")
        options["model"] = mlp_classifier(spec["input_dim"],
                                          spec["num_classes"],
                                          hidden=tuple(spec["hidden"]))
    res = ScenarioSuite(scn, seeds=seeds).run(mode=mode, **options)
    (entry,) = res.entries.values()
    return encode_entry(mode, entry)


def bitwise_equal(a, b) -> bool:
    return json.dumps(a) == json.dumps(b)


# ---------------------------------------------------------------------------
# metrics (unit)
# ---------------------------------------------------------------------------

def test_histogram_percentiles_exact():
    h = Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100
    assert h.percentile(0.0) == 1.0
    assert h.percentile(1.0) == 100.0
    assert h.percentile(0.5) == 51.0  # nearest rank of 0.5*(n-1)
    s = h.summary()
    assert s["count"] == 100 and s["mean"] == pytest.approx(50.5)


def test_metrics_labels_and_snapshot():
    m = Metrics()
    m.inc("suite.requests", mode="analyze")
    m.inc("suite.requests", by=2, mode="analyze")
    m.observe("suite.lanes_per_dispatch", 4, mode="simulate")
    with m.timed("suite.dispatch", mode="simulate"):
        pass
    snap = m.snapshot()
    assert snap["counters"]["suite.requests{mode=analyze}"] == 3
    assert snap["latency"]["suite.lanes_per_dispatch{mode=simulate}"][
        "p50"] == 4
    assert m.counter("suite.requests", mode="analyze") == 3


def test_direct_suite_run_reports_metrics():
    """Satellite: direct (serverless) runs surface the same per-bucket
    counters the server exports."""
    suite = ScenarioSuite({"a": make_scenario(2, seed=40),
                           "b": make_scenario(3, seed=41)}, seeds=(0, 1))
    res = suite.run(mode="analyze")
    assert res.metrics is not None
    counters = res.metrics["counters"]
    assert counters["suite.requests{mode=analyze}"] == 2
    lanes = res.metrics["latency"]["suite.lanes_per_dispatch{mode=analyze}"]
    assert lanes["count"] >= 1
    assert "suite.run{mode=analyze}" in res.metrics["latency"]


# ---------------------------------------------------------------------------
# micro-batcher (unit — no jax, no sockets)
# ---------------------------------------------------------------------------

def _fake_req(bucket, seeds=(0,)):
    return types.SimpleNamespace(bucket=bucket, seeds=tuple(seeds))


def test_batcher_window_groups_by_bucket():
    q = queue.Queue()
    b = MicroBatcher(q, lambda r: r.bucket, max_wait=0.05, max_lanes=64)
    for r in (_fake_req("A"), _fake_req("B"), _fake_req("A")):
        q.put(r)
    window = b.next_window(timeout=1.0)
    assert len(window) == 3
    groups = b.group(window)
    assert [(err, [r.bucket for r in g]) for err, g in groups] == [
        (None, ["A", "A"]), (None, ["B"])]


def test_batcher_lane_budget_bounds_window():
    q = queue.Queue()
    b = MicroBatcher(q, lambda r: r.bucket, max_wait=5.0, max_lanes=4)
    for _ in range(4):
        q.put(_fake_req("A", seeds=(0, 1)))
    t0 = time.monotonic()
    window = b.next_window(timeout=1.0)
    # 2 requests x 2 seeds hit the 4-lane budget: no waiting out max_wait
    assert len(window) == 2
    assert time.monotonic() - t0 < 4.0


def test_batcher_key_errors_become_singletons():
    q = queue.Queue()

    def key(r):
        if r.bucket == "boom":
            raise WireError("ProtocolError", "bad bucket")
        return r.bucket

    b = MicroBatcher(q, key, max_wait=0.05, max_lanes=64)
    for r in (_fake_req("A"), _fake_req("boom"), _fake_req("A")):
        q.put(r)
    groups = b.group(b.next_window(timeout=1.0))
    assert len(groups) == 2
    errs = [err for err, _ in groups if err is not None]
    assert len(errs) == 1 and isinstance(errs[0], WireError)


# ---------------------------------------------------------------------------
# protocol validation (unit)
# ---------------------------------------------------------------------------

def _msg(**over):
    base = {"id": "r0", "verb": "run", "mode": "analyze",
            "scenario": make_scenario(2, seed=50).to_dict(),
            "seeds": [0], "options": {}}
    base.update(over)
    return base


def _etype(msg):
    with pytest.raises(WireError) as exc:
        parse_request(msg)
    return exc.value.etype


def test_parse_request_validation():
    assert _etype(_msg(id=None)) == "ProtocolError"
    assert _etype(_msg(mode="explode")) == "ProtocolError"
    assert _etype(_msg(scenario="nope")) == "ProtocolError"
    assert _etype(_msg(seeds=[])) == "ProtocolError"
    assert _etype(_msg(options={"volume": 11})) == "ProtocolError"
    # unknown strategy name surfaces the spec's eager validation error
    bad = make_scenario(2, seed=50).to_dict()
    bad["strategy"]["name"] = "zigzag"
    assert _etype(_msg(scenario=bad)) == "ValueError"
    # oversized m (explicit and requested) is refused at admission
    big = make_scenario(2, seed=50, m=MAX_M + 1).to_dict()
    assert _etype(_msg(scenario=big)) == "ProtocolError"
    sim = _msg(mode="simulate",
               options={"num_updates": 10, "m_max": MAX_M + 1})
    assert _etype(sim) == "ProtocolError"
    # train without a DataSpec cannot build client datasets server-side
    nodata = make_scenario(2, seed=50, data=False).to_dict()
    opts = dict(TRAIN_OPTS, model=MODEL_SPEC)
    assert _etype(_msg(mode="train", scenario=nodata,
                       options=opts)) == "ProtocolError"


# ---------------------------------------------------------------------------
# the live server
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    sock = str(tmp_path_factory.mktemp("serve") / "repro.sock")
    server = Server(ServeConfig(socket_path=sock, max_wait=0.25,
                                max_lanes=16))
    server.start()
    yield sock, server
    server.stop()


def test_analyze_bitwise_and_response_cache(served):
    sock, server = served
    scn = make_scenario(3, seed=1)
    with ServeClient(sock, timeout=120) as c:
        rid = c.submit(scn, mode="analyze")
        msg = c.collect(rid)
        assert msg["cached"] is False
        assert [e["event"] for e in c.events_for(rid)] == ["accepted",
                                                           "scheduled"]
        assert bitwise_equal(c.unwrap(msg), direct_payload(scn, "analyze"))
        # the repeat is answered at admission: no accepted/scheduled events
        rid2 = c.submit(scn, mode="analyze")
        msg2 = c.collect(rid2)
        assert msg2["cached"] is True
        assert c.events_for(rid2) == []
        assert bitwise_equal(c.unwrap(msg2), c.unwrap(msg))
    assert server.metrics.counter("serve.cache_hits", mode="analyze") >= 1


def test_concurrent_simulate_coalesced_and_bitwise(served):
    sock, _ = served
    scns = [make_scenario(3, seed=2), make_scenario(5, seed=3)]
    opts = dict(num_updates=60)
    with ServeClient(sock, timeout=300) as a, \
            ServeClient(sock, timeout=300) as b:
        # two *connections* submit into the same micro-batch window
        ra = a.submit(scns[0], mode="simulate", seeds=(0, 1), **opts)
        rb = b.submit(scns[1], mode="simulate", seeds=(0, 1), **opts)
        pa = a.unwrap(a.collect(ra))
        pb = b.unwrap(b.collect(rb))
        sched = [e for e in a.events_for(ra) if e["event"] == "scheduled"]
    # mixed populations (n=3, n=5) coalesced into ONE padded dispatch
    assert sched and sched[0]["requests"] == 2 and sched[0]["lanes"] == 4
    assert bitwise_equal(pa, direct_payload(scns[0], "simulate",
                                            seeds=(0, 1), **opts))
    assert bitwise_equal(pb, direct_payload(scns[1], "simulate",
                                            seeds=(0, 1), **opts))


def test_train_mixed_n_coalesced_and_bitwise(served):
    sock, _ = served
    scns = [make_scenario(2, seed=4), make_scenario(3, seed=5)]
    opts = dict(TRAIN_OPTS, model=MODEL_SPEC)
    with ServeClient(sock, timeout=600) as c:
        ids = [c.submit(s, mode="train", seeds=(0,), **opts) for s in scns]
        payloads = [c.unwrap(c.collect(i)) for i in ids]
        sched = [e for e in c.events_for(ids[0])
                 if e["event"] == "scheduled"]
    # the mixed-n train bucket: both populations share one lane program
    assert sched and sched[0]["requests"] == 2
    for scn, payload in zip(scns, payloads):
        assert bitwise_equal(payload,
                             direct_payload(scn, "train", **opts))


def test_errors_are_structured_and_server_keeps_serving(served):
    sock, _ = served
    with ServeClient(sock, timeout=120) as c:
        # malformed JSON
        c.send_raw(b'{"id": "oops", not json\n')
        msg = c.collect(None)  # unparseable line -> id is None
        assert msg["event"] == "error"
        assert msg["error"]["type"] == "ProtocolError"
        # unknown strategy name (spec validation, with the request id)
        bad = make_scenario(2, seed=6).to_dict()
        bad["strategy"]["name"] = "zigzag"
        c.send({"id": "r-bad", "verb": "run", "mode": "analyze",
                "scenario": bad, "seeds": [0], "options": {}})
        msg = c.collect("r-bad")
        assert msg["error"]["type"] == "ValueError"
        # unknown verb
        c.send({"id": "r-verb", "verb": "dance"})
        assert c.collect("r-verb")["error"]["type"] == "ProtocolError"
        # oversized m_max
        c.send({"id": "r-m", "verb": "run", "mode": "simulate",
                "scenario": make_scenario(2, seed=6).to_dict(),
                "seeds": [0],
                "options": {"num_updates": 10, "m_max": MAX_M + 1}})
        assert c.collect("r-m")["error"]["type"] == "ProtocolError"
        # ...and the SAME connection still gets bitwise-correct results
        scn = make_scenario(2, seed=7)
        assert bitwise_equal(c.run(scn, mode="analyze"),
                             direct_payload(scn, "analyze"))


def test_killed_inflight_request_does_not_poison_the_server(served):
    sock, _ = served
    scn = make_scenario(4, seed=8)
    killer = ServeClient(sock, timeout=120)
    killer.submit(scn, mode="simulate", num_updates=60)
    killer.close()  # walk away with the request in flight
    # the dispatch completes into a dead transport; the server, the
    # resident programs and the response cache all stay healthy:
    with ServeClient(sock, timeout=300) as c:
        assert bitwise_equal(
            c.run(scn, mode="simulate", num_updates=60),
            direct_payload(scn, "simulate", num_updates=60))
        assert c.stats()["counters"]


def test_stats_verb_reports_counters_and_latency(served):
    sock, _ = served
    with ServeClient(sock, timeout=120) as c:
        scn = make_scenario(2, seed=9)
        c.run(scn, mode="analyze")
        st = c.stats()
    assert st["uptime"] > 0
    assert st["response_cache_size"] >= 1
    assert st["counters"]["serve.requests{mode=analyze}"] >= 1
    lat = st["latency"]
    assert any(k.startswith("serve.request_latency") for k in lat)
    key = next(k for k in lat if k.startswith("serve.dispatch"))
    assert lat[key]["count"] >= 1 and lat[key]["p99"] >= lat[key]["p50"]


def test_shutdown_drains_then_refuses():
    with tempfile.TemporaryDirectory() as tmp:
        sock = os.path.join(tmp, "s.sock")
        server = Server(ServeConfig(socket_path=sock, max_wait=0.02))
        server.start()
        with ServeClient(sock, timeout=60) as c:
            scn = make_scenario(2, seed=10)
            c.run(scn, mode="analyze")
            assert c.shutdown() == "draining"
        server._stopped.wait(timeout=60)
        assert server._stopped.is_set()
        assert not os.path.exists(sock)


def test_draining_server_refuses_new_requests():
    with tempfile.TemporaryDirectory() as tmp:
        sock = os.path.join(tmp, "s.sock")
        server = Server(ServeConfig(socket_path=sock, max_wait=0.02))
        server.start()
        server._draining.set()  # drain announced, listener still up
        try:
            with ServeClient(sock, timeout=60) as c:
                rid = c.submit(make_scenario(2, seed=11), mode="analyze")
                msg = c.collect(rid)
                assert msg["error"]["type"] == "Unavailable"
        finally:
            server._draining.clear()
            server.stop()


# ---------------------------------------------------------------------------
# warm restart: the persistent compilation cache
# ---------------------------------------------------------------------------

_RESTART_SCRIPT = r"""
import json, sys, tempfile, os
import numpy as np
from repro.serve.xla_cache import enable_persistent_cache
enable_persistent_cache()
from repro.analysis import tracecheck
from repro.serve.server import Server, ServeConfig
from repro.serve.client import ServeClient
from repro.scenario import (Scenario, NetworkSpec, LearningSpec,
                            StrategySpec, DataSpec)
from repro.core.complexity import LearningConstants

scn = Scenario(
    network=NetworkSpec(mu_c=[1.0, 1.5, 2.0], mu_d=[2.0] * 3,
                        mu_u=[2.0] * 3),
    learning=LearningSpec(consts=LearningConstants(
        L=1.0, delta=1.0, sigma=1.0, M=2.0, G=5.0, eps=1.0)),
    strategy=StrategySpec("explicit", p=[1 / 3] * 3, m=2))
sock = tempfile.mktemp(suffix=".sock")
server = Server(ServeConfig(socket_path=sock, max_wait=0.02))
server.start()
with tracecheck.watch() as w:
    with ServeClient(sock, timeout=300) as c:
        c.run(scn, mode="analyze")
        c.run(scn, mode="simulate", num_updates=40)
server.stop()
print(json.dumps({"compiles": w.compiles, "cache_hits": w.cache_hits,
                  "fresh": w.fresh_compiles}))
"""


def test_restarted_server_first_request_pays_zero_fresh_compiles(tmp_path):
    """Satellite: two boots of the server process against one
    ``JAX_COMPILATION_CACHE_DIR`` — the second boot's first requests
    deserialize every program from disk (zero *fresh* XLA compiles)."""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])

    def boot():
        out = subprocess.run([sys.executable, "-c", _RESTART_SCRIPT],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = boot()
    assert cold["fresh"] > 0  # first boot really compiled
    warm = boot()
    assert warm["compiles"] > 0
    assert warm["fresh"] == 0, warm  # restart: everything from disk


@pytest.mark.parametrize("from_env", (True, False))
def test_compile_cache_dir_is_env_or_fixed_in_checkout(monkeypatch, tmp_path,
                                                       from_env):
    """``$JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache is
    the one fixed ``<repo>/.jax_cache``, the same path in every process."""
    from repro.serve import xla_cache

    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
        assert xla_cache.default_cache_dir() == str(tmp_path / "xla")
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert xla_cache.default_cache_dir() == os.path.join(root,
                                                             ".jax_cache")
