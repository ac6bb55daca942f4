"""Drive analyze, simulate, train and serve once on one TPU chip.

The quickest proof that the system still starts on the chip: every phase
goes through the entry points a user calls (``Scenario`` ->
``ScenarioSuite.run`` and ``repro.serve.Server``) at the paper's size, and
checks what comes out against the repo's own references (closed forms,
the ``jnp`` Buzen DP, the unfused update, a direct suite run).

    python chip_smoke.py               # one chip: analyze, simulate, train, serve
    python chip_smoke.py --four-chips  # only the sharded simulate path on 4 chips

Run it from the root of a checkout; it adds ``src`` to the import path
itself.  It refuses to run anywhere JAX finds no TPU, and it never starts
a child process (one process owns the chip).  Each phase prints one line
``PHASE {...}`` with its checks, compile and wall seconds, compile and
persistent-cache-hit counts and the device; these lines are information,
not metrics.  The last line is ``{"ok": true, "device": {...}}``; any
failed check or exception exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

M_TABLE1 = 132            # Table-1 fleet concurrency (examples/paper_scale_sim.py)
# time_opt search bound of the Table-1 fleet (analyze and train share the
# sweep program): the default n + n // 4 = 125 costs B * m_max^2 per Adam
# step of f64 the TPU emulates; 48 still brackets the optimum (m = 33)
ANALYZE_M_MAX = 48
SIM_LANES = 8             # seed lanes of the one-chip simulate phase
SIM_UPDATES, SIM_WARMUP = 2000, 500
CLASS_M_MAX = 64          # README class fleet: n = 10^6, C = 3
TRAIN_UPDATES = 200       # updates each train lane applies
FOUR_CHIP_LANES = 14      # padded to 16 on a 4-device mesh

# tolerances, from tests/test_events.py and tests/test_classes.py
THR_RTOL = 0.05           # simulated vs Prop. 4 throughput
STALE_RTOL = 0.03         # sum_i p_i E0[R_i] vs m - 1 (per-client fleet)
CLASS_STALE_RTOL = 0.05   # the same identity in class space
# f32 Pallas vs f64 jnp DP: 101 f32 log-sum-exp folds of |log Z| <= 25 at
# n = 100, each off by up to ~1e-5 on the TPU's f32 exp/log, bound the
# throughput and wall-clock drift by ~2e-3 (an O(1) error means a wrong DP)
BUZEN_RTOL = 2e-3
FUSED_RTOL, FUSED_ATOL = 1e-3, 1e-5   # f32 fused vs unfused update


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def rel(a: float, b: float) -> float:
    return abs(float(a) - float(b)) / abs(float(b))


def device_label() -> str:
    import jax

    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind}"


def phase(name: str, fn):
    """Run one phase under the compile counter and print its line (keys
    of the phase's result that start with ``_`` are returned, not printed).
    A phase that raises still prints its line, with ``"ok": false`` and
    the error, and the exception propagates."""
    from repro.analysis import tracecheck

    t0 = time.perf_counter()
    info, error = {}, None
    try:
        with tracecheck.watch() as w:
            info = fn()
    except Exception as e:
        error = e
        raise
    finally:
        line = {"phase": name, "ok": error is None, "device": device_label(),
                "wall_s": round(time.perf_counter() - t0, 2),
                "compile_s": round(sum(s for _, _, s in w.spans), 2),
                "compiles": w.compiles, "cache_hits": w.cache_hits,
                "slowest_compiles": [[n, round(t, 1)] for n, _, t in sorted(
                    w.spans, key=lambda s: -s[2])[:3]]}
        if error is not None:
            line["error"] = f"{type(error).__name__}: {error}"
        line.update({k: v for k, v in info.items() if not k.startswith("_")})
        print("PHASE " + json.dumps(line), flush=True)
    return info


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def table1_network():
    from repro.scenario import NetworkSpec, PAPER_CLUSTERS_TABLE1

    return NetworkSpec.from_clusters(PAPER_CLUSTERS_TABLE1, 1)


def run_analyze(net):
    """time_opt on the default jnp DP, then on the compiled Pallas kernel.

    The wall-clock objective is flat at its optimum (on the Table-1 fleet
    the f64 sweep puts m = 33 and m = 34 within 2.3e-6 of each other), so
    the f32 kernel may pick the neighbouring m.  The checks are therefore:
    the two optima agree in wall-clock, and at the jnp optimum's own
    ``(p, m)`` the kernel's throughput and wall-clock agree with the DP's.
    """
    from repro.analysis import tracecheck
    from repro.core import buzen
    from repro.kernels import default_interpret
    from repro.scenario import Scenario, ScenarioSuite, StrategySpec

    scn = Scenario(network=net,
                   strategy=StrategySpec("time_opt", m_max=ANALYZE_M_MAX),
                   name="table1_time_opt")
    t0 = time.perf_counter()
    ref = ScenarioSuite(scn).run(mode="analyze").entries[scn.name]
    t1 = time.perf_counter()
    at_ref = Scenario(network=net, strategy=StrategySpec(
        "explicit", p=ref["p"], m=ref["m"]), name="table1_at_jnp_optimum")
    buzen.set_backend("pallas")
    try:
        with tracecheck.watch() as w:
            pal = ScenarioSuite(scn).run(mode="analyze").entries[scn.name]
            same = ScenarioSuite(at_ref).run(
                mode="analyze").entries[at_ref.name]
    finally:
        buzen.set_backend("jnp")
    t2 = time.perf_counter()
    kernel_traced = "buzen_pallas_batched" in w.traced
    check(kernel_traced, "the pallas analyze never traced the Buzen kernel")
    check(not default_interpret(), "Pallas kernels would run interpreted")
    d_opt = rel(pal["tau"], ref["tau"])
    d_thr = rel(same["throughput"], ref["throughput"])
    d_tau = rel(same["tau"], ref["tau"])
    check(d_opt <= BUZEN_RTOL, f"optimal m: pallas {pal['m']} vs jnp "
          f"{ref['m']}, optimal wall-clock drift {d_opt:.3g}")
    check(d_thr <= BUZEN_RTOL, f"throughput drift {d_thr:.3g}")
    check(d_tau <= BUZEN_RTOL, f"wall-clock drift {d_tau:.3g}")
    return {"n": scn.n, "m_max": ANALYZE_M_MAX, "m_opt": int(ref["m"]),
            "m_opt_pallas": int(pal["m"]),
            "throughput": ref["throughput"], "tau": ref["tau"],
            "pallas_rel_tau_opt": d_opt, "pallas_rel_throughput": d_thr,
            "pallas_rel_tau": d_tau, "jnp_s": round(t1 - t0, 2),
            "pallas_s": round(t2 - t1, 2),
            "buzen_kernel": "interpreted" if default_interpret() else "compiled",
            "_params": (ref["p"], ref["m"])}


def run_simulate(net, m: int, lanes: int, updates: int, warmup: int):
    """Uniform routing at m, batched backend, vs Prop. 4 and Eq. 7."""
    import numpy as np

    from repro.core import jackson
    from repro.scenario import Scenario, ScenarioSuite, SimSpec, StrategySpec

    p = np.full(net.n, 1.0 / net.n)
    scn = Scenario(network=net, strategy=StrategySpec("explicit", p=p, m=m),
                   sim=SimSpec(backend="batched"), name="table1_uniform")
    res = ScenarioSuite(scn, seeds=range(lanes)).run(
        mode="simulate", num_updates=updates, warmup=warmup, m_max=m)
    stats = res.entries[scn.name]
    lam = float(jackson.throughput(scn.params(p), m))
    thr = float(np.mean([float(s.throughput) for s in stats]))
    stale = float(np.mean([float(np.sum(p * np.asarray(s.mean_delay)))
                           for s in stats]))
    upd = min(int(s.updates) for s in stats)
    check(upd >= updates, f"a lane retired only {upd} updates")
    check(rel(thr, lam) <= THR_RTOL,
          f"throughput {thr:.4f} vs Prop. 4 {lam:.4f}")
    check(rel(stale, m - 1) <= STALE_RTOL,
          f"staleness {stale:.3f} vs m - 1 = {m - 1}")
    return {"n": scn.n, "m": m, "lanes": res.lanes, "programs": res.programs,
            "updates_per_lane": upd, "throughput": thr, "prop4": lam,
            "rel_thr": rel(thr, lam), "staleness": stale,
            "rel_staleness": rel(stale, m - 1)}


def class_fleet():
    from repro.scenario import ClusterSpec, NetworkSpec, Scenario

    rows = (ClusterSpec("slow", 1.0, 6.0, 6.0, 700_000),
            ClusterSpec("mid", 2.0, 7.0, 7.0, 250_000),
            ClusterSpec("fast", 3.0, 8.0, 8.0, 50_000))
    net = NetworkSpec.from_clusters(rows, aggregate=True)
    return Scenario(network=net, name="class_fleet").with_strategy(
        "time_opt", m_max=CLASS_M_MAX)


def run_simulate_classes(scn, lanes: int, updates: int, warmup: int):
    """The n = 10^6, C = 3 class fleet vs its class closed forms."""
    import numpy as np

    from repro.scenario import ScenarioSuite

    suite = ScenarioSuite(scn, seeds=range(lanes))
    closed = suite.run(mode="analyze").entries[scn.name]
    res = suite.run(mode="simulate", num_updates=updates, warmup=warmup)
    stats = res.entries[scn.name]
    p, m = res.strategies[scn.name]
    mass = np.asarray(scn.network.classes.count) * np.asarray(p)
    frac = mass / mass.sum()
    thr = float(np.mean([float(s.throughput) for s in stats]))
    stale = float(np.mean([float(np.sum(frac * np.asarray(s.mean_delay)))
                           for s in stats]))
    check(rel(thr, closed["throughput"]) <= THR_RTOL,
          f"class throughput {thr:.4f} vs {closed['throughput']:.4f}")
    check(rel(stale, m - 1) <= CLASS_STALE_RTOL,
          f"class staleness {stale:.3f} vs m - 1 = {m - 1}")
    return {"n": scn.n, "C": scn.network.classes.C, "m": int(m),
            "lanes": res.lanes, "throughput": thr,
            "closed_form": closed["throughput"],
            "rel_thr": rel(thr, closed["throughput"]), "staleness": stale,
            "rel_staleness": rel(stale, m - 1)}


def emnist_source() -> str:
    from repro.data.emnist import emnist_cache_path

    return "emnist.npz" if os.path.exists(emnist_cache_path()) \
        else "synthetic stand-in"


def run_train(net, updates: int, samples_per_class: int):
    """Two strategy lanes of the CNN, then one lane fused vs unfused."""
    import jax
    import numpy as np

    from repro.core import jackson
    from repro.fl.engine import DeviceTrainer
    from repro.fl.models import cnn_classifier
    from repro.scenario import DataSpec, Scenario, ScenarioSuite

    data = DataSpec(dataset="emnist", partition="dirichlet", alpha=0.2,
                    num_classes=10, samples_per_class=samples_per_class,
                    test_fraction=0.2)
    base = Scenario(network=net, data=data)
    suite = ScenarioSuite.strategy_grid(base, ("asyncsgd", "time_opt"),
                                        seeds=(0,), m_max=ANALYZE_M_MAX)
    strategies = suite.resolve()
    rate = min(float(jackson.throughput(base.params(p), m))
               for p, m in strategies.values())
    horizon = 2.0 * updates / rate   # twice the time the slower lane needs
    model = cnn_classifier(28, 10)
    opts = dict(horizon_time=horizon, max_updates=updates, batch_size=32,
                eval_every_time=horizon / 10)
    res = suite.run(mode="train", model=model, **opts)
    lanes = {}
    for name, (log,) in res.entries.items():
        losses = np.asarray(log.losses)
        check(np.isfinite(losses).all(), f"{name}: non-finite loss")
        check(losses[-1] < losses[0],
              f"{name}: eval loss {losses[-1]:.4f} not below {losses[0]:.4f}")
        check(log.updates[-1] == updates,
              f"{name}: {log.updates[-1]} updates, planned {updates}")
        lanes[name] = {"m": int(res.strategies[name][1]),
                       "loss0": float(losses[0]), "loss": float(losses[-1]),
                       "acc": float(log.accuracies[-1])}

    # one lane through the compiled fused_update kernel vs the XLA update
    scn = suite.scenarios["time_opt"]
    p, m = res.strategies["time_opt"]
    clients, test = data.build(scn.n)
    runs = {}
    for fused in (False, True):
        tr = DeviceTrainer.from_scenario(
            scn, model, clients, test_data=test, batch_size=32,
            eval_every_time=horizon / 10, use_fused_update=fused)
        logs, params = tr.run_lanes([p], [m], [scn.eta()], [0], horizon,
                                    max_updates=updates)
        runs[fused] = (logs[0], params)
    diff = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(runs[False][1]),
                    jax.tree_util.tree_leaves(runs[True][1])):
        a, b = np.asarray(a), np.asarray(b)
        check(a.dtype == np.float32, f"parameters are {a.dtype}, not f32")
        check(np.allclose(a, b, rtol=FUSED_RTOL, atol=FUSED_ATOL),
              "fused vs unfused parameters differ beyond f32 tolerance")
        diff = max(diff, float(np.max(np.abs(a - b))))
    check(np.allclose(runs[False][0].losses, runs[True][0].losses,
                      rtol=FUSED_RTOL, atol=FUSED_ATOL),
          "fused vs unfused eval losses differ beyond f32 tolerance")
    check(runs[True][0].updates[-1] == updates, "fused lane update count")
    return {"n": scn.n, "model": "cnn_classifier(28, 10)",
            "data": emnist_source(), "horizon": horizon,
            "updates_per_lane": updates, "lanes": lanes,
            "fused_max_abs_param_diff": diff}


def run_serve(net, analyzed):
    """In-thread server on a unix socket: bitwise vs direct, repeat cached."""
    import numpy as np

    from repro.scenario import Scenario, ScenarioSuite, SimSpec, StrategySpec
    from repro.serve.client import ServeClient
    from repro.serve.protocol import encode_entry
    from repro.serve.server import ServeConfig, Server

    p_opt, m_opt = analyzed
    an = Scenario(network=net, strategy=StrategySpec("explicit", p=p_opt,
                                                     m=int(m_opt)),
                  name="serve_analyze")
    p = np.full(net.n, 1.0 / net.n)
    sim = Scenario(network=net,
                   strategy=StrategySpec("explicit", p=p, m=M_TABLE1),
                   sim=SimSpec(backend="batched"), name="serve_simulate")
    seeds = tuple(range(SIM_LANES))
    # the simulate phase's options: the served dispatch reuses its program
    sim_opts = dict(num_updates=SIM_UPDATES, warmup=SIM_WARMUP, m_max=M_TABLE1)
    with tempfile.TemporaryDirectory() as tmp:
        sock = os.path.join(tmp, "serve.sock")
        server = Server(ServeConfig(socket_path=sock, max_wait=0.02))
        server.start()
        try:
            with ServeClient(sock, timeout=900) as c:
                got_an = c.unwrap(c.collect(c.submit(an, mode="analyze")))
                first = c.collect(c.submit(sim, mode="simulate", seeds=seeds,
                                           **sim_opts))
                again = c.collect(c.submit(sim, mode="simulate", seeds=seeds,
                                           **sim_opts))
        finally:
            server.stop()
    want_an = encode_entry("analyze", ScenarioSuite(an).run(
        mode="analyze").entries[an.name])
    want_sim = encode_entry("simulate", ScenarioSuite(sim, seeds=seeds).run(
        mode="simulate", **sim_opts).entries[sim.name])
    same = lambda a, b: json.dumps(a) == json.dumps(b)  # noqa: E731
    check(same(got_an, want_an), "served analyze != direct ScenarioSuite.run")
    check(first["cached"] is False, "first simulate request was cached")
    check(same(ServeClient.unwrap(first), want_sim),
          "served simulate != direct ScenarioSuite.run")
    check(again["cached"] is True, "repeated simulate was not a cache hit")
    check(same(ServeClient.unwrap(again), want_sim), "cached payload differs")
    return {"requests": 3, "bitwise_vs_direct": True, "repeat_cached": True}


def run_four_chips(net, lanes: int, updates: int, warmup: int):
    """The sharded backend over every device vs batched on one device."""
    import jax
    import numpy as np

    from repro.scenario import Scenario, ScenarioSuite, StrategySpec
    from repro.sim.batched_events import build_lanes_fn

    p = np.full(net.n, 1.0 / net.n)
    scn = Scenario(network=net,
                   strategy=StrategySpec("explicit", p=p, m=M_TABLE1),
                   name="table1_uniform")
    kw = dict(num_updates=updates, warmup=warmup, m_max=M_TABLE1)
    sharded = ScenarioSuite(scn, seeds=range(lanes)).run(
        mode="simulate", backend="sharded", **kw).entries[scn.name]
    batched = ScenarioSuite(scn, seeds=range(lanes)).run(
        mode="simulate", backend="batched", **kw).entries[scn.name]
    mesh = build_lanes_fn("sharded", updates, warmup, "exponential",
                          M_TABLE1, False).mesh
    check(mesh.devices.size == len(jax.devices()) == 4,
          f"lane mesh spans {mesh.devices.size} of {len(jax.devices())} "
          f"devices")
    for a, b in zip(sharded, batched):
        for x, y in zip(a, b):
            check(np.array_equal(np.asarray(x), np.asarray(y)),
                  "sharded != batched")
    padded = -(-lanes // mesh.devices.size) * mesh.devices.size
    return {"n": scn.n, "m": M_TABLE1, "lanes": lanes,
            "mesh_devices": int(mesh.devices.size), "mesh_lanes": padded,
            "sharded_equals_batched": "bitwise"}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded simulate path on 4 chips and "
                         "compare it with batched on one")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from the "
              f"root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's devices are "
              f"{devices[0].platform}); this script runs only on a TPU",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: {want} chips needed, {len(devices)} found",
              file=sys.stderr)
        return 1

    from repro.serve.xla_cache import enable_persistent_cache

    print(f"chip_smoke: compile cache {enable_persistent_cache()}",
          flush=True)
    net = table1_network()
    if args.four_chips:
        phase("four_chips", lambda: run_four_chips(
            net, FOUR_CHIP_LANES, SIM_UPDATES, SIM_WARMUP))
    else:
        analyzed = phase("analyze", lambda: run_analyze(net))["_params"]
        phase("simulate", lambda: run_simulate(net, M_TABLE1, SIM_LANES,
                                               SIM_UPDATES, SIM_WARMUP))
        phase("simulate_classes", lambda: run_simulate_classes(
            class_fleet(), SIM_LANES, SIM_UPDATES, SIM_WARMUP))
        phase("train", lambda: run_train(net, TRAIN_UPDATES, 300))
        phase("serve", lambda: run_serve(net, analyzed))
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
