"""Fused device-resident Generalized AsyncSGD training (Algorithms 1 + 2).

An entire training run — queueing dynamics (``repro.core.events``),
stale-gradient computation against the in-flight parameter-snapshot ring,
the bias-corrected ``eta / (n p_C)`` apply (optionally through the Pallas
``repro.kernels.fused_update`` kernel), energy accounting, and eval-grid
logging — executes inside ONE jitted ``lax.scan`` over update rounds, and
``jax.vmap`` batches whole runs over seeds and over padded
``(p, m, eta)`` strategy lanes.  A full Table-3 style multi-seed strategy
comparison compiles into a handful of vmapped programs (lanes are bucketed
by planned scan length so slow-throughput lanes never pay fast lanes'
padded rounds).

Snapshot ring: each in-flight task carries the parameter version it was
dispatched with (Algorithm 1).  Because the event engine re-dispatches into
the freed task-table slot, the slot index doubles as the ring index: the
ring is a ``[m_max, ...]``-stacked copy of the model pytree holding at most
``m`` live snapshots; an update reads its stale snapshot at the completed
slot and writes the post-update parameters back into the same slot for the
freshly dispatched task.

Eval-grid semantics match the host reference loop
(``AsyncFLTrainer`` with ``backend="host"``): parameters are piecewise
constant between updates, so when an update interval sweeps past grid
times the scan records one *pre-update* parameter snapshot per swept run;
after the scan, only these ``G << K`` snapshots are evaluated (on a fixed
held-out eval batch) and a ``searchsorted`` gather fills the grid — a grid
time ``t`` sees the parameters after exactly ``#{updates with time <= t}``
updates.

Host-reference contract: ``repro.core.simulator.AsyncNetworkSim`` (driven
by ``backend="host"``) remains the exact per-task-identity reference; the
engines consume randomness differently, so trainer-level cross-checks are
statistical (``tests/test_events.py``).  Known intentional deviations,
each Monte-Carlo-equivalent: fixed (seeded) eval batch instead of a fresh
draw per eval; minibatch indices drawn with replacement at full
``batch_size`` even when a client holds fewer samples; float32 parameter
updates (the host loop promotes to float64 via the x64 scale factor);
energy integrated exactly to the horizon rather than to the first event
beyond it; and when a ``max_updates`` cap binds before the horizon, the
throughput denominator is the K-th update time (the host divides by the
time of the discarded K+1-th update it popped before breaking — a ~1/K
relative difference).
"""
from __future__ import annotations
# contract: padded-n — reductions here are on the bitwise padding contract

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import jackson
from ..core import events
from ..core.buzen import NetworkParams
from ..core.numerics import seqsum
from ..sim.backend import resolve_backend
from .models import Model, accuracy, cross_entropy_loss

_GRID_CAP = 20_000  # static eval-grid safety bound


def _quantize_len(k: int) -> int:
    """Round a scan length up onto a x1.25 geometric grid so jit-cache
    entries are shared across seed sets (exact counts vary per trajectory)
    while keeping padded rounds bounded (~11% on average)."""
    q = 16
    while q < k:
        q = int(q * 1.25) + 1
    return q


class PaddedClientData(NamedTuple):
    """Client datasets padded to a common length for device-side sampling."""

    x: jax.Array      # [n, S_max, ...] float32
    y: jax.Array      # [n, S_max] int32
    sizes: jax.Array  # [n] int32


def pad_client_data(clients, n_total: Optional[int] = None,
                    min_samples: Optional[int] = None) -> PaddedClientData:
    """Stack per-client ``(x_i, y_i)`` datasets into padded device arrays.

    ``n_total`` (the traced-``n`` convention: the network's static
    ``n_max``) appends empty placeholder rows beyond the real clients —
    padded clients carry zero routing mass, are never dispatched, and so
    never have a minibatch sampled from their (single zero) row.
    ``min_samples`` forces the sample axis to at least that length so
    per-lane tables of different datasets stack into one ``[L, n, S_max]``
    array (minibatch draws are bounded by the *real* ``sizes``, so the
    extra zero rows are never sampled and trajectories are bitwise
    invariant to the sample-axis padding).
    """
    sizes = np.array([len(y) for _, y in clients], dtype=np.int32)
    if (sizes <= 0).any():
        raise ValueError("every client needs at least one sample")
    n_rows = len(clients) if n_total is None else int(n_total)
    if n_rows < len(clients):
        raise ValueError(f"n_total={n_rows} is smaller than the "
                         f"{len(clients)} provided clients")
    s_max = int(sizes.max())
    if min_samples is not None:
        s_max = max(s_max, int(min_samples))
    x0 = np.asarray(clients[0][0])
    xs = np.zeros((n_rows, s_max) + x0.shape[1:], dtype=np.float32)
    ys = np.zeros((n_rows, s_max), dtype=np.int32)
    for i, (x, y) in enumerate(clients):
        xs[i, :len(y)] = x
        ys[i, :len(y)] = y
    sizes = np.concatenate(
        [sizes, np.ones(n_rows - len(clients), dtype=np.int32)])
    return PaddedClientData(x=jnp.asarray(xs), y=jnp.asarray(ys),
                            sizes=jnp.asarray(sizes))


class DeviceTrainLog(NamedTuple):
    """Per-lane device arrays of one fused run (leading lane axis under
    vmap); converted to ``TrainLog`` by :meth:`DeviceTrainer.run_lanes`."""

    grid_times: jax.Array    # [G]
    grid_losses: jax.Array   # [G]
    grid_accs: jax.Array     # [G]
    grid_updates: jax.Array  # [G]
    grid_valid: jax.Array    # [G] bool
    t_end: jax.Array
    final_loss: jax.Array
    final_acc: jax.Array
    updates: jax.Array       # k_h — updates applied within the horizon
    mean_delay: jax.Array    # [n] unscaled E0[R_i] estimator
    delay_counts: jax.Array  # [n]
    throughput: jax.Array
    energy: jax.Array


def max_throughput_bound(net: NetworkParams, m) -> float:
    """Distribution-free upper bound on the update rate ``lambda``:
    ``min(single-server capacity, m / E[pure service per cycle])``."""
    p = np.asarray(net.p, dtype=np.float64)
    # contract: allow(raw-reduction): host-side numpy planning bound (scan sizing only) — the traced path never sees it
    p = p / p.sum()
    station = float(np.min(np.asarray(net.mu_c) / np.maximum(p, 1e-12)))
    if net.mu_cs is not None:
        station = min(station, float(net.mu_cs))
    # contract: allow(raw-reduction): host-side numpy planning bound (scan sizing only) — the traced path never sees it
    cycle = float(np.sum(p * (1.0 / np.asarray(net.mu_d)
                              + 1.0 / np.asarray(net.mu_c)
                              + 1.0 / np.asarray(net.mu_u))))
    if net.mu_cs is not None:
        cycle += 1.0 / float(net.mu_cs)
    return min(station, float(m) / cycle)


class DeviceTrainer:
    """Compiles and caches the fused training scan for one FL problem
    (model, client data, network rates); lanes vary ``(p, m, eta, seed)``."""

    def __init__(self, model: Model, clients, net: NetworkParams,
                 config, test_data=None, power=None,
                 loss_fn: Callable = cross_entropy_loss,
                 sim_backend: Optional[str] = None,
                 sim_interpret: Optional[bool] = None,
                 sim_chunk: int = 1,
                 trace_updates: int = 0):
        self.model = model
        self.net = net
        self.cfg = config
        self.power = power
        # event-engine backend for the queueing scans (repro.sim); None
        # defers to the process-wide REPRO_SIM_BACKEND at build time;
        # sim_interpret overrides the pallas kernel's compile/interpret auto
        self.sim_backend = sim_backend
        self.sim_interpret = sim_interpret
        # megastep chunk for the queueing scans: next_update retires up to
        # sim_chunk events per inner step — update semantics (and the event
        # trajectories) are bitwise unchanged for any value
        self.sim_chunk = int(sim_chunk)
        # repro.obs update-telemetry ring capacity (0 = tracing off: the
        # fused scan is byte-identical to the untraced program); when set,
        # each lane of :meth:`run_lanes` records its last ``trace_updates``
        # applied updates and the per-lane rings land in
        # :attr:`last_update_rings`
        self.trace_updates = int(trace_updates)
        self.last_update_rings = None
        self.n = net.n              # static row count (n_max when padded)
        # real population: the bias correction eta/(n p_C) and the reported
        # per-client statistics use the *active* count under the traced-n
        # convention (padded clients contribute no updates)
        self.n_act = (net.n if net.n_active is None
                      else int(np.asarray(net.n_active)))
        if len(clients) not in (self.n, self.n_act):
            raise ValueError(
                f"{len(clients)} clients for a network with "
                f"{self.n_act} active of {self.n} rows")
        self.data = pad_client_data(clients, n_total=self.n)
        self.has_test = test_data is not None
        if self.has_test:
            x, y = test_data
            rng = np.random.default_rng(0)
            idx = rng.permutation(len(y))[:min(config.eval_batch, len(y))]
            self.test_x = jnp.asarray(np.asarray(x)[idx], jnp.float32)
            self.test_y = jnp.asarray(np.asarray(y)[idx], jnp.int32)
        else:
            self.test_x = self.test_y = None

        def loss(params, x, y):
            return loss_fn(model.apply(params, x), y)

        self._grad_fn = jax.grad(loss)
        self._raw_loss = loss_fn
        self._jit_cache: dict = {}
        self._count_cache: dict = {}

    @classmethod
    def from_scenario(cls, scenario, model: Model, clients, *,
                      test_data=None, loss_fn: Callable = cross_entropy_loss,
                      **config_overrides) -> "DeviceTrainer":
        """Build the fused trainer from a declarative
        ``repro.scenario.Scenario`` (network rates/law, grad clip and power
        profile come from the spec; ``config_overrides`` feed
        ``AsyncFLConfig``).  Lane routing/concurrency still varies per
        :meth:`run_lanes` call — resolve them with
        ``repro.scenario.resolve_strategy`` or a ``ScenarioSuite``."""
        sim = getattr(scenario, "sim", None)
        trace = None if sim is None else getattr(sim, "trace", None)
        return cls(model, clients, scenario.params(),
                   scenario.fl_config(**config_overrides),
                   test_data=test_data, power=scenario.power(),
                   loss_fn=loss_fn,
                   sim_backend=None if sim is None else sim.backend,
                   sim_interpret=None if sim is None else sim.interpret,
                   sim_chunk=1 if sim is None else sim.chunk,
                   trace_updates=0 if trace is None else trace.updates)

    # -- static-shape planning ---------------------------------------------

    def _plan_one(self, p, m, horizon: float, net=None) -> int:
        """Per-lane *upper bound* on rounds within ``horizon``, from the
        closed-form throughput (exponential) tightened / replaced by the
        distribution-free bound otherwise.  Only used to size the cheap
        queueing-only pre-simulation; the training scan itself gets the
        exact per-lane count from :meth:`_count_updates`."""
        base = self.net if net is None else net
        lane = base._replace(p=jnp.asarray(p))
        rate = max_throughput_bound(lane, m)
        if self.cfg.distribution == "exponential":
            rate = min(rate, 1.25 * float(jackson.throughput(lane, int(m))))
        return int(horizon * rate * 1.08) + 2 * int(m) + 32

    def _count_updates(self, ps, ms, sim_keys, horizon: float,
                       max_updates: Optional[int] = None,
                       nets=None) -> np.ndarray:
        """Exact per-lane update counts within ``horizon`` (capped by
        ``max_updates`` when given — e.g. a huge horizon with a round cap
        must not size the counting scan from the horizon).

        The event trajectory is a pure function of the sim key, so a
        queueing-only scan (no gradients, no snapshots — a fraction of the
        fused scan's cost) reproduces exactly the event stream the training
        scan will see; its count sizes that scan with zero padding margin.
        ``nets`` (per-lane padded networks, see :meth:`run_lanes`) switches
        the counting program to take the network pytree as a vmapped
        argument instead of a closure constant."""
        backend = resolve_backend(self.sim_backend)
        interp = self.sim_interpret
        ck = self.sim_chunk
        net_key = None if nets is None else tuple(
            np.asarray(leaf).tobytes()
            for net in nets for leaf in jax.tree_util.tree_leaves(net))
        cache_key = (tuple(np.asarray(p, np.float64).tobytes() for p in ps),
                     tuple(int(m) for m in ms),
                     np.asarray(sim_keys).tobytes(), round(horizon, 9),
                     max_updates, backend, interp, ck, net_key)
        hit = self._count_cache.get(cache_key)
        if hit is not None:
            return hit
        lane_nets = [None] * len(ms) if nets is None else nets
        K_bound = max(self._plan_one(p, m, horizon, net=lane_net)
                      for p, m, lane_net in zip(ps, ms, lane_nets))
        if max_updates is not None:
            K_bound = min(K_bound, int(max_updates))
        K_bound = max(K_bound, 1)
        m_max = int(max(ms))
        key_stat = ("count", K_bound, m_max, round(horizon, 9), backend,
                    interp, ck, nets is not None)
        if key_stat not in self._jit_cache:
            net0, dist = self.net, self.cfg.distribution

            def count_body(net, m, key_sim):
                st = events.init_state(net, m, key_sim, m_max=m_max,
                                       distribution=dist)

                def body(st, _):
                    st, upd = events.next_update(net, st, distribution=dist,
                                                 backend=backend,
                                                 interpret=interp, chunk=ck)
                    return st, upd.time

                _, times = jax.lax.scan(body, st, None, length=K_bound)
                # contract: allow(raw-reduction): boolean count over scan steps — exact integer arithmetic under any association
                return jnp.sum(times <= horizon)

            if nets is None:
                def one(p, m, key_sim):
                    return count_body(net0._replace(p=p), m, key_sim)
            else:
                def one(net, p, m, key_sim):
                    return count_body(net._replace(p=p), m, key_sim)

            self._jit_cache[key_stat] = jax.jit(jax.vmap(one))
        p_mat = jnp.asarray(np.stack([np.asarray(p, np.float64) for p in ps]))
        m_arr = jnp.asarray(np.asarray(ms, np.int32))
        if nets is None:
            counts = np.asarray(self._jit_cache[key_stat](
                p_mat, m_arr, sim_keys))
        else:
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *nets)
            counts = np.asarray(self._jit_cache[key_stat](
                stacked, p_mat, m_arr, sim_keys))
        self._count_cache[cache_key] = counts
        return counts

    def plan_updates(self, ps, ms, horizon: float,
                     max_updates: Optional[int] = None) -> int:
        """Upper bound on the scan length covering ``horizon`` for every
        given lane (informational; the fused scans are sized by the exact
        pre-simulated counts)."""
        k = max(self._plan_one(p, m, horizon) for p, m in zip(ps, ms))
        if max_updates is not None:
            k = min(k, int(max_updates))
        return max(k, 1)

    # -- the fused run ------------------------------------------------------

    def _build(self, K: int, G: int, m_max: int, horizon: float,
               backend: str, interp: Optional[bool],
               lane_mode: bool = False, lane_power: bool = False,
               trace_updates: int = 0, chunk: int = 1):
        tr = int(trace_updates)
        ck = int(chunk)
        cfg = self.cfg
        n = self.n
        net0 = self.net
        has_test = self.has_test
        dist = cfg.distribution
        grad_clip = cfg.grad_clip
        use_fused = getattr(cfg, "use_fused_update", False)
        batch = cfg.batch_size
        delta = cfg.eval_every_time
        grad_fn = self._grad_fn
        raw_loss = self._raw_loss
        model_apply = self.model.apply
        test_x, test_y = self.test_x, self.test_y

        def evaluate(params):
            logits = model_apply(params, test_x)
            return raw_loss(logits, test_y), accuracy(logits, test_y)

        def apply_update(params, g, scale):
            # keep every op in the parameter dtype: under x64 some gradient
            # leaves and the f64 scale would otherwise promote the whole
            # update chain (and the scan carry) to f64
            g = jax.tree_util.tree_map(
                lambda v, w: v.astype(w.dtype), g, params)
            if grad_clip is not None:
                # contract: allow(raw-reduction): parameter-axis grad norm — model leaves are never padded along the client axis
                norm = jnp.sqrt(sum(jnp.sum(jnp.square(v))
                                    for v in jax.tree_util.tree_leaves(g)))
                factor = jnp.minimum(jnp.asarray(1.0, norm.dtype),
                                     grad_clip / (norm + 1e-12))
                g = jax.tree_util.tree_map(
                    lambda v: v * factor.astype(v.dtype), g)
            if use_fused:
                from ..kernels.fused_update import fused_async_update
                new, _ = fused_async_update(params, g, scale)
                return new
            # final astype guards the scan carry: any residual promotion
            # would flip the params pytree to f64 between iterations
            return jax.tree_util.tree_map(
                lambda w, v: (w - scale.astype(w.dtype) * v).astype(w.dtype),
                params, g)

        t_grid_static = jnp.arange(G) * delta

        def run_one(params0, net, s_max, data_x_flat, data_y_flat, sizes,
                    n_act, power, p, m, eta, key_sim, key_data):
            net = net._replace(p=p)
            # sequential sum: bitwise invariant to padded zero-mass clients
            p_norm = p / seqsum(p)
            st = events.init_state(net, m, key_sim, m_max=m_max,
                                   distribution=dist, t_cap=horizon)
            snaps = jax.tree_util.tree_map(
                lambda w: jnp.broadcast_to(w[None], (m_max,) + w.shape),
                params0)
            # parameters seen by the eval grid: the pre-update params of
            # step k are active on [t_{k-1}, t_k); when that interval sweeps
            # past grid points, ONE representative row (the first swept grid
            # index) records the params — all grid points swept by the same
            # interval see identical params, so the rest are reconstructed
            # by a searchsorted gather after the scan.  This keeps the
            # per-update cost free of eval forward passes (G << K) and
            # touches a single snapshot row per update.
            grid_snaps = jax.tree_util.tree_map(
                lambda w: jnp.broadcast_to(w[None], (G,) + w.shape), params0)
            if tr:
                # telemetry aux carry (repro.obs): the update ring plus the
                # per-slot snapshot write times.  Appends read (upd, g) and
                # never feed back into the training state, so the traced
                # program is bitwise identical to the untraced one
                # (tests/test_obs.py)
                from ..obs.rings import update_ring_append, update_ring_init
                aux0 = (update_ring_init(tr),
                        jnp.zeros((m_max,), jnp.float64))
            else:
                aux0 = ()

            def body(carry, _):
                st, params, snaps, grid_snaps, prev_t, dkey, aux = carry
                st, upd = events.next_update(net, st, distribution=dist,
                                             power=power, backend=backend,
                                             interpret=interp, chunk=ck)
                live = upd.time <= horizon
                j, c = upd.slot, upd.client
                stale = jax.tree_util.tree_map(lambda s: s[j], snaps)
                dkey, kb = jax.random.split(dkey)
                idx = (c * s_max
                       + jax.random.randint(kb, (batch,), 0, sizes[c]))
                xb, yb = data_x_flat[idx], data_y_flat[idx]
                # bias correction over the REAL population (Algorithm 2):
                # padded rows have p = 0 and are never drawn as C_k
                scale = eta / (n_act * p_norm[c])
                g = grad_fn(stale, xb, yb)
                if tr:
                    ring, snap_t = aux
                    # contract: allow(raw-reduction): parameter-axis grad norm — model leaves are never padded along the client axis
                    sq = [jnp.sum(jnp.square(v.astype(jnp.float64)))
                          for v in jax.tree_util.tree_leaves(g)]
                    gnorm = jnp.sqrt(sum(sq))
                    ring = update_ring_append(
                        ring, time=upd.time, client=c, staleness=upd.delay,
                        grad_norm=gnorm, snapshot_age=upd.time - snap_t[j],
                        valid=live)
                    # like the snaps write, no live-mask on snap_t: time is
                    # monotone, so a post-horizon write is only ever read by
                    # appends whose valid gate is already False
                    aux = (ring, snap_t.at[j].set(upd.time))
                new_params = apply_update(params, g, scale)
                new_params = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(live, a, b), new_params, params)
                # first grid point inside [prev_t, t_k), if any
                g0 = jnp.searchsorted(t_grid_static, prev_t, side="left")
                g0c = jnp.clip(g0, 0, G - 1)
                cross = ((t_grid_static[g0c] >= prev_t)
                         & (t_grid_static[g0c] < upd.time))
                grid_snaps = jax.tree_util.tree_map(
                    lambda s, w: s.at[g0c].set(jnp.where(cross, w, s[g0c])),
                    grid_snaps, params)
                # the ring write needs no live-mask: time is monotone, so
                # post-horizon writes are never read by a live update
                snaps = jax.tree_util.tree_map(
                    lambda s, w: s.at[j].set(w), snaps, new_params)
                out = (upd.time, c, upd.delay, live)
                return (st, new_params, snaps, grid_snaps, upd.time, dkey,
                        aux), out

            (st, paramsK, _, grid_snaps, _, _, aux), outs = jax.lax.scan(
                body, (st, params0, snaps, grid_snaps,
                       jnp.zeros((), jnp.float64), key_data, aux0),
                None, length=K)
            times, clients_k, delays, live = outs

            if has_test:
                final_loss, final_acc = evaluate(paramsK)
                # one grid point at a time: under the lane vmap each eval
                # is then a single-batch-axis program whatever the lane
                # count, so an f32 model evaluates bitwise alike alone and
                # coalesced (a [lanes, G] batch axis changes XLA:CPU's f32
                # dot blocking with the lane count)
                snap_losses, snap_accs = jax.lax.map(evaluate, grid_snaps)
            else:
                final_loss = final_acc = jnp.zeros(())
                snap_losses = snap_accs = jnp.zeros((G,))

            # contract: allow(raw-reduction): int32 count of live updates over the scan axis — exact integer arithmetic under any association
            k_h = jnp.sum(live.astype(jnp.int32))
            delay_sum = jnp.zeros((n,)).at[clients_k].add(
                jnp.where(live, delays.astype(jnp.float64), 0.0))
            delay_cnt = jnp.zeros((n,), jnp.int32).at[clients_k].add(
                live.astype(jnp.int32))
            mean_delay = jnp.where(delay_cnt > 0,
                                   delay_sum / jnp.maximum(delay_cnt, 1), 0.0)
            t_last = jnp.max(jnp.where(live, times, 0.0))
            t_end = jnp.where(k_h < K, horizon, t_last)
            # host reference divides by the time of the first update beyond
            # the horizon (the loop's break event) when one exists
            t_break = jnp.min(jnp.where(live, jnp.inf, times))
            denom = jnp.where(jnp.isfinite(t_break), t_break, t_last)
            thr = jnp.where(denom > 0, k_h / jnp.maximum(denom, 1e-12), 0.0)

            live_times = jnp.where(live, times, jnp.inf)
            kg = jnp.searchsorted(live_times, t_grid_static, side="right")
            # grid points swept by the same update interval share kg; gather
            # each from the representative (first) index of its kg-run
            g_first = jnp.searchsorted(kg, kg, side="left")
            grid_losses = jnp.where(kg < k_h, snap_losses[g_first],
                                    final_loss)
            grid_accs = jnp.where(kg < k_h, snap_accs[g_first], final_acc)
            dlog = DeviceTrainLog(
                grid_times=t_grid_static, grid_losses=grid_losses,
                grid_accs=grid_accs, grid_updates=kg.astype(jnp.int32),
                grid_valid=t_grid_static < t_end, t_end=t_end,
                final_loss=final_loss, final_acc=final_acc, updates=k_h,
                mean_delay=mean_delay, delay_counts=delay_cnt,
                throughput=thr, energy=st.energy)
            if tr:
                return dlog, paramsK, aux[0]
            return dlog, paramsK

        if not lane_mode:
            data = self.data
            # flat views: one row-gather per minibatch instead of slicing
            # the whole client dataset out first
            s_max0 = data.x.shape[1]
            dxf0 = data.x.reshape((n * s_max0,) + data.x.shape[2:])
            dyf0 = data.y.reshape((n * s_max0,))
            sizes0, n_act0, power0 = data.sizes, self.n_act, self.power

            def single(params0, p, m, eta, key_sim, key_data):
                return run_one(params0, net0, s_max0, dxf0, dyf0, sizes0,
                               n_act0, power0, p, m, eta, key_sim, key_data)

            return jax.jit(jax.vmap(single))

        # lane mode: network, client table, real-population count (and
        # optionally the power profile) ride along each lane as vmapped
        # arguments, so lanes with different populations/datasets share one
        # resident program — the mixed-n train bucket.  The in-program
        # reshape to flat views is a free metadata op under XLA.
        if lane_power:
            def single_lanes(params0, net, dx, dy, sizes, n_act, power,
                             p, m, eta, key_sim, key_data):
                s_max = dx.shape[1]
                dxf = dx.reshape((n * s_max,) + dx.shape[2:])
                dyf = dy.reshape((n * s_max,))
                return run_one(params0, net, s_max, dxf, dyf, sizes, n_act,
                               power, p, m, eta, key_sim, key_data)
        else:
            def single_lanes(params0, net, dx, dy, sizes, n_act,
                             p, m, eta, key_sim, key_data):
                s_max = dx.shape[1]
                dxf = dx.reshape((n * s_max,) + dx.shape[2:])
                dyf = dy.reshape((n * s_max,))
                return run_one(params0, net, s_max, dxf, dyf, sizes, n_act,
                               None, p, m, eta, key_sim, key_data)

        return jax.jit(jax.vmap(single_lanes))

    def _run_bucket(self, ps, ms, etas, sim_keys, init_keys, data_keys,
                    horizon: float, K: int, m_max: int, lane_args=None):
        """One jitted, vmapped call over lanes sharing a scan length.

        ``lane_args`` (stacked ``(nets, x, y, sizes, n_acts, powers)``)
        selects the lane-mode program where the network and client table
        are vmapped arguments rather than closure constants."""
        G = int(horizon / self.cfg.eval_every_time) + 1
        if G > _GRID_CAP:
            raise ValueError(
                f"eval grid of {G} points exceeds the device cap "
                f"{_GRID_CAP}; coarsen eval_every_time or use the host "
                f"backend")
        backend = resolve_backend(self.sim_backend)
        interp = self.sim_interpret
        ck = self.sim_chunk
        params0 = jax.vmap(self.model.init)(init_keys)
        p_mat = jnp.asarray(np.stack([np.asarray(p, np.float64) for p in ps]))
        m_arr = jnp.asarray(np.asarray(ms, np.int32))
        eta_arr = jnp.asarray(np.asarray(etas, np.float64))
        tr = self.trace_updates
        if lane_args is not None:
            nets, lx, ly, lsizes, n_acts, powers = lane_args
            key_stat = ("lanes", K, G, m_max, round(horizon, 9), backend,
                        interp, lx.shape[1:], powers is not None,
                        nets.mu_cs is not None, tr, ck)
            if key_stat not in self._jit_cache:
                self._jit_cache[key_stat] = self._build(
                    K, G, m_max, horizon, backend, interp,
                    lane_mode=True, lane_power=powers is not None,
                    trace_updates=tr, chunk=ck)
            fn = self._jit_cache[key_stat]
            args = (params0, nets, lx, ly, lsizes, n_acts)
            if powers is not None:
                args = args + (powers,)
            return fn(*args, p_mat, m_arr, eta_arr, sim_keys, data_keys)
        key_stat = (K, G, m_max, round(horizon, 9), backend, interp, tr, ck)
        if key_stat not in self._jit_cache:
            self._jit_cache[key_stat] = self._build(K, G, m_max, horizon,
                                                    backend, interp,
                                                    trace_updates=tr,
                                                    chunk=ck)
        fn = self._jit_cache[key_stat]
        return fn(params0, p_mat, m_arr, eta_arr, sim_keys, data_keys)

    def run_lanes(self, ps, ms, etas, seeds, horizon_time: float, *,
                  max_updates: Optional[int] = None, init_keys=None,
                  nets=None, lane_clients=None, lane_powers=None):
        """Run ``L`` lanes (routing ``ps[L, n]``, concurrency ``ms[L]``,
        step size ``etas[L]``, seed ``seeds[L]``) as jitted, vmapped scans.

        A queueing-only pre-simulation (same keys, hence bit-identical
        event streams) counts each lane's exact rounds within the horizon;
        lanes are then bucketed by that count (within 1.25x) so the fused
        scans run with near-zero padded rounds and a slow-throughput lane
        never pays a fast lane's scan length.  Each bucket is one compile,
        cached across calls.  Returns
        ``(list[TrainLog], final_params_stacked)`` in input lane order.

        Mixed-``n`` lanes: ``nets`` gives each lane its own network, padded
        (``pad_network``) to this trainer's static row count; it requires
        ``lane_clients`` (per-lane client datasets, padded here into one
        ``[L, n, S_max]`` table) and optionally ``lane_powers`` (per-lane
        power profiles padded to the same rows).  Under the padding
        contract the per-lane trajectories are bitwise identical to a
        single-lane run of each scenario at its own size."""
        from .trainer import TrainLog  # local: trainer imports this module

        L = len(ms)
        horizon = float(horizon_time)
        lane_mode = nets is not None
        if lane_mode:
            if len(nets) != L:
                raise ValueError(f"{len(nets)} lane networks for {L} lanes")
            if lane_clients is None or len(lane_clients) != L:
                raise ValueError("per-lane networks require per-lane "
                                 "client datasets (lane_clients)")
            if lane_powers is not None and len(lane_powers) != L:
                raise ValueError(
                    f"{len(lane_powers)} lane powers for {L} lanes")
            for net in nets:
                if net.n != self.n:
                    raise ValueError(
                        f"lane network has {net.n} rows; pad_network it "
                        f"to this trainer's {self.n}")
            n_acts = [net.n if net.n_active is None
                      else int(np.asarray(net.n_active)) for net in nets]
            s_top = max(max(len(y) for _, y in cl) for cl in lane_clients)
            tables = [pad_client_data(cl, n_total=self.n, min_samples=s_top)
                      for cl in lane_clients]
            lane_x = jnp.stack([t.x for t in tables])
            lane_y = jnp.stack([t.y for t in tables])
            lane_sizes = jnp.stack([t.sizes for t in tables])
            stacked_nets = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *nets)
            stacked_pw = (None if lane_powers is None else
                          jax.tree_util.tree_map(
                              lambda *xs: jnp.stack(xs), *lane_powers))
            n_act_arr = jnp.asarray(np.asarray(n_acts, np.float64))
        elif lane_clients is not None or lane_powers is not None:
            raise ValueError("lane_clients/lane_powers need nets")
        # sim/data streams always derive from the lane seeds (matching the
        # host loop, whose sim is seeded by cfg.seed); ``init_keys`` only
        # overrides the model-initialization keys (the host loop's rng_key)
        seed_keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
        all_init_keys = seed_keys if init_keys is None else jnp.asarray(
            init_keys)
        if all_init_keys.shape[0] != L:
            raise ValueError(
                f"init_keys has {all_init_keys.shape[0]} rows for {L} lanes")
        all_sim_keys = jax.vmap(lambda k: jax.random.fold_in(k, 1))(seed_keys)
        all_data_keys = jax.vmap(lambda k: jax.random.fold_in(k, 2))(seed_keys)
        counts = self._count_updates(ps, ms, all_sim_keys, horizon,
                                     max_updates, nets=nets)
        # +1: include the first update beyond the horizon (the host loop's
        # break event), which pins t_end and the throughput denominator
        plans = [int(c) + 1 for c in counts]
        if max_updates is not None:
            plans = [min(k, int(max_updates)) for k in plans]
        plans = [max(k, 1) for k in plans]
        # group by the quantized count: bucket shapes (and hence compiled
        # programs) are stable across seed sets that land in the same
        # quantum, and a slow lane never pays a fast lane's scan length
        buckets: dict = {}
        for i in range(L):
            buckets.setdefault(_quantize_len(plans[i]), []).append(i)

        dlogs = [None] * L
        finals = [None] * L
        rings = [None] * L if self.trace_updates else None
        m_max = int(max(ms))  # shared: bucket membership must not change shapes
        for K, idx in sorted(buckets.items()):
            if max_updates is not None:
                K = min(K, int(max_updates))
            rows = jnp.asarray(idx)
            lane_args = None
            if lane_mode:
                take = lambda t: jax.tree_util.tree_map(
                    lambda a: a[rows], t)
                lane_args = (take(stacked_nets), lane_x[rows], lane_y[rows],
                             lane_sizes[rows], n_act_arr[rows],
                             None if stacked_pw is None else take(stacked_pw))
            out = self._run_bucket(
                [ps[i] for i in idx], [ms[i] for i in idx],
                [etas[i] for i in idx], all_sim_keys[rows],
                all_init_keys[rows], all_data_keys[rows], horizon, K, m_max,
                lane_args=lane_args)
            if self.trace_updates:
                dlog, fin, ring = out
            else:
                dlog, fin = out
            for row, i in enumerate(idx):
                dlogs[i] = jax.tree_util.tree_map(lambda a: a[row], dlog)
                finals[i] = jax.tree_util.tree_map(lambda a: a[row], fin)
                if rings is not None:
                    rings[i] = jax.tree_util.tree_map(lambda a: a[row], ring)
        # per-lane update rings in input lane order (None when tracing off);
        # decode with repro.obs.rings.decode
        self.last_update_rings = rings
        final_params = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *finals)

        logs = []
        for i in range(L):
            dlog = dlogs[i]
            if self.has_test:
                valid = np.asarray(dlog.grid_valid)
                times = [float(t) for t in np.asarray(dlog.grid_times)[valid]]
                losses = [float(v) for v in np.asarray(dlog.grid_losses)[valid]]
                accs = [float(v) for v in np.asarray(dlog.grid_accs)[valid]]
                upds = [int(v) for v in np.asarray(dlog.grid_updates)[valid]]
                times.append(float(dlog.t_end))
                losses.append(float(dlog.final_loss))
                accs.append(float(dlog.final_acc))
                upds.append(int(dlog.updates))
            else:
                times = losses = accs = upds = []
            logs.append(TrainLog(
                times=times, accuracies=accs, losses=losses, updates=upds,
                mean_delay=np.asarray(dlog.mean_delay)[
                    :(n_acts[i] if lane_mode else self.n_act)],
                throughput=float(dlog.throughput),
                energy=float(dlog.energy)))
        return logs, final_params


@dataclasses.dataclass
class StrategyGridResult:
    """Result of :func:`run_strategy_grid`: ``logs[name][seed_idx]``."""

    logs: dict
    seeds: tuple
    lanes: int
    updates_per_lane: int


def run_strategy_grid(model: Model, clients, net: NetworkParams,
                      strategies: dict, config, *, horizon_time: float,
                      seeds=(0,), etas=None, test_data=None, power=None,
                      trainer: Optional[DeviceTrainer] = None,
                      loss_fn: Callable = cross_entropy_loss
                      ) -> StrategyGridResult:
    """One jitted multi-seed strategy comparison: the full
    ``strategies x seeds`` grid runs as a single vmapped scan.

    ``strategies`` maps name -> ``(p, m)`` (the :func:`make_strategies`
    output); ``etas`` maps name -> step size (or a scalar for all).
    """
    if trainer is None:
        trainer = DeviceTrainer(model, clients, net, config,
                                test_data=test_data, power=power,
                                loss_fn=loss_fn)
    names = list(strategies)
    if etas is None:
        etas = {name: config.eta for name in names}
    elif not isinstance(etas, dict):
        etas = {name: float(etas) for name in names}
    ps, ms, es, ss = [], [], [], []
    for name in names:
        p, m = strategies[name]
        for s in seeds:
            ps.append(np.asarray(p, np.float64))
            ms.append(int(m))
            es.append(float(etas[name]))
            ss.append(int(s))
    logs, _ = trainer.run_lanes(ps, ms, es, ss, horizon_time)
    n_seeds = len(seeds)
    per_name = {name: logs[i * n_seeds:(i + 1) * n_seeds]
                for i, name in enumerate(names)}
    return StrategyGridResult(logs=per_name, seeds=tuple(seeds),
                              lanes=len(ms),
                              updates_per_lane=trainer.plan_updates(
                                  ps, ms, float(horizon_time)))
