"""Multi-device lane sharding for the event engine (``backend="sharded"``).

Lanes (seeds x strategy lanes x scenarios) are embarrassingly parallel —
the ``"batched"`` backend already advances them in one vmapped program, but
on ONE device.  This backend splits the lane axis across every local device
with ``shard_map`` (via ``repro.compat``): each device runs the identical
vmapped single-lane scan on its slice of lanes, so a suite sweep scales
with the device count.  On CI the devices are the XLA host-platform CPUs
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the
``repro.launch.dryrun`` trick); on real hardware they are the accelerator
cores.

Bitwise contract: each lane's program is strictly lane-local (no
collectives, no cross-lane reductions), so sharding only changes WHERE a
lane runs, not what it computes — results are bitwise identical to the
``"batched"`` backend lane-by-lane at any device count (asserted in
``tests/test_sharded.py``).  The lane axis is padded to a device-count
multiple by repeating the final lane; padded lanes are computed and
discarded (never observable, and cheaper than a ragged mesh).

With a single local device the mesh is trivial and this backend is the
``"batched"`` program under one extra (identity) partitioning — useful as
the always-on CI configuration of the multi-device path.
"""
from __future__ import annotations
# contract: padded-n — reductions here are on the bitwise padding contract

import functools

import jax
import jax.numpy as jnp

from ..compat import make_mesh, shard_map
from ..core import events


def device_count() -> int:
    """Local devices the lane mesh spans (1 on a plain CPU process; >1
    under ``--xla_force_host_platform_device_count`` or real multi-chip)."""
    return len(jax.devices())


@functools.lru_cache(maxsize=None)
def _build_sharded_fn(nu: int, wu: int, distribution: str, m_max: int,
                      has_power: bool, kind: str = "client",
                      trace_events: int = 0, chunk: int = 1):
    """The compiled sharded lane-sweep program for one static signature.

    Memoized like ``batched_events._build_lanes_fn``; the returned wrapper
    handles lane padding on the host and slices the pad back off.
    ``kind`` selects the per-lane engine: ``"client"`` lanes carry
    ``NetworkParams`` (per-client tables), ``"class"`` lanes carry
    ``ClassParams`` through the O(#classes) class-aggregated engine.
    ``trace_events > 0`` runs the traced engine variant — per-lane
    telemetry rings shard with their lanes (strictly lane-local, so the
    bitwise contract is untouched) and the return becomes
    ``(stats, ring)``.  ``chunk > 1`` runs the megastep engine variant
    (bitwise equal trajectories, lane-local like everything else here).
    """
    ndev = device_count()

    if kind == "class":
        if trace_events:
            def one(prm, m, key, power):
                return events._simulate_stats_classes_traced(
                    prm, m, key, nu, wu, distribution, m_max, power,
                    trace_events, chunk)
        else:
            def one(prm, m, key, power):
                return events._simulate_stats_classes(
                    prm, m, key, nu, wu, distribution, m_max, power, chunk)
    else:
        if trace_events:
            def one(prm, m, key, power):
                return events._simulate_stats_traced(
                    prm, m, key, nu, wu, distribution, m_max, power,
                    trace_events, chunk)
        else:
            def one(prm, m, key, power):
                return events._simulate_stats(prm, m, key, nu, wu,
                                              distribution, m_max, power,
                                              chunk)

    mesh = make_mesh((ndev,), ("lanes",))
    spec = jax.sharding.PartitionSpec("lanes")

    # named (not a lambda) so the compile log — and the
    # repro.analysis.tracecheck program budgets — can identify the sharded
    # planner program by name
    if has_power:
        def sharded_lanes(prm, m, key, pw):
            return jax.vmap(one)(prm, m, key, pw)

        jfn = jax.jit(shard_map(sharded_lanes, mesh,
                                in_specs=(spec, spec, spec, spec),
                                out_specs=spec))
    else:
        def sharded_lanes(prm, m, key):
            return jax.vmap(lambda p_, m_, k_: one(p_, m_, k_, None))(
                prm, m, key)

        jfn = jax.jit(shard_map(sharded_lanes, mesh,
                                in_specs=(spec, spec, spec),
                                out_specs=spec))

    def wrapper(lane_params, m_vec, keys, power):
        L = int(m_vec.shape[0])
        Lp = -(-L // ndev) * ndev

        def pad(x):
            x = jnp.asarray(x)
            if Lp == L:
                return x
            reps = jnp.broadcast_to(x[-1:], (Lp - L,) + x.shape[1:])
            return jnp.concatenate([x, reps], axis=0)

        prm = jax.tree_util.tree_map(pad, lane_params)
        mv, ks = pad(m_vec), pad(keys)
        if has_power:
            out = jfn(prm, mv, ks, jax.tree_util.tree_map(pad, power))
        else:
            out = jfn(prm, mv, ks)
        return jax.tree_util.tree_map(lambda x: x[:L], out)

    wrapper.mesh = mesh  # the lane mesh, for callers that check its extent
    return wrapper


def build_sharded_lanes_fn(num_updates: int, warmup: int, distribution: str,
                           m_max: int, has_power: bool,
                           trace_events: int = 0, chunk: int = 1):
    """``fn(lane_params, m_vec, keys, power) -> EventStats`` sharding the
    lane axis over all local devices (the ``"sharded"`` entry of
    ``batched_events._build_lanes_fn``)."""
    return _build_sharded_fn(int(num_updates), int(warmup), distribution,
                             int(m_max), bool(has_power), "client",
                             int(trace_events), int(chunk))


def build_sharded_class_lanes_fn(num_updates: int, warmup: int,
                                 distribution: str, m_max: int,
                                 has_power: bool, trace_events: int = 0,
                                 chunk: int = 1):
    """Class-aggregated variant: ``fn(lane_classes, m_vec, keys, power)``
    where each lane is a :class:`~repro.core.buzen.ClassParams` network run
    through ``events._simulate_stats_classes`` — the ``"sharded"`` entry of
    ``batched_events._build_class_lanes_fn``."""
    return _build_sharded_fn(int(num_updates), int(warmup), distribution,
                             int(m_max), bool(has_power), "class",
                             int(trace_events), int(chunk))
