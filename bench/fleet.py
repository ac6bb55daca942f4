"""A configuration file's fleet, as plain arrays and as the system's spec.

Two fleet kinds, named by the configuration's ``fleet`` key:

* ``"clusters"``: rows under ``"clusters"``, each ``count`` clients of one
  rate profile, simulated client by client;
* ``"classes"``: rows under ``"classes"``, each a class of
  ``count * scale`` exchangeable members (``scale`` defaults to 1),
  simulated by the class-aggregated engine.

``arrays(config)`` is what the references read of a cluster fleet:
per-client rates, the clients of each cluster in file order.
``class_arrays(config)`` is the same of a class fleet: per-class rates and
counts, unexpanded.  ``network(config)`` builds the system's
``NetworkSpec`` from the same numbers, for the timed path, and
``uniform_routing(config)`` the routing that gives every client or member
the same probability.
"""
from __future__ import annotations

import numpy as np

RATES = ("mu_c", "mu_d", "mu_u")


def _kind(config: dict) -> str:
    kind = config["fleet"]
    if kind not in ("clusters", "classes"):
        raise ValueError(f"unknown fleet kind {kind!r}")
    return kind


def arrays(config: dict) -> dict:
    if _kind(config) != "clusters":
        raise ValueError(f"fleet kind {config['fleet']!r} has no per-client "
                         "arrays; use class_arrays()")
    rows = config["clusters"]
    rep = [int(c["count"]) for c in rows]
    return {k: np.repeat([float(c[k]) for c in rows], rep) for k in RATES}


def class_arrays(config: dict) -> dict:
    """Per-class rates and member counts of a class fleet."""
    if _kind(config) != "classes":
        raise ValueError(f"fleet kind {config['fleet']!r} has no classes")
    rows = config["classes"]
    out = {k: np.asarray([float(c[k]) for c in rows]) for k in RATES}
    out["count"] = np.asarray([int(c["count"]) * int(config.get("scale", 1))
                               for c in rows], np.int64)
    return out


def uniform_routing(config: dict) -> np.ndarray:
    """Routing that gives each client the same probability: per client for
    a cluster fleet, per member of each class (``[C]`` entries of
    ``1 / n``) for a class fleet."""
    if _kind(config) == "classes":
        count = class_arrays(config)["count"]
        return np.full(len(count), 1.0 / int(count.sum()))
    n = len(arrays(config)["mu_c"])
    return np.full(n, 1.0 / n)


def network(config: dict):
    """The system's ``NetworkSpec``: per client for a cluster fleet, class
    by class (``aggregate=True``) for a class fleet."""
    from repro.scenario import ClusterSpec, NetworkSpec

    kind = _kind(config)
    # a class fleet's scale multiplies its counts here; from_clusters'
    # own scale would divide them
    scale = int(config.get("scale", 1)) if kind == "classes" else 1
    rows = [ClusterSpec(c["name"], c["mu_c"], c["mu_u"], c["mu_d"],
                        int(c["count"]) * scale) for c in config[kind]]
    return NetworkSpec.from_clusters(rows, 1,
                                     law=config.get("law", "exponential"),
                                     aggregate=kind == "classes")
