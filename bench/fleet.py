"""A configuration file's fleet, as plain arrays and as the system's spec.

``arrays(config)`` is what the references read: per-client rates, the
clients of each cluster in file order.  ``network(config)`` builds the
system's ``NetworkSpec`` from the same numbers, for the timed path.
"""
from __future__ import annotations

import numpy as np


def arrays(config: dict) -> dict:
    if config["fleet"] != "clusters":
        raise ValueError(f"unknown fleet kind {config['fleet']!r}")
    rows = config["clusters"]
    rep = [int(c["count"]) for c in rows]
    return {k: np.repeat([float(c[k]) for c in rows], rep)
            for k in ("mu_c", "mu_d", "mu_u")}


def uniform_routing(config: dict) -> np.ndarray:
    """Per-client routing of a uniform fleet."""
    n = len(arrays(config)["mu_c"])
    return np.full(n, 1.0 / n)


def network(config: dict):
    """The system's ``NetworkSpec``."""
    from repro.scenario import ClusterSpec, NetworkSpec

    return NetworkSpec.from_clusters(
        [ClusterSpec(c["name"], c["mu_c"], c["mu_u"], c["mu_d"],
                     int(c["count"])) for c in config["clusters"]],
        1, law=config.get("law", "exponential"))
