"""``repro.serve`` — the always-on suite service.

A persistent server (``python -m repro.serve``) accepts scenario
requests over JSON lines (unix socket, stdio fallback), coalesces
concurrent requests into spare lanes of the suite planner's resident
programs, answers repeats from a ``Scenario.hash()`` response cache,
and restarts warm through the jax persistent compilation cache.

This ``__init__`` stays import-light: the server/executor pull in
jax-heavy modules only when actually booted.  The metrics registry they
share with the suite planner is :mod:`repro.obs.metrics`.
"""
from ..obs.metrics import Histogram, Metrics

__all__ = ["Histogram", "Metrics"]
