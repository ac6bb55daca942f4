"""Observability: telemetry rings, trace export, metrics, drift monitors.

Layers (see ``README.md`` "Observability"):

  * ``repro.obs.rings`` — on-device ring buffers carried through the
    event scan and the fused trainer (bitwise non-invasive; statically
    disabled at capacity 0);
  * ``repro.obs.metrics`` — the process-wide counters/histograms/spans
    registry, shared by the suite planner, the optimizer and
    ``repro.serve``; every span is also a profiler annotation;
  * ``repro.obs.trace`` — Chrome-trace/Perfetto JSON export of the
    simulated closed-network timeline plus host spans and compiles;
  * ``repro.obs.drift`` — empirical-vs-closed-form drift monitors with
    tolerance bands;
  * ``python -m repro.obs`` — smoke/check/report CLI over saved traces.

Tracing is selected per scenario by ``TraceSpec`` on
``Scenario.sim`` (``repro.scenario.SimSpec``).

This ``__init__`` stays import-light (metrics only): the exporters pull
in the scenario/suite layers and are imported on demand.
"""
from .metrics import Histogram, Metrics

__all__ = ["Histogram", "Metrics"]
