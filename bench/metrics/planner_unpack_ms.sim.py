"""Planner host time per request spent unpacking lanes: the
``suite.unpack`` spans (per-lane slicing and unpadding of the statistics,
the result-cache fill; ``repro.obs.metrics``) of simulate requests, over
the ``suite.run`` spans the window holds."""


def read(run):
    if run.mode != "simulate":
        return None
    runs = sum(1 for s in run.spans if s["name"] == "suite.run")
    unpack = [s["duration"] for s in run.spans if s["name"] == "suite.unpack"
              and s["labels"].get("mode") == "simulate"]
    if not runs or not unpack:
        return None
    return 1e3 * sum(unpack) / runs
