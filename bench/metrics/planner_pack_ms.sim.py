"""Planner host time per request spent packing lanes: the ``suite.pack``
spans (lane padding and stacking, the program lookup, up to the dispatch;
``repro.obs.metrics``) of simulate requests, over the ``suite.run`` spans
the window holds."""


def read(run):
    if run.mode != "simulate":
        return None
    runs = sum(1 for s in run.spans if s["name"] == "suite.run")
    pack = [s["duration"] for s in run.spans if s["name"] == "suite.pack"
            and s["labels"].get("mode") == "simulate"]
    if not runs or not pack:
        return None
    return 1e3 * sum(pack) / runs
