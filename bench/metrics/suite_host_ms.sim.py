"""Planner host time per request: the ``suite.run`` span minus the
``suite.dispatch`` spans inside it (``repro.obs.metrics``), simulate
cells."""


def read(run):
    if run.mode != "simulate":
        return None
    runs = [s for s in run.spans if s["name"] == "suite.run"]
    if not runs:
        return None
    host = 0.0
    for s in runs:
        end = s["start"] + s["duration"]
        inner = sum(d["duration"] for d in run.spans
                    if d["name"] == "suite.dispatch"
                    and s["start"] <= d["start"] <= end)
        host += s["duration"] - inner
    return 1e3 * host / len(runs)
