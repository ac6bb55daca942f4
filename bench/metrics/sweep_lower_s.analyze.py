"""Seconds per request that the ``time_opt`` concurrency sweep spends in
trace and lowering: the ``optimize.lower`` spans (``repro.obs.metrics``)
over the window's completed requests."""


def read(run):
    done = run.done
    if run.mode != "analyze" or not done:
        return None
    xs = [s["duration"] for s in run.spans if s["name"] == "optimize.lower"]
    return sum(xs) / len(done) if xs else None
