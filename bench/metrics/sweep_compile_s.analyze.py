"""Seconds per request that the ``time_opt`` concurrency sweep spends in
XLA compilation or loading from the persistent compile cache: the
``optimize.compile`` spans (``repro.obs.metrics``) over the window's
completed requests."""


def read(run):
    done = run.done
    if run.mode != "analyze" or not done:
        return None
    xs = [s["duration"] for s in run.spans
          if s["name"] == "optimize.compile"]
    return sum(xs) / len(done) if xs else None
