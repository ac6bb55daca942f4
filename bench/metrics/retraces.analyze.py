"""jaxpr traces per request inside the window (``tracecheck.watch()``):
the sweep re-created per request, and the eager operations around it."""


def read(run):
    done = run.done
    if run.mode != "analyze" or not done:
        return None
    return run.traces / len(done)
