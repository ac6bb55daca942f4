"""Device seconds per request of the optimizer: the program runs that start
inside the strategy resolution (``bench.resolve``, the ``time_opt``
concurrency sweep), over the requests the traced window counts."""


def read(run):
    if run.mode != "analyze" or run.trace is None or not run.trace.requests:
        return None
    device_s = run.trace.span_s.get("bench.resolve", 0.0)
    return device_s / run.trace.requests if device_s > 0 else None
