"""Simulated SGD updates retired per second of wall time: lanes x (warmup +
updates) of every request completed in the window, over the time from the
window's start to the last completion."""


def read(run):
    done = run.done
    if run.mode != "simulate" or not done:
        return None
    span = max(r.end for r in done) - run.window_start
    return sum(r.work["updates"] for r in done) / span
