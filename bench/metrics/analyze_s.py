"""Seconds per ``time_opt`` request: the summed wall time of the window's
completed requests over their count, so a stall inside a request counts."""


def read(run):
    done = run.done
    if run.mode != "analyze" or not done:
        return None
    return sum(r.end - r.start for r in done) / len(done)
