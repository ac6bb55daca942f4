"""Device time of the event engine's lane program (the traffic's
``trace_programs``) in the trace, per simulated update of the requests the
traced window counts."""


def read(run):
    if run.mode != "simulate" or run.trace is None:
        return None
    programs = run.cell.traffic["trace_programs"]
    device_s = sum(v for k, v in run.trace.program_s.items()
                   if k in programs)
    updates = sum(r.work["updates"] for r in run.done[:run.trace.requests])
    if device_s <= 0 or updates == 0:
        return None
    return 1e6 * device_s / updates
