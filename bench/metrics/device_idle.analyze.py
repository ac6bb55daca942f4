"""Share of the traced window in which no operation ran on the device,
analyze cells."""


def read(run):
    if run.mode != "analyze" or run.trace is None or run.trace.devices == 0:
        return None
    return 100.0 * run.trace.idle_share
