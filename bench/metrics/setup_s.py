"""Seconds from process start to the first timed request: imports, the
chip, the fleet and data, compile-cache loads and one warm request."""


def read(run):
    return run.setup_s
