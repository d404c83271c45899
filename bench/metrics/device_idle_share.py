"""Device: share of the traced window in which no operation ran on the
chip (device trace)."""

from benchkit import trace


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / run.trace.window_s)
