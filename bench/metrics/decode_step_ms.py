"""Model step: device time of one run of the decode-step program, the mean
over the runs in the traced window (device trace)."""

from benchkit import trace


def read(run):
    if run.trace is None:
        return None
    runs = trace.call_runs(run.trace, "decode")
    if not runs:
        return None
    return 1e3 * sum(r.dur for r in runs) / len(runs)
