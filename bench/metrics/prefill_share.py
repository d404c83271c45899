"""Scheduler: share of the traced window the device spent running the
chunked-prefill program (device trace)."""

from benchkit import trace


def read(run):
    if run.trace is None:
        return None
    runs = trace.call_runs(run.trace, "chunk")
    return 100.0 * sum(r.dur for r in runs) / run.trace.window_s
