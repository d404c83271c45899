"""Scheduler: host time of a typical loop iteration, in which the
one-sync serve loop keeps the device waiting: the median, over the
iterations that dispatched a step program, of each iteration's wall time
less the time it spent blocked in the step readbacks
(``EngineStats.host_s_per_iteration``).

The median, and not ``host_s / loop_iterations``: a traced run starts
and stops the profiler from inside the loop (the benchmark's wrapper of
the step call), and collecting the trace adds seconds of host time to
the one iteration that stops it.  The median is a decode-only
iteration's: about one iteration in eleven also runs a chunk call, and
the host time that adds (chunk assembly, first-token readback) is not
counted here."""

import statistics


def read(run):
    per_iteration = getattr(run.engine_stats, "host_s_per_iteration", None)
    if not per_iteration:
        return None
    return 1e3 * statistics.median(per_iteration)
