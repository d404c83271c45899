"""Scheduler: mean share of the decode lanes that held a live request,
over every decode iteration of the window (``EngineStats``)."""


def read(run):
    live = run.engine_stats.live_per_iteration
    if not live:
        return None
    return 100.0 * sum(live) / len(live) / run.cell.geometry["slots"]
