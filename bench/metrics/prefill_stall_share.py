"""Scheduler: share of the decoded tokens whose inter-token gap holds a
whole chunked-prefill call (``EngineStats.stalled_tokens`` over
``decoded_tokens``, counted by the engine over the serve call).  Where
it passes 5%, the p95 of the gap lands on a stall."""


def read(run):
    st = run.engine_stats
    stalled = getattr(st, "stalled_tokens", None)
    if stalled is None or not st.decoded_tokens:
        return None
    return 100.0 * stalled / st.decoded_tokens
