"""Kernels: the fused paged decode-attention kernel's share of its
roofline over the traced window.

Least time of one call (one layer of one decode step) is the larger of
bytes / HBM bandwidth and FLOPs / bf16 peak, for what the algorithm needs
for the step's live tokens (``costs.paged_decode_call``); at these shapes
the bytes bound it.  The share is the summed least time over the summed
device time of the kernel's runs inside the traced decode steps.
"""

from benchkit import costs, trace


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    steps = trace.call_runs(run.trace, "decode")
    calls = [c for c in run.traced_calls if c.kind == "decode"]
    n = min(len(steps), len(calls))
    if n == 0:
        return None
    kern = trace.kernel_ops(trace.ops_in(run.trace, steps[:n]))
    spent = sum(e.dur for e in kern)
    if spent <= 0:
        return None
    cfg, pk = run.cell.cfg, run.peaks
    layers = cfg["num_hidden_layers"]
    least = 0.0
    for c in calls[:n]:
        nbytes, flops = costs.paged_decode_call(
            cfg, run.cell.geometry["kv_quant"], c.lane_tokens)
        least += layers * max(nbytes / pk.hbm_bw, flops / pk.bf16_flops)
    return 100.0 * least / spent
