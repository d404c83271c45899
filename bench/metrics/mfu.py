"""Device, whole step: model FLOPs of the tokens the traced window
prefilled and decoded, over the window and the chip's bf16 peak.

A decoded token costs every layer's matrix products, attention over its
lane's tokens and the LM head.  A prefilled token costs the layers and
attention over the positions up to its own; each chunk row adds one LM
head (the program reads logits at every row's last position).
"""

from benchkit import costs


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    cfg = run.cell.cfg
    m = costs.dims(cfg)
    body = costs.token_flops(cfg, 0, head=False)
    head = costs.token_flops(cfg, 0, head=True) - body
    attn = m["layers"] * 4.0 * m["h"] * m["hd"]
    flops = 0.0
    for c in run.traced_calls:
        if c.kind == "decode":
            flops += len(c.lane_tokens) * (body + head) \
                + attn * sum(c.lane_tokens)
        else:
            for start, n in c.rows:
                flops += n * body + head + attn * (n * start
                                                   + n * (n + 1) / 2)
    return 100.0 * flops / run.trace.window_s / run.peaks.bf16_flops
