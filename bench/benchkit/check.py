"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the served requests, drawn from the seed and always holding the longest,
goes through the plain reference: each prompt with its served tokens, in
one forward pass.  At every position whose next token was served, the
number compared is how far the served token's logit lies below the
reference's best logit there; a run reports the widest such gap.  That
reading holds for greedy tokens, which is how every cell serves.

The control is the reference one precision below the configuration
(``mode="control"``): the token it puts first at each position is read
against the reference the same way.
"""

from __future__ import annotations

import numpy as np


def sample(served: list[tuple[list[int], list[int]]], seed: int,
           tokens: int, max_requests: int) -> list[tuple[list, list]]:
    """The longest request, then others in an order drawn from the seed,
    until ``tokens`` served tokens or ``max_requests`` requests."""
    if not served:
        return []
    order = sorted(range(len(served)),
                   key=lambda i: -(len(served[i][0]) + len(served[i][1])))
    rest = np.random.default_rng(seed).permutation(order[1:]).tolist()
    picked, n = [], 0
    for i in [order[0]] + rest:
        if n >= tokens or len(picked) >= max_requests:
            break
        picked.append(served[i])
        n += len(served[i][1])
    return picked


def _inputs(picked):
    seqs = [np.asarray(p + o[:-1], np.int32) for p, o in picked]
    rows = [np.arange(len(p) - 1, len(p) + len(o) - 1) for p, o in picked]
    toks = [np.asarray(o, np.int32) for _, o in picked]
    return seqs, rows, toks


STATS = {
    # widest gap over the sampled positions
    "served_logit_gap": lambda g: float(np.max(g)),
    # mean gap over the sampled positions
    "served_logit_gap_mean": lambda g: float(np.mean(g)),
    # share of the sampled positions whose token is not the best
    "served_flip_share": lambda g: float(np.mean(g > 0)),
}


def gaps(ref, cfg: dict, seed: int, picked, pools: str | None,
         control: bool = False) -> tuple[np.ndarray, np.ndarray | None]:
    """Per sampled position, how far the served token's logit lies below
    the reference's best; with ``control``, the same for the control's
    first token."""
    seqs, rows, toks = _inputs(picked)
    other = None
    if control:
        c = ref.evaluate(cfg, seed, seqs, rows, toks, mode="control",
                         pools=pools)
        cuts = np.cumsum([len(r) for r in rows])[:-1]
        other = np.split(c["argmax"], cuts)
    r = ref.evaluate(cfg, seed, seqs, rows, toks, other, pools=pools)
    return (r["best"] - r["served"],
            r["best"] - r["other"] if control else None)


def compare(ref, cell, seed: int, served, n_failed: int,
            control: bool = False) -> tuple[dict, dict]:
    """Each number compared, with its limit (the statistics that
    ``cells/<cell>.json`` gives a limit), and the readings of every
    statistic for the program and, with ``control``, for the control (as
    ``calibrate.py`` reads them to set the limits).  ``correct`` holds
    when every compared value is at most its limit."""
    g = cell.geometry
    picked = sample(served, seed, g["check"]["tokens"],
                    g["check"]["max_requests"])
    out = {"failed_requests": {"value": n_failed, "limit": 0}}
    if not picked:
        for name, lim in g["limits"].items():
            out[name] = {"value": float("inf"), "limit": lim}
        return out, {}
    sg, cg = gaps(ref, cell.cfg, seed, picked, g["kv_quant"], control)
    for name, lim in g["limits"].items():
        out[name] = {"value": STATS[name](sg), "limit": lim}
    readings = {"tokens": int(sg.size), "requests": len(picked)}
    for name, f in STATS.items():
        readings[name] = {"program": f(sg)}
        if control:
            readings[name]["control"] = f(cg)
    return out, readings
