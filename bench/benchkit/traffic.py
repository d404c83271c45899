"""One generator for every traffic mix: a mix is a data file of length
distributions under ``bench/traffic/<name>.json``.

Lengths are log-normal, clipped to a range, and drawn by stratified
quantiles rather than at random, so that every seed serves the same set of
sizes: the seed only orders each block of ``block`` requests and picks the
token ids.  Runs with different seeds then do the same work in another
order, and a prefix of the queue has the same make-up on every seed.
"""

from __future__ import annotations

import json
import math
import statistics
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


def load(name: str, traffic_dir: Path = TRAFFIC_DIR) -> dict:
    """The mix ``name``, found by name alone."""
    path = traffic_dir / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def stratified_lengths(dist: dict, n: int) -> list[int]:
    """``n`` lengths at the mid-quantiles of a clipped log-normal with the
    given median and sigma (of the log)."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def block_sizes(mix: dict) -> list[tuple[int, int]]:
    """The (prompt, output) lengths of one block, the same for every seed.
    Prompt and output strata are paired by a fixed shuffle, so long prompts
    do not always come with long answers."""
    b = int(mix["block"])
    prompts = stratified_lengths(mix["prompt"], b)
    outputs = stratified_lengths(mix["output"], b)
    order = np.random.default_rng(0).permutation(b)
    return [(prompts[i], outputs[j]) for i, j in enumerate(order)]


def requests(mix: dict, n: int, vocab: int,
             seed: int) -> list[tuple[list[int], int]]:
    """``n`` requests as (prompt token ids, tokens to generate)."""
    rng = np.random.default_rng(seed)
    sizes = block_sizes(mix)
    out: list[tuple[list[int], int]] = []
    while len(out) < n:
        for k in rng.permutation(len(sizes)):
            p, o = sizes[k]
            out.append((rng.integers(0, vocab, p).tolist(), o))
    return out[:n]
