"""The traffic generator: deterministic per seed, the same sizes on every
seed, and a mix found by its file name alone."""

from __future__ import annotations

import collections
import json

from benchkit import runner, traffic


def _sizes(reqs):
    return [(len(p), o) for p, o in reqs]


def test_same_seed_same_requests():
    mix = traffic.load("chat")
    a = traffic.requests(mix, 70, 151936, 2**33 + 5)
    b = traffic.requests(mix, 70, 151936, 2**33 + 5)
    assert a == b
    assert all(0 <= t < 151936 for p, _ in a for t in p)


def test_seeds_reorder_the_same_sizes():
    mix = traffic.load("longdoc")
    n = 3 * mix["block"]
    a = traffic.requests(mix, n, 1000, 1)
    b = traffic.requests(mix, n, 1000, 2)
    assert a != b
    assert collections.Counter(_sizes(a)) == collections.Counter(_sizes(b))
    # each block holds the same sizes on every seed
    blk = mix["block"]
    assert sorted(_sizes(a[:blk])) == sorted(_sizes(b[blk:2 * blk]))


def test_lengths_follow_the_mix():
    mix = traffic.load("chat")
    p = traffic.stratified_lengths(mix["prompt"], 101)
    assert min(p) >= mix["prompt"]["min"] and max(p) <= mix["prompt"]["max"]
    assert p[50] == mix["prompt"]["median"]
    assert p == sorted(p)


def test_new_mix_and_cell_found_by_name(tmp_path):
    """A mix and a cell added as data files are found with no code edit."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "cells").mkdir()
    mix = {"prompt": {"median": 9, "sigma": 0.1, "min": 8, "max": 10},
           "output": {"median": 3, "sigma": 0.1, "min": 2, "max": 4},
           "block": 2}
    (tmp_path / "traffic" / "tiny_mix.json").write_text(json.dumps(mix))
    (tmp_path / "cells" / "m.tiny_mix.json").write_text(
        json.dumps({"slots": 2}))
    (tmp_path / "m.json").write_text(json.dumps({"arch": "qwen2"}))
    spec = {"configs": [{"name": "m", "file": str(tmp_path / "m.json")}],
            "workloads": [{"name": "m.tiny_mix", "config": "m",
                           "traffic": "tiny_mix", "chips": 1}],
            "end_to_end": [{"name": "setup_s"}],
            "per_layer": [{"name": "x", "workloads": ["other"]}]}
    cell = runner.find_cell(spec, "m.tiny_mix", tmp_path, tmp_path)
    assert cell.mix == mix and cell.geometry == {"slots": 2}
    assert cell.per_layer == []
    reqs = traffic.requests(cell.mix, 5, 50, 0)
    assert all(8 <= len(p) <= 10 and 2 <= o <= 4 for p, o in reqs)
