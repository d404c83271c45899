"""The control fails the comparison at the tiny size: the reference one
precision below the configuration (float8 activations, float8 K/V for
bfloat16 pools), put in the program's place, reads wider than the limit
on every seed, while the program reads under it.

Readings on the CPU when the tiny limit was set (seeds 1-6 and 2**40+5):
program 0.0-0.0104, control 0.056-0.199; limit 0.03."""

from __future__ import annotations

import pytest

import tiny


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_program_passes(tmp_path, seed):
    res = tiny.run(tmp_path, seed=seed, control=True)
    for name, limit in tiny.CELL["limits"].items():
        r = res["readings"][name]
        assert r["program"] <= limit
        assert r["control"] > limit
        assert res["compared"][name]["value"] == r["program"]
    assert res["correct"]
