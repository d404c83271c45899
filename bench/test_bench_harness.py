"""The harness end to end on the CPU: it refuses to run without a chip,
a sound run of the tiny cell is correct, and the same run with the timed
path broken underneath is not."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

import tiny
from benchkit import runner


def _bench_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2-1.5b.dq3.chat", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_chip_exits_nonzero_and_prints_no_result():
    p = _bench_cmd(runner.ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's files has no
    program to measure: the run fails and prints no result."""
    shutil.copy(runner.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(runner.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_cmd(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return tiny.run(tmp_path_factory.mktemp("sound"), seed=2**33 + 11)


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["compared"]
    assert sound["failed"] == 0 and sound["attempted"] == tiny.CELL["requests"]
    assert list(sound["compared"])[-1] == "served_logit_gap"
    assert set(sound["metrics"]) == {"out_tok_s", "itl_p95_ms",
                                     "ttft_admitted_p95_ms", "peak_hbm_gb",
                                     "setup_s"}
    assert sound["device"]["platform"] == "cpu"


def _token_altered(engine):
    """Every decode step's logits favour token 7: the token is altered
    where it is produced."""
    step = engine._decode_paged

    def broken(*a, **k):
        logits, cache = step(*a, **k)
        return logits.at[:, 7].add(1e4), cache
    engine._decode_paged = broken


def _state_unchanged(engine):
    """The decode step returns the cache it was given: its K/V writes are
    lost."""
    step = engine._decode_paged

    def broken(params, cache, *a, **k):
        logits, _ = step(params, cache, *a, **k)
        return logits, cache
    engine._decode_paged = broken


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(tmp_path, fault, sound):
    res = tiny.run(tmp_path, seed=2**33 + 11, fault=fault)
    gap = res["compared"]["served_logit_gap"]
    assert not res["correct"]
    assert gap["value"] > gap["limit"]
    assert gap["value"] > 10 * max(
        sound["compared"]["served_logit_gap"]["value"], 1e-3)


def test_traced_run_reports_per_layer_metrics(tmp_path):
    res = tiny.run(tmp_path, seed=3, trace=1)
    assert res["correct"]
    assert "lane_occupancy" in res["metrics"]
    assert "out_tok_s" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
