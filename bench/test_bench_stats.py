"""Percentile and rate arithmetic over every token and request."""

from __future__ import annotations

import types

import pytest

from benchkit import runner, stats


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None
    assert stats.percentile([3, 1, 2], 50) == 2


def test_tokens_and_gaps_stop_at_window_end():
    stamps = [[1.0, 1.5, 2.5, 4.0], [0.5, 3.0], []]
    assert stats.window_tokens(stamps, 3.0) == 5
    assert stats.inter_token_gaps(stamps, 3.0) == [0.5, 1.0, 2.5]
    assert stats.window_tokens(stamps, 10.0) == 6


def test_e2e_metrics_over_every_token_and_request():
    def req(stamps, prefill_s):
        out = runner._StampedOut()
        out.stamps = stamps
        return types.SimpleNamespace(
            out=out, stats=types.SimpleNamespace(prefill_s=prefill_s))

    reqs = [req([10.1, 10.2, 10.4], 0.1), req([10.5, 10.9, 11.5], 0.3),
            req([11.2], 0.05)]
    m = runner.e2e_metrics(reqs, 10.0, 1.0, setup_s=12.5,
                           peak_bytes=3_000_000_000)
    assert m["out_tok_s"] == 5.0          # the 11.2 and 11.5 tokens are late
    # gaps 0.1, 0.2, 0.4 -> nearest-rank p95 of three is the largest
    assert m["itl_p95_ms"] == pytest.approx(400.0)
    # the third request's first token came after the window
    assert m["ttft_admitted_p95_ms"] == pytest.approx(300.0)
    assert m["peak_hbm_gb"] == 3.0
    assert m["setup_s"] == 12.5
