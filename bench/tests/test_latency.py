"""The serving arithmetic on synthetic timelines: a stall moves the tail of
the inter-token gap and the median time to first token, and requests are
counted against those attempted."""
from __future__ import annotations

import pytest

from bench import latency


def timelines(stall_from=None, stall_to=None):
    """Ten requests due at 0..9 s; each delivers its first token 0.5 s
    after it is due and nine more 0.1 s apart.  A stall makes every
    request due inside it wait 2 s more for its first token and its gaps
    1 s long."""
    out = []
    for k in range(10):
        stalled = stall_from is not None and stall_from <= k < stall_to
        first = k + 0.5 + (2.0 if stalled else 0.0)
        gap = 1.0 if stalled else 0.1
        out.append(latency.Served(due=float(k), sent=float(k),
                                  tokens=[first + gap * i for i in range(10)]))
    return out


def test_steady():
    s = latency.summarize(timelines(), 0.0, 30.0)
    assert s["attempted"] == 10 and s["failed"] == 0
    assert s["ttft_p50_s"] == pytest.approx(0.5)
    assert s["itl_p95_ms"] == pytest.approx(100.0)
    assert s["output_tokens_per_s"] == pytest.approx(100 / 30.0)


def test_stall_moves_tail_gap_and_median_ttft():
    base = latency.summarize(timelines(), 0.0, 30.0)
    stall = latency.summarize(timelines(3, 9), 0.0, 30.0)
    assert stall["ttft_p50_s"] == pytest.approx(2.5)
    assert stall["itl_p95_ms"] == pytest.approx(1000.0)
    assert stall["ttft_p50_s"] > base["ttft_p50_s"]
    assert stall["itl_p95_ms"] > base["itl_p95_ms"]


def test_counted_against_attempted():
    reqs = timelines()
    reqs[2].tokens = []                                  # never delivered
    reqs.append(latency.Served(due=40.0, tokens=[40.5]))  # after the window
    s = latency.summarize(reqs, 0.0, 30.0)
    assert s["attempted"] == 10
    assert s["failed"] == 1
    assert s["output_tokens_per_s"] == pytest.approx(90 / 30.0)


def test_window_bounds_gaps_and_tokens():
    # only gaps whose later token falls inside [t0, t1) count
    s = latency.summarize(timelines(), 0.0, 1.0)
    assert s["attempted"] == 1
    assert s["n_gaps"] == 4                  # 0.6, 0.7, 0.8, 0.9
    assert s["output_tokens_per_s"] == pytest.approx(5.0)


def test_quantile():
    assert latency.quantile([1, 2, 3, 4, 5], 0.5) == 3
    assert latency.quantile([0, 10], 0.95) == pytest.approx(9.5)
