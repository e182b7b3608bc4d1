"""Percentile and TPOT arithmetic on hand-made timelines."""

import pytest

from bench_paths import BENCH  # noqa: F401
from dtbench.stats import (Timeline, live_decode_tokens, percentile, tpot_ms,
                           window_summary)


def done(index, due, first, last, end, n, want=None, error=None):
    return Timeline(index=index, due=due, sent=due + 0.001, n_prompt=100,
                    want_tokens=want if want is not None else n,
                    first=first, last=last, end=end, got_tokens=n,
                    error=error, tag=f"t-{index}")


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 95, 19.5), ([7], 95, 7.0), (list(range(101)), 95, 95.0)])
def test_percentile_interpolates_like_numpy(values, q, want):
    np = pytest.importorskip("numpy")
    assert percentile(values, q) == pytest.approx(want)
    assert percentile(values, q) == pytest.approx(np.percentile(values, q))


def test_tpot_is_per_request_not_the_raw_chunk_gap():
    t = done(0, 0.0, first=1.0, last=3.0, end=3.0, n=21)
    assert tpot_ms(t) == pytest.approx(100.0)  # 2 s over 20 gaps
    assert tpot_ms(done(1, 0.0, 1.0, 1.0, 1.0, n=1)) is None


def test_window_counts_what_finished_inside_it():
    timelines = [
        done(0, 10.0, first=10.5, last=12.5, end=12.5, n=21),   # in
        done(1, 11.0, first=11.2, last=13.2, end=13.2, n=11),   # in
        done(2, 5.0, first=5.5, last=10.1, end=10.1, n=41),     # began before
        done(3, 19.0, first=19.5, last=20.5, end=20.5, n=5),    # straddles
        done(4, 12.0, first=None, last=None, end=12.3, n=None,
             want=8, error="http 503"),                          # refused
        done(5, 12.0, first=12.2, last=13.0, end=13.0, n=7, want=8),  # short
        Timeline(index=6, due=15.0, sent=15.0, n_prompt=10,
                 want_tokens=5),                                 # cut: no end
    ]
    timelines[0].chunk_times = [10.5 + 0.1 * i for i in range(21)]
    timelines[2].chunk_times = [5.5 + 0.115 * i for i in range(41)]
    timelines[3].chunk_times = [19.5, 19.7, 19.9, 20.2, 20.5]
    timelines[5].chunk_times = [12.2 + 0.1 * i for i in range(7)]  # failed
    s = window_summary(timelines, t0=10.0, seconds=10.0)
    assert (s["attempted"], s["failed"], s["completed"]) == (5, 2, 3)
    assert s["out_tokens_completed"] == 21 + 11 + 41
    # streamed inside the window, whenever the request began or ended:
    # request 0 all 21, request 2 its last 1 of 41, the straddler 3 of 5;
    # the failed request's 7 chunks add nothing
    assert s["out_tokens_streamed"] == pytest.approx(21 + 1 + 3)
    assert s["metrics"]["out_tok_s"] == pytest.approx(2.5)
    # TTFT from the due time: 500, 200, 500 ms
    assert s["metrics"]["ttft_p50_ms"] == pytest.approx(500.0)
    assert s["metrics"]["ttft_p95_ms"] == pytest.approx(500.0)
    # TPOT: 100, 200, 115 ms
    assert s["metrics"]["tpot_p95_ms"] == pytest.approx(191.5)
    assert s["generator_lag_ms_max"] == pytest.approx(1.0)


def test_a_window_with_nothing_finished_reports_no_latency():
    s = window_summary([], 0.0, 5.0)
    assert s["attempted"] == 0 and "ttft_p50_ms" not in s["metrics"]
    assert s["metrics"]["out_tok_s"] == 0.0


def test_live_decode_tokens_counts_prompt_plus_streamed():
    t = done(0, 0.0, first=1.0, last=2.0, end=2.0, n=4)
    t.chunk_times = [1.0, 1.3, 1.6, 2.0]
    assert live_decode_tokens([t], 0.5) == (0, 0)
    assert live_decode_tokens([t], 1.4) == (1, 102)
    assert live_decode_tokens([t], 2.5) == (0, 0)
