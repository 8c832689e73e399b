"""Percentile and due-time arithmetic of the load generator's records."""

import array

import pytest

import _paths  # noqa: F401
from lib import stats
from lib.loadgen import Req


def req(due, times, sent=None, status="ok", prompt=4, max_tokens=None,
        ended=None):
    r = Req(0, [7] * prompt, max_tokens or max(1, len(times)), due=due)
    r.times = array.array("d", times)
    r.sent = due if sent is None else sent
    r.status, r.ended = status, ended
    return r


@pytest.mark.parametrize("p,want", [(0, 1.0), (50, 2.5), (90, 3.7),
                                    (100, 4.0), (25, 1.75)])
def test_percentile_is_linear_between_ranks(p, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], p) == pytest.approx(want)


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 50) is None


def test_ttft_counts_from_the_due_instant_not_from_sending():
    # due at 1.0, sent late at 1.4, first token at 1.5: the user waited 0.5
    r = req(1.0, [1.5, 1.6], sent=1.4)
    assert stats.ttfts([r], 10) == [pytest.approx(0.5)]
    assert stats.lags([r], 10) == [pytest.approx(0.4)]


def test_only_requests_due_in_the_window_are_attempted():
    before = req(-0.5, [0.2, 0.3])
    inside = req(2.0, [2.2])
    after = req(10.0, [10.1])
    assert stats.attempts([before, inside, after], 10) == (1, 0)
    assert stats.ttfts([before, inside, after], 10) == [pytest.approx(0.2)]


def test_tokens_count_where_they_arrive_not_where_the_request_was_due():
    before = req(-0.5, [-0.1, 0.2, 0.3])        # two of three inside
    late = req(9.5, [9.8, 10.2])                # one of two inside
    assert stats.tokens_in([before, late], 0, 10) == 3
    e2e = stats.end_to_end([before, late], 10)
    assert e2e["output_tok_s"] == pytest.approx(0.3)


def test_gaps_belong_to_the_window_of_their_later_token():
    r = req(-1.0, [-0.5, 0.5, 0.75, 10.5])
    assert stats.gaps([r], 10) == [pytest.approx(1.0), pytest.approx(0.25)]


def test_a_request_without_a_first_token_counts_as_the_worst():
    ok = req(1.0, [1.2])
    failed = req(2.0, [], status="failed: HTTP 429", ended=2.1)
    cut = req(3.0, [], status="cut", ended=9.0)
    first = stats.ttfts([ok, failed, cut], 10)
    assert sorted(first) == [pytest.approx(0.2), pytest.approx(6.0),
                             pytest.approx(6.0)]
    assert stats.attempts([ok, failed, cut], 10) == (3, 1)


def test_a_stream_that_ends_short_is_a_failure():
    short = req(1.0, [1.1, 1.2], status="failed: stream ended after 2 of 5",
                max_tokens=5)
    assert stats.attempts([short], 10) == (1, 1)


def test_itl_tail_and_medians_in_milliseconds():
    r = req(0.0, [0.1 + 0.01 * i for i in range(101)])
    e2e = stats.end_to_end([r], 10)
    assert e2e["ttft_p50_ms"] == pytest.approx(100.0)
    assert e2e["itl_p95_ms"] == pytest.approx(10.0)
