"""Rule 2 on synthetic client records: events count by the time they
reached the client, and nothing is computed from completed requests'
totals."""

import pytest

from benchmark import counting
from benchmark.counting import RequestRecord

T0, T1 = 100.0, 110.0


def record(submit, times, done=None, ordinal=0, error=None, max_tokens=4, client=0):
    return RequestRecord(client=client, ordinal=ordinal, turn=0, prompt_tokens=10,
                         max_tokens=max_tokens, t_due=submit, t_submit=submit,
                         token_times=list(times), t_done=done, n_out=len(times), error=error)


def test_tokens_before_and_after_the_window_do_not_count():
    records = [
        record(95.0, [99.0, 99.5, 100.0, 101.0], done=101.0),       # straddles t0
        record(104.0, [105.0, 106.0, 107.0, 108.0], done=108.0),    # inside
        record(108.0, [109.0, 109.9, 110.0, 111.0], done=111.0),    # straddles t1
    ]
    assert counting.tokens_in_window(records, T0, T1) == 2 + 4 + 2
    e2e = counting.serving_end_to_end(records, T0, T1)
    assert e2e["serve_tokens_per_s"] == pytest.approx(0.8)


def test_first_tokens_count_by_arrival_not_by_submission():
    records = [
        record(95.0, [99.0], done=99.0),              # first token before t0
        record(98.0, [103.0, 104.0], done=104.0),     # sent before, arrived inside
        record(109.0, [112.0], done=112.0),           # sent inside, arrived after
    ]
    assert counting.ttfts_in_window(records, T0, T1) == [5.0]
    assert counting.serving_end_to_end(records, T0, T1)["ttft_mean_ms"] == pytest.approx(5000.0)


def test_tpot_is_per_request_first_to_last_and_needs_the_finish_inside():
    records = [
        record(101.0, [102.0, 102.5, 103.0, 105.0], done=105.0),   # (105 - 102) / 3
        record(101.0, [108.0, 109.0, 110.5], done=110.5),          # finished after t1
        record(101.0, [103.0], done=103.0, max_tokens=1),          # one token: no gap
    ]
    assert counting.tpots_in_window(records, T0, T1) == [pytest.approx(1.0)]
    assert counting.serving_end_to_end(records, T0, T1)["tpot_mean_ms"] == pytest.approx(1000.0)


def test_abandoned_requests_are_neither_attempted_nor_failed():
    answers = {(0, 0): [1, 2, 3, 4], (0, 1): [1, 2], (1, 0): [1, 2, 3, 4], (1, 1): [1, 2, 3, 9999]}
    records = [
        record(101.0, [102, 103, 104, 105], done=105.0, ordinal=0),
        record(106.0, [108.0, 109.0], done=None, ordinal=1),                 # cut by t1
        record(101.0, [102, 103, 104, 105], done=111.0, ordinal=0, client=1),  # ended after t1
        record(102.0, [103, 104, 105, 106], done=106.0, ordinal=1, client=1),  # bad token
    ]
    finished = counting.finished_in_window(records, T0, T1)
    assert [(r.client, r.ordinal) for r in finished] == [(0, 0), (1, 1)]
    failed = counting.failures(records, T0, T1, vocab=100, answers=answers)
    assert len(failed) == 1 and "outside the vocabulary" in failed[0]


def test_errors_and_short_answers_are_failures():
    answers = {(0, 0): [1, 2], (0, 1): []}
    records = [
        record(101.0, [102.0, 103.0], done=103.0, ordinal=0),
        record(104.0, [], done=105.0, ordinal=1, error="TimeoutError: x"),
    ]
    failed = counting.failures(records, T0, T1, vocab=100, answers=answers)
    assert len(failed) == 2
    assert "2 tokens, asked 4" in failed[0] and "TimeoutError" in failed[1]


def test_percentile_and_mean():
    assert counting.percentile([], 50) is None and counting.mean([]) is None
    assert counting.percentile([3, 1, 2], 50) == 2
    assert counting.percentile(list(range(101)), 90) == 90
