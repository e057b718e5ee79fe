"""Window accounting and percentile arithmetic, on hand-made records."""

import pytest

from benchmark.harness import stats


def _rec(due, tokens, phase="window", ok=True, sent=None):
    return {"phase": phase, "due_s": due, "sent_s": due if sent is None
            else sent, "token_s": tokens, "asked": len(tokens), "ok": ok,
            "error": None}


@pytest.mark.parametrize("values,q,want", [
    ([1.0], 50, 1.0),
    ([1.0, 3.0], 50, 2.0),
    ([1.0, 2.0, 3.0, 4.0], 25, 1.75),
    ([4.0, 1.0, 3.0, 2.0], 90, 3.7),
    (list(range(101)), 99, 99.0),
    ([], 50, None),
])
def test_percentile_is_linear_interpolation(values, q, want):
    got = stats.percentile(values, q)
    assert got == want if want is None else got == pytest.approx(want)


def test_percentile_matches_numpy():
    import numpy as np

    xs = list(np.random.default_rng(0).exponential(30.0, 5000))
    for q in (25, 50, 90, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


SECONDS = 10.0
RECORDS = [
    _rec(-1.0, [-0.8, -0.7, 0.1, 0.3], phase="ramp"),   # ramp, tokens cross 0
    _rec(1.0, [1.2, 1.25, 1.35]),                        # wholly inside
    _rec(9.5, [9.9, 10.4, 10.5]),                        # drains past close
    _rec(10.5, [10.6, 10.7]),                            # due after close
    _rec(2.0, [], ok=False),                             # failed, no token
]


def test_time_to_first_token_counts_requests_due_in_the_window():
    assert stats.ttft_ms(RECORDS, SECONDS) == pytest.approx([200.0, 400.0])


def test_gaps_count_when_the_later_token_is_in_the_window():
    got = sorted(stats.pooled_gaps_ms(RECORDS, SECONDS))
    # ramp: (-0.7 -> 0.1) and (0.1 -> 0.3); inside: 50, 100; drain: none
    assert got == pytest.approx([50.0, 100.0, 200.0, 800.0])


def test_tokens_count_when_emitted_in_the_window():
    assert stats.tokens_in_window(RECORDS, SECONDS) == 2 + 3 + 1


def test_slo_share_counts_failed_requests_as_misses():
    # due in window: the 1.0, 9.5 and 2.0 requests; the failed one misses
    assert stats.slo_share(RECORDS, SECONDS, 1000, 1000) \
        == pytest.approx(100.0 * 2 / 3)
    assert stats.slo_share(RECORDS, SECONDS, 300, 1000) \
        == pytest.approx(100.0 / 3)
    assert stats.slo_share([], SECONDS, 1, 1) is None


def test_lag_is_send_time_minus_due_time():
    recs = [_rec(1.0, [1.1], sent=1.002), _rec(-1.0, [0.1], sent=-0.9)]
    assert stats.lag_ms(recs, SECONDS) == pytest.approx([2.0])


def test_longest_silence_is_over_all_streams():
    recs = [_rec(0.0, [0.1, 0.2, 5.0]), _rec(0.0, [2.0, 2.1])]
    # 0.2 -> 2.0 and 2.1 -> 5.0: the second is longer
    assert stats.longest_silence_ms(recs, SECONDS) == pytest.approx(2900.0)
    assert stats.longest_silence_ms([], SECONDS) is None
