"""The metric arithmetic: tails over every request, a failed request
counted as missing, rates over the whole window."""
import pytest

from chipbench import stats
from chipbench.stats import Served


def test_tokens_per_s_runs_to_the_last_finished_request():
    reqs = [Served(due=1.0, token_times=[2.0, 3.0]),
            Served(due=1.5, token_times=[2.0, 4.0, 10.0])]
    # 5 tokens over the 10 s from the window's start at 0 to the last
    assert stats.tokens_per_s(reqs, 0.0) == pytest.approx(0.5)


def test_failed_request_counts_as_missing_in_the_ttft_tail():
    ok = [Served(due=0.0, token_times=[0.1]) for _ in range(9)]
    failed = Served(due=0.0, failed=True)
    # run ends at 50 s: the failed request's ttft runs to it
    assert stats.ttfts(ok + [failed], 50.0)[-1] == 50.0
    assert stats.percentile(stats.ttfts(ok + [failed], 50.0), 100) == 50.0
    assert stats.percentile(stats.ttfts(ok, 50.0), 100) == \
        pytest.approx(0.1)


def test_itl_tail_is_over_every_gap_of_every_request():
    a = Served(due=0, token_times=[0.0, 0.01, 0.02, 0.03])
    b = Served(due=0, token_times=[0.0, 0.5])
    gaps = stats.inter_token_gaps([a, b])
    assert sorted(gaps) == pytest.approx([0.01, 0.01, 0.01, 0.5])
    e2e = stats.end_to_end([a, b], 0.0, 1.0)
    assert e2e["itl_p95_ms"] == pytest.approx(
        stats.percentile([10, 10, 10, 500], 95))


def test_percentile_is_linear_between_order_statistics():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(11)), 90) == pytest.approx(9.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
