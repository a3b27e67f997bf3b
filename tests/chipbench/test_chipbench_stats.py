"""Tail arithmetic over all requests, with censored TTFTs."""
import numpy as np
import pytest

from chipbench import stats
from chipbench.traffic import RequestRecord


def rec(due, times):
    return RequestRecord(rid=0, prompt=np.zeros(1, np.int32), gen=1,
                         offset=0.0, due=due, token_times=list(times))


def test_ttft_counts_from_due_and_censors_at_close():
    reqs = [rec(1.0, [1.5, 2.0]), rec(2.0, []), rec(3.0, [11.0]),
            rec(20.0, [20.1])]  # due after the close: not in the sample
    sample, censored = stats.ttft_sample(reqs, 0.0, 10.0)
    assert sample == pytest.approx([0.5, 8.0, 7.0])
    assert censored == 2


def test_token_gaps_keep_only_pairs_inside_the_window():
    reqs = [rec(0.0, [0.5, 1.0, 1.5, 10.5]), rec(0.0, [2.0, 4.0])]
    assert stats.token_gaps(reqs, 0.8, 10.0) == pytest.approx([0.5, 2.0])
    assert stats.tokens_in_window(reqs, 0.8, 10.0) == 4


def test_percentile_is_over_all_values():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == pytest.approx(50.5)
    assert stats.percentile(v, 99) == pytest.approx(99.01)
    assert stats.percentile([], 90) is None


def test_counter_delta():
    assert stats.counter_delta({"a": 2.0}, {"a": 7.0}, "a") == 5.0
    assert stats.counter_delta({}, {"b": 1.0}, "b") == 1.0


def test_pool_fill_is_the_mean_share_of_pages_held():
    from types import SimpleNamespace as NS

    steps = [NS(used_pages=10), NS(used_pages=30)]
    assert stats.pool_fill(steps, 41) == pytest.approx(50.0)
    assert stats.pool_fill([], 41) is None
