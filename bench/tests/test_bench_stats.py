"""Arithmetic of the end-to-end metrics: due-time TTFT, grouped
deliveries for ITL, window rates and the tails of drained requests."""
from bench import stats


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 95) == 95
    assert stats.percentile([3.0], 99) == 3.0
    assert stats.percentile([], 50) is None


def test_deliveries_group_tokens_emitted_together():
    # (emitted_at, received_at, n): a flush of 3 tokens at decision
    ev = [(1.0, 1.01, 1), (1.0, 1.01, 1), (1.0, 1.02, 1), (2.0, 2.05, 1),
          (3.0, 3.01, 1)]
    assert stats.deliveries(ev) == [(1.01, 3), (2.05, 1), (3.01, 1)]


def test_window_tokens_and_gaps():
    streams = {1: [(0.5, 4), (1.5, 1), (2.5, 1)],
               2: [(1.2, 2), (3.5, 1)]}
    assert stats.window_tokens(streams, 1.0, 3.0) == 1 + 1 + 2
    # gaps that end in [1, 3): 1.5-0.5, 2.5-1.5 (stream 1); none of 2's
    assert sorted(stats.itl_gaps(streams, 1.0, 3.0)) == [1.0, 1.0]


def test_ttft_from_due_time_and_failures():
    due = {1: 1.0, 2: 2.0, 3: 2.5, 4: 5.0}
    streams = {1: [(1.75, 1)], 2: [(4.0, 2)], 4: [(5.1, 1)]}
    vals, failed = stats.ttfts(due, streams, 0.0, 3.0)
    assert sorted(vals) == [0.75, 2.0]      # request 4 was due after
    assert failed == 1                       # request 3 never got a token
