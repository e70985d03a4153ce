"""Committed answer tokens delivered to client streams during the
window, per second of the window."""
from bench import stats


def read(run, metric):
    n = stats.window_tokens(stats.client_streams(run), run.w0, run.w1)
    return n / run.seconds
