"""Requests preempted (pages freed, replayed later) during the
window."""
from bench import stats


def read(run, metric):
    return sum(r["d_preemptions"] for r in stats.window_ticks(run))
