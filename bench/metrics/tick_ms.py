"""Host-clock span of one scheduler tick (which ends in a blocking
transfer of its tokens), mean over the window's ticks, in ms."""
from bench import stats


def read(run, metric):
    ticks = stats.window_ticks(run)
    if not ticks:
        return None
    return 1e3 * sum(r["t1"] - r["t0"] for r in ticks) / len(ticks)
