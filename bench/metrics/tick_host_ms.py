"""The program's own host seconds of the tick's advance loop
(``tick_time["host"]``), per tick of the window, in ms."""
from bench import stats


def read(run, metric):
    ticks = stats.window_ticks(run)
    if not ticks:
        return None
    return 1e3 * sum(r["d_host_s"] for r in ticks) / len(ticks)
