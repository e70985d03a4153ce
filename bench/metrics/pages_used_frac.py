"""Pages in use over the pool's pages, averaged over the window's
ticks (the program's ``_page_ticks``), in %."""
from bench import stats


def read(run, metric):
    ticks = stats.window_ticks(run)
    if not ticks:
        return None
    used = sum(r["d_page_ticks"] for r in ticks)
    return 100.0 * used / (len(ticks) * run.num_pages)
