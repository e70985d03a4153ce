"""From when a request was due to its first delivered token, 90th
percentile over every request due in the window. A request still
without a token when the run stopped waiting counts with the time it
had waited by then (a lower bound)."""
from bench import stats


def read(run, metric):
    vals, failed = stats.ttfts(run.due, stats.client_streams(run),
                               run.w0, run.w1)
    vals += [run.t_close - d for r, d in run.due.items()
             if run.w0 <= d < run.w1 and not run.client.events.get(r)]
    return stats.percentile(vals, 90)
