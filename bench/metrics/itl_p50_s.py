"""Gap between successive deliveries to a stream after its first
(tokens emitted together count as one delivery), median over the gaps
that end in the window."""
from bench import stats


def read(run, metric):
    return stats.percentile(stats.itl_gaps(stats.client_streams(run),
                                           run.w0, run.w1), 50)
