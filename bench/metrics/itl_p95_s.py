"""The delivery gaps of ``itl_p50_s``, 95th percentile."""
from bench import stats


def read(run, metric):
    return stats.percentile(stats.itl_gaps(stats.client_streams(run),
                                           run.w0, run.w1), 95)
