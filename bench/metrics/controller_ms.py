"""Device time of the pooled KAPPA controller program per traced tick,
from the profiler trace, in ms."""
from bench import devtime


def read(run, metric):
    return devtime.module_ms_per_tick(run, "_pooled_kappa_tick")
