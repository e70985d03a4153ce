"""Device time of the fused sampler program (``sample_rows``) per
traced tick, from the profiler trace, in ms."""
from bench import devtime


def read(run, metric):
    return devtime.module_ms_per_tick(run, "_sample_rows")
