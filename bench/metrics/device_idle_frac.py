"""Share of the traced stretch in which no operation ran on the device
(1 - busy / traced seconds, from the profiler trace), in %."""


def read(run, metric):
    ts = run.trace_summary
    if not ts or ts["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ts["busy_s"] / ts["window_s"])
