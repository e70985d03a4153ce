"""Programs traced (and so compiled, or fetched from the persistent
cache) inside the window; warm-up should leave none."""


def read(run, metric):
    return run.compiles.traced
