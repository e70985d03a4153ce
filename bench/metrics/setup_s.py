"""Set-up: process start to window open (weights, pool, warm-up of the
cell's shapes, pre-roll population), on the host clock."""


def read(run, metric):
    return run.setup_s
