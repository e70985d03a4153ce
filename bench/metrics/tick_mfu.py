"""The whole step's share of the chip's bf16 peak: the operations the
window's ticks required (matmuls, attention and unembedding of every
decode row and prefill chunk they fed, from ``bench/flops.py``) over
the window's seconds times the peak, in %."""
from bench import flops, stats


def read(run, metric):
    ticks = stats.window_ticks(run)
    if not ticks or run.peaks is None:
        return None
    total = 0.0
    for r in ticks:
        if r["pos"] is not None and len(r["pos"]):
            total += flops.decode_flops(run.mc, r["pos"])
        for p0, c in r["chunks"]:
            total += flops.chunk_flops(run.mc, p0, c)
    return 100.0 * total / (run.seconds * run.peaks["bf16_flops"])
