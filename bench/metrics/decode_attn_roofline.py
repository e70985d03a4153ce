"""Paged decode attention kernel's share of its roofline: over the
traced ticks, the least time the chip could take for the attention the
decode rows needed (the larger of its operations over the bf16 peak and
its bytes over HBM bandwidth, from ``bench/flops.py``: each row reads
its own ``pos + 1`` keys and values) over the device time of the
kernel's decode calls in the trace, in %."""
from bench import devtime, flops, model
from bench.peaks import roofline_share


def read(run, metric):
    ticks = [r for r in devtime.traced_ticks(run) if r["pos"] is not None]
    sec = devtime.decode_kernel_seconds(run)
    if not ticks or sec <= 0:
        return None
    size = model.dtype_bytes(run.mc)
    f = b = 0.0
    for r in ticks:
        df, db = flops.decode_attn_cost(run.mc, r["pos"], size)
        f, b = f + df, b + db
    return roofline_share(f, b, sec, run.device["kind"])
