"""Device time from the reduced profiler trace, per traced tick."""
from __future__ import annotations


def traced_ticks(run):
    return [r for r in run.rec.ticks if r["traced"]]


def module_ms_per_tick(run, pattern: str):
    """Device seconds of the compiled programs whose name contains
    ``pattern``, per traced tick, in ms; None without a trace."""
    ts = run.trace_summary
    n = len(traced_ticks(run))
    if not ts or not n:
        return None
    sec = sum(v for k, v in ts["modules"].items() if pattern in k)
    return 1e3 * sec / n if sec > 0 else None


# the paged attention kernel's custom call is named after its jitted
# wrapper in every program that inlines it; its output is
# (rows, KV heads, chunk tokens x query heads per KV head, head dim)
KERNEL_OP = "_paged_attn_jit"


def decode_kernel_seconds(run):
    """Device seconds of the paged kernel's decode calls (one token a
    row: the output's third dim is the query heads per KV head), in the
    decode-only and the fused programs alike."""
    ts = run.trace_summary
    if not ts:
        return 0.0
    group = run.mc["num_attention_heads"] // run.mc["num_key_value_heads"]
    return sum(v for k, v in ts["ops"].items()
               if k.startswith(KERNEL_OP) and len(ts["shapes"][k]) == 4
               and ts["shapes"][k][2] == group)
