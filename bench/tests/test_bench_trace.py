"""Reduction of a profiler trace to busy time, per-op and per-program
device time, and the longest idle gaps with the host span open in
each."""
from bench import trace


def test_reduce_events():
    ops = "XLA Ops"
    kernel = ("%_paged_attn_jit.8 = f32[104,2,6,128]{3,2,1,0:T(8,128)S(1)} "
              "custom-call(s32[8320]{0:T(1024)S(1)} %copy-done.1)")
    device = {"/device:TPU:0": [
        ("XLA Modules", "jit_decode_step", 1.0, 1.5),
        (ops, "%fusion.1 = bf16[104,1536]{1,0} fusion(%p)", 1.0, 1.2),
        (ops, kernel, 1.1, 1.4),                            # overlaps
        (ops, "fusion.2", 1.7, 1.8),
        (ops, "%fusion.1 = bf16[104,1536]{1,0} fusion(%p)", 2.6, 2.9),
    ]}
    host = [("bench.tick", 0.9, 1.95), ("bench.tick", 2.0, 3.0)]
    out = trace.reduce_events(device, host, 0.9, 3.0)
    # busy: [1.0, 1.4] + [1.7, 1.8] + [2.6, 2.9]
    assert abs(out["busy_s"] - 0.8) < 1e-9
    assert abs(out["window_s"] - 2.1) < 1e-9
    assert abs(out["ops"]["fusion.1"] - 0.5) < 1e-9
    assert abs(out["ops"]["_paged_attn_jit.8"] - 0.3) < 1e-9
    assert out["shapes"]["_paged_attn_jit.8"] == (104, 2, 6, 128)
    assert out["shapes"]["fusion.2"] == ()
    assert out["modules"] == {"jit_decode_step": 0.5}
    # gaps: 0.9-1.0 (tick), 1.4-1.7 (tick), 1.8-2.6 (second tick:
    # midpoint 2.2), 2.9-3.0 (tick)
    widths = [round(w, 6) for _, w in out["idle_gaps"]]
    assert widths == [0.8, 0.3, 0.1, 0.1]
    assert [s for s, _ in out["idle_gaps"]] == ["bench.tick"] * 4


def test_gap_outside_host_spans_is_named():
    device = {"/device:TPU:0": [("XLA Ops", "f", 0.0, 1.0),
                                ("XLA Ops", "f", 2.0, 3.0)]}
    out = trace.reduce_events(device, [], 0.0, 3.0)
    assert out["idle_gaps"] == [["no host span", 1.0]]
    assert out["busy_s"] == 2.0
