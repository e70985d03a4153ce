"""The phase reduction of a traced run (``bench/phases.py``): device
idle time split at the program's span boundaries and named by the
innermost span, the ten longest gaps named by their phase, the clock
check that catches a device plane shifted past the blocking wait that
waited for it, and a toy cell run end to end on the CPU with the
scheduler's per-phase host times."""
from bench import phases, trace
from bench.tests import toy

OPS, MODS = "XLA Ops", "XLA Modules"
# one tick: admission 0.0-0.1, step enqueue 0.1-0.2, key wait 0.2-0.3,
# sampler enqueue 0.3-0.35, blocking sync 0.35-0.8, host 0.8-1.0
HOST = [("bench.tick", 0.0, 1.0), ("serve.tick", 0.0, 1.0),
        ("serve.admit", 0.0, 0.1), ("serve.step", 0.1, 0.2),
        ("serve.keys_wait", 0.2, 0.3), ("serve.sample", 0.3, 0.35),
        ("serve.sync", 0.35, 0.8), ("serve.host", 0.8, 1.0),
        ("serve.emit", 0.9, 1.0)]
DEVICE = {"/device:TPU:0": [
    (MODS, "jit_fused_decode", 0.15, 0.5), (OPS, "%while.4 = f32[1]", 0.15,
                                            0.5),
    (MODS, "jit__sample_rows", 0.5, 0.7), (OPS, "%sort.1 = f32[1]", 0.5,
                                           0.7),
]}


def _close(d, want):
    assert set(d) == set(want), d
    for k, v in want.items():
        assert abs(d[k] - v) < 1e-9, (k, d[k], v)


def test_idle_is_split_at_span_boundaries():
    out = phases.reduce_phases(DEVICE, HOST, 0.0, 1.0)
    # idle: 0.0-0.15 (admit 0.1, step 0.05) and 0.7-1.0 (sync 0.1,
    # host 0.1, emit 0.1)
    _close(out["idle_by_span"], {"serve.admit": 0.1, "serve.step": 0.05,
                                 "serve.sync": 0.1, "serve.host": 0.1,
                                 "serve.emit": 0.1})
    # the longest gap (0.7-1.0) is named at its midpoint, 0.85: host
    assert [n for n, _ in out["idle_gaps"]] == ["serve.host", "serve.admit"]
    assert abs(out["longest_gap"]["seconds"] - 0.3) < 1e-9
    _close(out["longest_gap"]["split"], {"serve.sync": 0.1,
                                         "serve.host": 0.1,
                                         "serve.emit": 0.1})
    assert out["clock_skew_ms"] == 0.0
    assert out["serve_ticks"] == 1 and out["dark_ticks"] == 0


def test_program_spans_leave_the_trace_reduction_as_it_was():
    """``bench/trace.py`` reads the harness's spans alone: the program's
    spans move none of its outputs."""
    bench_only = [s for s in HOST if s[0].startswith("bench.")]
    a = trace.reduce_events(DEVICE, bench_only, 0.0, 1.0)
    b = trace.reduce_events(DEVICE, HOST, 0.0, 1.0)
    for k in ("busy_s", "window_s", "ops", "shapes", "modules", "devices"):
        assert a[k] == b[k], k
    assert [w for _, w in a["idle_gaps"]] == [w for _, w in b["idle_gaps"]]


def test_gap_outside_every_span_and_shifted_device_clock():
    host = [("serve.tick", 0.0, 1.0), ("serve.sync", 0.35, 0.8)]
    # the device plane 0.2 s late: the sampler that the sync waited for
    # now ends 0.1 s after the wait ended, and the stretch after the
    # tick is idle under no span
    late = {"/device:TPU:0": [(l, n, a + 0.2, b + 0.2)
                              for l, n, a, b in DEVICE["/device:TPU:0"]]}
    out = phases.reduce_phases(late, host, 0.0, 1.2)
    assert abs(out["clock_skew_ms"] - 100.0) < 1e-6
    assert abs(out["idle_by_span"]["no host span"] - 0.2) < 1e-9
    assert phases.reduce_phases(DEVICE, host, 0.0, 1.0)["clock_skew_ms"] \
        == 0.0
    # an operation that spans the whole tick leaves it lit
    lit = phases.reduce_phases({"/device:TPU:0": [(OPS, "f", -1.0, 2.0)]},
                               host, 0.0, 1.0)
    assert lit["dark_ticks"] == 0
    # a tick in which the device ran nothing
    dark = phases.reduce_phases({"/device:TPU:0": []}, host, 0.0, 1.0)
    assert dark["dark_ticks"] == 1
    assert dark["clock_skew_ms"] is None
    _close(dark["idle_by_span"], {"serve.tick": 0.55, "serve.sync": 0.45})


def test_window_phase_ms_reads_the_window_ticks():
    ticks = [{"t0": t, "t1": t + d, "traced": False}
             for t, d in ((0.0, 0.5), (1.0, 0.2), (2.0, 0.9), (3.0, 0.1))]
    snaps = [{"admit": a, "frontend": f} for a, f in
             ((0.1, 0.0), (0.3, 0.01), (0.6, 0.03), (1.6, 0.04))]
    # window [1, 3): ticks 1 and 2
    per_tick = phases.tick_phases(ticks, snaps, 1.0, 3.0)
    _close(phases.window_phase_ms(per_tick),
           {"admit": 1e3 * (0.2 + 0.3) / 2, "frontend": 1e3 * 0.03 / 2})
    top = phases.longest_tick(per_tick, 1.0)
    assert abs(top["ms"] - 900.0) < 1e-9 and top["from_window_open_s"] == 1.0
    _close(top["phases_ms"], {"admit": 300.0, "frontend": 20.0})
    assert phases.tick_phases(ticks, snaps, 5.0, 6.0) == []
    assert phases.longest_tick([], 5.0) is None


def test_toy_cell_phases_on_cpu(tmp_path):
    root = toy.make_root(tmp_path, limits=0.05)
    out = phases.run("toy.batch", 2 ** 33 + 78, 2.0, root=root,
                     require_tpu=False)
    assert out["result"]["correct"], out["result"]["checks"]
    ph = out["phases"]
    ms = ph["window_phase_ms"]
    assert set(ms) >= {"tick", "admit", "pages", "step", "sync", "host",
                       "frontend"}
    assert ph["prep_host_ms"] > 0 and ph["frontend_ms"] > 0
    assert ph["tick_ms_traced"] > 0 and ph["ticks_untraced"] > 0
    # the CPU has no device plane: the spans are there, the device not
    assert ph["serve_ticks"] > 0 and ph["dark_ticks"] == ph["serve_ticks"]
    assert "keys_idle_ms" not in ph or ph["keys_idle_ms"] == 0.0
    assert ph["span_cost_us"] > 0
    assert ph["longest_tick"]["ms"] >= ph["tick_ms_untraced"]
    assert all(ms >= 0 for ms in ph["gc_window_ms"])
