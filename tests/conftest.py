"""Lock jax to the single host CPU device before any test import can
touch dry-run machinery (which sets XLA_FLAGS for its own process), and
provide a per-test timeout fallback when pytest-timeout is missing."""
import signal
import threading

import jax
import pytest

_ = jax.devices()  # initialize backend: tests must see exactly 1 device


class FakeClock:
    """Deterministic stand-in for the schedulers' injectable monotonic
    clock: time moves only when a test calls ``advance()``, so deadline
    and latency-window tests never real-sleep."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        assert dt >= 0, "monotonic clocks do not rewind"
        self.t += dt
        return self.t


@pytest.fixture
def fake_clock():
    return FakeClock()


# Dynamic twin of repro-lint's static R2 sync-discipline rule: the
# static allowlist (rules/determinism.py ALLOWED_SYNC_SITES) names the
# sanctioned blocking-transfer call sites; this guard asserts the
# runtime counters those sites increment stay within the DESIGN.md §4
# budget — ≤1 pooled-controller sync per tick riding ≤1 blocking
# transfer per tick — on EVERY scheduler any scheduler-level test
# constructs. The two can't drift apart silently: a new sync site
# trips the lint, a new per-tick transfer trips this.
_SYNC_GUARDED_FILES = ("test_scheduler.py", "test_paged.py")


@pytest.fixture(autouse=True)
def _sync_budget_guard(request, monkeypatch):
    if getattr(request.node, "fspath", None) is None or \
            request.node.fspath.basename not in _SYNC_GUARDED_FILES:
        yield
        return
    from repro.serving import scheduler as sched_mod
    created = []
    orig_init = sched_mod._SchedulerBase.__init__

    def _tracking_init(self, *args, **kwargs):
        orig_init(self, *args, **kwargs)
        created.append(self)

    monkeypatch.setattr(sched_mod._SchedulerBase, "__init__",
                        _tracking_init)
    yield
    for sched in created:
        c = sched.counters
        # one pooled dispatch per tick at most, and every dispatch's
        # outputs ride exactly one blocking transfer
        assert c["controller_syncs"] <= c["controller_dispatches"] \
            <= sched.ticks, (
            "pooled-controller sync budget exceeded: "
            f"{c['controller_syncs']} syncs / "
            f"{c['controller_dispatches']} dispatches over "
            f"{sched.ticks} ticks (≤1 per tick, DESIGN.md §4)")
        # the fused tick's one sanctioned transfer: THE tokens/
        # controller/finite transfer (sampling keys stay on the device)
        assert c["host_syncs"] <= sched.ticks, (
            f"host-sync budget exceeded: {c['host_syncs']} blocking "
            f"transfers over {sched.ticks} ticks (≤1 per tick)")

try:
    import pytest_timeout  # noqa: F401
    _HAVE_TIMEOUT_PLUGIN = True
except ImportError:
    _HAVE_TIMEOUT_PLUGIN = False


def pytest_addoption(parser):
    if not _HAVE_TIMEOUT_PLUGIN:
        # claim pytest-timeout's ini keys so plugin-absent runs stay
        # clean under --strict-config (no "unknown config option")
        parser.addini("timeout", "per-test timeout (pytest-timeout "
                      "fallback)", default="900")
        parser.addini("timeout_method", "ignored by the fallback",
                      default="signal")


if not _HAVE_TIMEOUT_PLUGIN and hasattr(signal, "SIGALRM"):
    # degraded stand-in for pytest-timeout (pyproject sets timeout=900):
    # a SIGALRM per test so a hung fuzz case raises loudly instead of
    # wedging the run. Main-thread only; the real plugin supersedes it.

    @pytest.fixture(autouse=True)
    def _fallback_test_timeout(request):
        if threading.current_thread() is not threading.main_thread():
            yield
            return
        marker = request.node.get_closest_marker("timeout")
        limit = int(float(marker.args[0])) if (marker and marker.args) \
            else int(float(request.config.getini("timeout")))

        def _on_alarm(signum, frame):
            raise TimeoutError(
                f"test exceeded fallback timeout of {limit}s "
                "(install pytest-timeout for precise per-test caps)")

        prev = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(limit)
        try:
            yield
        finally:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, prev)
