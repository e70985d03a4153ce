"""R1 replay-determinism and R2 sync-discipline.

Both rules mechanize serving contracts that used to live only in prose:

* DESIGN.md §8 — a preempted / faulted / cancelled-and-retried request
  replays **token-for-token from its original submission RNG**, and all
  request-visible latency flows through the injectable ``clock=``
  (PR 7/PR 8). A stray wall-clock read or ambient-RNG draw in the
  serving/core layers silently breaks that equivalence.
* DESIGN.md §4 — the fused tick performs **at most one blocking
  controller-carrying transfer per tick** (PR 3), and no other: the
  sampling keys are derived on the device. Any new ``.item()`` /
  ``device_get`` / host-coercion in a tick-path module is either a
  regression or a new sanctioned site that must be added to the
  explicit allowlist below (and to the dynamic counter twin in
  tests/conftest.py).
"""
from __future__ import annotations

import ast
from typing import Iterable

from repro.analysis.core import FileContext, Finding, Rule, register


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression ('np.random.rand',
    'time.monotonic', '' when not a plain name chain)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


_WALLCLOCK = {
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns", "time.sleep",
}
_DATETIME_ATTRS = {"now", "utcnow", "today"}
# module-level stdlib `random` draws share one ambient global state
_RANDOM_FNS = {
    "random", "randint", "randrange", "choice", "choices", "shuffle",
    "sample", "uniform", "gauss", "normalvariate", "betavariate",
    "getrandbits", "randbytes", "triangular", "expovariate",
}
# numpy legacy global-RNG draws (np.random.<fn>)
_NP_GLOBAL_FNS = {
    "rand", "randn", "randint", "random", "random_sample", "choice",
    "shuffle", "permutation", "uniform", "normal", "standard_normal",
    "beta", "binomial", "poisson", "exponential", "bytes",
}
_MISC_ENTROPY = {"uuid.uuid4", "os.urandom", "secrets.token_bytes",
                 "secrets.token_hex", "secrets.randbelow"}


@register
class ReplayDeterminism(Rule):
    """R1: no ambient wall-clock or un-seeded RNG in replay-critical
    modules (``serving/``, ``core/``, ``launch/serve.py``)."""

    id = "replay-determinism"
    severity = "error"
    contract = ("serving/ + core/ + launch/serve.py replay token-for-token "
                "from the submission RNG; wall-clock goes through the "
                "injectable clock= (DESIGN.md §8)")
    rationale = (
        "Preemption, fault retry, and cancellation all REPLAY a request "
        "from its original submission RNG and assert token-for-token "
        "equality; SLO/latency logic reads time only through the "
        "scheduler's injectable clock= so tests can advance a FakeClock. "
        "A time.time()/datetime.now() call or an un-seeded random/"
        "np.random draw in these modules produces values that differ "
        "between the first run and the replay (or between test and "
        "production), breaking replay equivalence with no test failing. "
        "Referencing time.monotonic as the clock= DEFAULT is fine — only "
        "direct calls are flagged. Seeded generators "
        "(np.random.default_rng(seed), jax.random with explicit keys) "
        "are exempt by construction.")
    example = ("def _watchdog(self):\n"
               "    now = time.monotonic()   # R1: bypasses self.clock\n"
               "    ...\n"
               "    jitter = np.random.random()   # R1: ambient RNG\n")

    def applies(self, ctx: FileContext) -> bool:
        return (ctx.in_path("serving") or ctx.in_path("core")
                or ctx.name == "serve.py")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            if dotted in _WALLCLOCK:
                yield self.finding(
                    ctx, node,
                    f"wall-clock call `{dotted}()` outside the injectable "
                    "clock= — route request-visible time through the "
                    "scheduler clock (replay/FakeClock contract)")
            elif dotted in _MISC_ENTROPY:
                yield self.finding(
                    ctx, node,
                    f"`{dotted}()` draws ambient entropy — replay from "
                    "the submission RNG cannot reproduce it")
            elif (dotted.split(".")[-1] in _DATETIME_ATTRS
                  and "datetime" in dotted.split(".")[:-1]
                  or dotted in ("date.today",)):
                yield self.finding(
                    ctx, node,
                    f"`{dotted}()` reads the wall clock — route through "
                    "the injectable clock= or stamp outside serving/core")
            elif (dotted.startswith("random.")
                  and dotted.split(".", 1)[1] in _RANDOM_FNS):
                yield self.finding(
                    ctx, node,
                    f"`{dotted}()` uses the ambient global random state — "
                    "derive from the request's submission RNG instead")
            elif dotted == "random.Random" and not node.args:
                yield self.finding(
                    ctx, node,
                    "`random.Random()` without a seed is entropy-seeded — "
                    "pass an explicit seed derived from the submission RNG")
            elif (dotted.startswith(("np.random.", "numpy.random."))
                  and dotted.split(".")[-1] in _NP_GLOBAL_FNS):
                yield self.finding(
                    ctx, node,
                    f"`{dotted}()` draws from numpy's global RNG — use a "
                    "seeded np.random.default_rng(...) (see "
                    "serving/faults.py for the convention)")
            elif (dotted.split(".")[-1] in ("default_rng", "RandomState")
                  and ".random" in dotted.rsplit(".", 1)[0] + "."
                  and not node.args and not node.keywords):
                yield self.finding(
                    ctx, node,
                    f"`{dotted}()` with no seed is entropy-seeded — pass "
                    "an explicit seed (FaultPlan seeds "
                    "default_rng([seed, site, tick]))")


# The sanctioned blocking-transfer sites: (filename, enclosing function).
# Everything here was audited in the ISSUE 9 sync sweep; the dynamic twin
# (tests/conftest.py `_sync_budget_guard`) asserts the runtime counters
# these sites increment stay within the ≤1-controller-sync-per-tick
# budget, so this list and runtime truth cannot drift apart silently.
ALLOWED_SYNC_SITES = {
    # the fused tick's one sanctioned transfer: THE blocking transfer
    # carrying tokens + picked log-probs + pooled controller outputs +
    # the finite mask (DESIGN.md §4; sampling keys stay on the device)
    ("scheduler.py", "tick"),
    # engine-loop twin of the tick sync: the single-request path reads
    # its own sampled tokens back each step by design
    ("strategies.py", "sample_and_advance"),
}


@register
class SyncDiscipline(Rule):
    """R2: host-sync constructs in tick-path modules only at allowlisted
    sites (or baselined with a reason)."""

    id = "sync-discipline"
    severity = "error"
    contract = ("tick-path modules (engine.py, scheduler.py, "
                "strategies.py, core/kappa.py) make ≤1 controller-"
                "carrying blocking transfer per tick (DESIGN.md §4)")
    rationale = (
        "PR 3 collapsed the per-request controller host reads into ONE "
        "pooled dispatch whose outputs ride the tick's single blocking "
        "device_get, and the sampling keys never leave the device. "
        "Every `.item()`, `jax.device_get`, `block_until_ready`, "
        "`np.asarray`, or float()/int() coercion of a jax value in a "
        "tick-path module is a potential hidden round-trip that "
        "serializes host and device again. New sites must be allowlisted "
        "in rules/determinism.py:ALLOWED_SYNC_SITES (true per-tick "
        "transfers, mirrored by the conftest counter twin) or baselined "
        "with a reason (host-side numpy on host data). np.asarray on "
        "genuinely-host data is flagged too — statically "
        "indistinguishable, and the audit trail is the point.")
    example = ("def step(self, logits, ...):\n"
               "    # R2: per-request blocking read inside the tick\n"
               "    alive = np.asarray(self.state.alive)\n"
               "    if float(jnp.sum(alive)) == 1.0:  # R2: host coercion\n"
               "        ...\n")

    TICK_MODULES = ("engine.py", "scheduler.py", "strategies.py", "kappa.py")

    def applies(self, ctx: FileContext) -> bool:
        return (ctx.name in ("engine.py", "scheduler.py", "strategies.py")
                and ctx.in_path("serving")) \
            or (ctx.name == "kappa.py" and ctx.in_path("core"))

    def _allowed(self, ctx: FileContext, node: ast.AST) -> bool:
        fn = ctx.enclosing_function(node)
        return fn is not None and (ctx.name, fn.name) in ALLOWED_SYNC_SITES

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            msg = self._classify(node)
            if msg and not self._allowed(ctx, node):
                yield self.finding(
                    ctx, node, msg + " — tick-path syncs are allowlisted "
                    "in ALLOWED_SYNC_SITES or baselined with a reason "
                    "(≤1-transfer-per-tick contract, DESIGN.md §4)")

    @staticmethod
    def _mentions_jax(node: ast.AST) -> bool:
        return any(isinstance(n, ast.Name) and n.id in ("jnp", "jax")
                   for n in ast.walk(node))

    def _classify(self, node: ast.Call) -> str:
        func = node.func
        dotted = _dotted(func)
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and not node.args:
            return "`.item()` is a blocking device->host transfer"
        if isinstance(func, ast.Attribute) \
                and func.attr == "block_until_ready":
            return "`.block_until_ready()` blocks on device completion"
        if dotted in ("jax.device_get", "jax.block_until_ready"):
            return f"`{dotted}(...)` is a blocking transfer"
        if dotted in ("np.asarray", "numpy.asarray"):
            return ("`np.asarray(...)` blocks when handed a device "
                    "array")
        if isinstance(func, ast.Name) and func.id in ("float", "int",
                                                      "bool") \
                and node.args and self._mentions_jax(node.args[0]):
            return (f"`{func.id}(...)` of a jax expression forces a "
                    "blocking scalar transfer")
        return ""
