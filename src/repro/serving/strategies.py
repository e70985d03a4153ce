"""Decode strategies: each method's per-step controller state and
selection rule behind one uniform interface (DESIGN.md §3).

A ``DecodeStrategy`` owns everything method-specific — KAPPA's jitted
controller state, BoN's running log-probabilities, ST-BoN's divergence
tracking — while ``RequestState`` holds the method-agnostic host state of
one in-flight request (token log, done mask, RNG stream, byte/token
accounting). The same two classes drive both execution modes:

  * the single-request loop in ``repro.serving.engine`` (one model step
    per request per iteration, cache gathered on compaction), and
  * the continuous-batching scheduler in ``repro.serving.scheduler``
    (one fused model step over a fixed row pool, rows freed on prune).

Because every host-side decision (sampling keys, masking, compaction
order, termination) lives here and is shared verbatim, the scheduler is
token-for-token equivalent to sequential serving given the same
per-request RNG keys and ``max_seq``.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import KappaConfig, ModelConfig
from repro.core import kappa as kappa_lib
from repro.core.signals import reference_log_q
from repro.models import train_logits
from repro.serving import cache as cache_lib
from repro.serving import sampler


@dataclass
class GenResult:
    tokens: List[int]                 # generated tokens of the chosen branch
    chosen_branch: int                # original branch index
    all_tokens: np.ndarray            # (N, T) all branch tokens (-1 pad)
    lengths: np.ndarray               # (N,) live lengths
    logical_tokens: int               # paper-style token count
    compute_tokens: int               # TPU rows actually decoded
    peak_cache_bytes: int             # branch-scaling memory peak
    steps: int
    compactions: List[int] = field(default_factory=list)
    extra: Dict = field(default_factory=dict)
    status: str = "OK"                # terminal status: OK | CANCELLED |
                                      #   TIMEOUT | FAILED | SHED
    n_retries: int = 0                # fault-triggered replays before finish


@dataclass
class StepDecision:
    """What a strategy decided after observing one decode step."""
    counted: np.ndarray               # (rows,) bool — log + logical accounting
    keep: Optional[np.ndarray] = None  # sorted row indices to compact to
    stop: bool = False                # request finished


class TokenLog:
    """Host-side per-branch token buffers surviving compaction."""

    def __init__(self, n: int, max_new: int):
        self.buf = np.full((n, max_new), -1, np.int32)
        self.len = np.zeros((n,), np.int64)

    def append(self, branch_ids: np.ndarray, tokens: np.ndarray,
               active: np.ndarray):
        for row, b in enumerate(branch_ids):
            if active[row]:
                self.buf[b, self.len[b]] = tokens[row]
                self.len[b] += 1


@functools.partial(jax.jit, static_argnums=(1,))
def _bos_log_q(params, cfg: ModelConfig, bos_token, frontend=None):
    """Unconditional reference logits q from the BOS-only context
    (Alg. 2 line 9)."""
    logits, _ = train_logits(params, cfg, bos_token[None, None], frontend)
    return reference_log_q(logits[0, -1])


_kappa_controller = jax.jit(kappa_lib.kappa_step, static_argnums=(4,))


def controller_key(kcfg: KappaConfig) -> KappaConfig:
    """The subset of a KappaConfig the controller math depends on.
    ``max_new_tokens`` is a host-side stopping knob only, so requests
    that differ in nothing else can share one pooled controller (and one
    jit specialization)."""
    return dataclasses.replace(kcfg, max_new_tokens=0)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _pooled_kappa_tick(kcfg: KappaConfig, state, logits, toks, gather_idx,
                       done_prev, reset, slot_active, row_n, log_q, eos_id):
    """ONE device program advancing every pooled kappa controller:

      * re-initialize slots acquired since the last tick (``reset``) with
        their own live-row count (padding rows masked dead);
      * gather each slot's branch logits/tokens from the scheduler's row
        pool (``gather_idx`` maps controller rows to pool rows — dropped
        rows point at row 0 and are dead in the state, so their garbage
        never propagates);
      * force already-done rows' tokens to EOS exactly as
        ``RequestState.advance`` does on host;
      * one vmapped kappa_step over all slots; inactive slots keep their
        (reset) state untouched.

    Returns the new state plus the (alive, traj, cutoff) views the host
    needs — transferred by the caller in the same blocking device_get as
    the sampled tokens, so the controller costs one dispatch and zero
    extra syncs per tick."""
    def sel(mask, a, b):
        return jnp.where(mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b)

    fresh = kappa_lib.init_pool_rows(kcfg, row_n)
    state = jax.tree.map(lambda f, s: sel(reset, f, s), fresh, state)
    step_logits = logits[gather_idx]                      # (S, N, V)
    step_toks = jnp.where(done_prev, eos_id, toks[gather_idx])
    new = kappa_lib.pooled_step(state, step_logits, step_toks, log_q, kcfg)
    new = jax.tree.map(lambda a, b: sel(slot_active, a, b), new, state)
    return new, (new.alive, new.traj, new.cutoff)


class PooledKappaController:
    """Device-resident stacked KappaState shared by every kappa request
    in a scheduler pool (DESIGN.md §4).

    The scheduler acquires a slot per admitted kappa request, builds one
    (slots, fan_out) gather map per tick, and calls :meth:`dispatch`
    once — regardless of how many requests are active. ``publish``
    stores the host copies (fetched by the scheduler inside its existing
    per-tick device_get) that :class:`KappaStrategy` then reads its
    slice of, replacing the per-request ``np.asarray(state.alive)``
    sync that previously dominated scheduler ticks."""

    def __init__(self, params, cfg: ModelConfig, kcfg: KappaConfig, *,
                 slots: int, bos_id: int, frontend=None):
        self.kcfg = kcfg
        self.slots = slots
        self.nmax = kcfg.num_branches
        self.log_q = _bos_log_q(params, cfg, jnp.int32(bos_id),
                                frontend[:1] if frontend is not None else None)
        self.state = kappa_lib.init_pool(kcfg, slots)
        self.free = list(range(slots))
        self.row_n = np.full((slots,), self.nmax, np.int32)
        self.pending_reset = np.zeros((slots,), bool)
        self.slot_active = np.zeros((slots,), bool)
        # mirror defaults come from init_state itself so the values served
        # before a slot's first dispatch can never drift from the device
        init_cut = int(kappa_lib.init_state(kcfg).cutoff)
        self._init_cut = init_cut
        # host mirrors of the per-tick controller outputs
        self.alive = np.zeros((slots, self.nmax), bool)
        self.traj = np.zeros((slots, self.nmax), np.float32)
        self.cutoff = np.full((slots,), init_cut, np.int32)
        self.dispatches = 0

    def acquire(self, n_rows: int) -> int:
        slot = self.free.pop(0)
        self.pending_reset[slot] = True
        self.slot_active[slot] = True
        self.row_n[slot] = n_rows
        self.alive[slot] = np.arange(self.nmax) < n_rows
        self.traj[slot] = 0.0
        self.cutoff[slot] = self._init_cut
        return slot

    def release(self, slot: int) -> None:
        self.slot_active[slot] = False
        self.free.append(slot)
        self.free.sort()

    def dispatch(self, pool_logits, pool_toks, gather_idx: np.ndarray,
                 done_prev: np.ndarray, eos_id: int):
        """One jitted controller step for all active slots; returns the
        DEVICE (alive, traj, cutoff) tuple so the caller can fold it into
        its single blocking transfer for the tick."""
        self.state, out = _pooled_kappa_tick(
            self.kcfg, self.state, pool_logits, pool_toks,
            jnp.asarray(gather_idx), jnp.asarray(done_prev),
            jnp.asarray(self.pending_reset), jnp.asarray(self.slot_active),
            jnp.asarray(self.row_n), self.log_q, jnp.int32(eos_id))
        # a fresh array, not a clear in place: the host-to-device copy of
        # the flags may still be in flight after the dispatch returns
        self.pending_reset = np.zeros_like(self.pending_reset)
        self.dispatches += 1
        return out

    def publish(self, out_host) -> None:
        """Store the host copies of this tick's controller outputs.
        Copied: device_get hands back read-only buffers, and acquire()
        re-initializes a slot's mirror rows in place."""
        alive, traj, cutoff = out_host
        self.alive = np.array(alive)
        self.traj = np.array(traj)
        self.cutoff = np.array(cutoff)


# device-side picked-token log-prob: only the (N,) vector crosses to
# host, not the full (N, V) softmax (the BoN per-step round-trip fix).
# One definition shared with the fused sampler dispatch so the BoN
# single-request path and the scheduler's fused path can never diverge.
_picked_logprob = sampler.picked_logprob


# ------------------------------------------------------------- strategies

class DecodeStrategy:
    """Per-method controller. Subclasses hold all method-specific state;
    the driving loop only sees rows/begin/step/choose."""

    name = "base"
    greedy = False  # argmax sampling instead of temperature sampling
    # strategy consumes the picked-token log-prob each step; the
    # scheduler then computes it for ALL rows in one fused per-tick
    # dispatch and hands each request its slice (see RequestState.advance)
    wants_picked_lp = False
    # strategy reads the raw per-step logits in step() — False lets the
    # scheduler skip the per-request device gather entirely
    needs_step_logits = True

    def rows(self, kcfg: KappaConfig) -> int:
        return kcfg.num_branches

    def begin(self, params, cfg: ModelConfig, kcfg: KappaConfig, *,
              bos_id: int, frontend=None) -> None:
        self.kcfg = kcfg

    def init_done(self, tokens0: np.ndarray, eos_id: int) -> np.ndarray:
        return np.zeros(tokens0.shape, bool)

    def observe_prefill(self, logits0, tokens0: np.ndarray) -> None:
        pass

    def step(self, logits, in_tokens: np.ndarray, out_tokens: np.ndarray,
             branch_ids: np.ndarray, done: np.ndarray,
             done_prev: np.ndarray, step_idx: int,
             picked_lp: Optional[np.ndarray] = None) -> StepDecision:
        raise NotImplementedError

    def choose(self, branch_ids: np.ndarray, done: np.ndarray) -> int:
        return int(branch_ids[0])

    def decided_branch(self, branch_ids: np.ndarray,
                       done: np.ndarray) -> Optional[int]:
        """Branch id whose logged tokens are *committed* — certain to be
        the final ``choose()`` pick however decoding continues — or None
        while selection is still open. The streaming scheduler emits a
        request's tokens only from this branch, which keeps every
        streamed prefix a prefix of the final ``GenResult.tokens``.
        Conservative default: undecided until the terminal flush."""
        return None

    def release_pool(self) -> None:
        """Return any shared pooled-controller slot (no-op by default)."""

    def extra(self) -> Dict:
        return {}


class GreedyStrategy(DecodeStrategy):
    """Single deterministic branch decoded to EOS."""

    name = "greedy"
    greedy = True
    needs_step_logits = False

    def rows(self, kcfg: KappaConfig) -> int:
        return 1

    def init_done(self, tokens0, eos_id):
        return tokens0 == eos_id

    def step(self, logits, in_tokens, out_tokens, branch_ids, done,
             done_prev, step_idx, picked_lp=None):
        # the EOS token itself is logged/counted (emitted before done)
        return StepDecision(counted=~done_prev,
                            stop=bool(done[branch_ids[0]]))

    def decided_branch(self, branch_ids, done):
        return int(branch_ids[0])   # one branch; every token is final


class BoNStrategy(DecodeStrategy):
    """Full Best-of-N with negative-perplexity selection (Kang et al.
    2025): every branch decodes to EOS, keep the most likely one."""

    name = "bon"
    wants_picked_lp = True

    def begin(self, params, cfg, kcfg, *, bos_id, frontend=None):
        super().begin(params, cfg, kcfg, bos_id=bos_id, frontend=frontend)
        n = kcfg.num_branches
        self.sum_lp = np.zeros((n,), np.float64)
        self.count = np.zeros((n,), np.int64)

    def observe_prefill(self, logits0, tokens0):
        picked = _picked_logprob(logits0, jnp.asarray(tokens0))
        self.sum_lp += np.asarray(picked, np.float64)
        self.count += 1

    def step(self, logits, in_tokens, out_tokens, branch_ids, done,
             done_prev, step_idx, picked_lp=None):
        if picked_lp is None:  # single-request path: own (N,) extraction
            picked_lp = np.asarray(
                _picked_logprob(logits, jnp.asarray(out_tokens)))
        step_lp = np.asarray(picked_lp, np.float64)
        newly = ~done_prev  # a branch's own EOS step still counts toward ppl
        # index by branch id: after eager release the step arrays cover
        # only surviving rows, while sum_lp/count stay full fan-out
        self.sum_lp[branch_ids] += np.where(newly, step_lp, 0.0)
        self.count[branch_ids] += newly
        # release EOS'd branches eagerly: a done branch contributes
        # nothing further to its perplexity, so its rows (and KV pages)
        # go back to the pool instead of decoding dead tokens to the end
        alive = ~done[branch_ids]
        keep = np.where(alive)[0] if alive.any() and not alive.all() else None
        return StepDecision(counted=newly, keep=keep, stop=bool(np.all(done)))

    def choose(self, branch_ids, done):
        return int(np.argmax(self._neg_ppl()))

    def decided_branch(self, branch_ids, done):
        # perplexity ranks over the FULL fan-out (eagerly-released EOS
        # branches included), so the winner can change until the last
        # branch finishes — undecided unless the fan-out is one
        return int(branch_ids[0]) if len(self.sum_lp) == 1 else None

    def _neg_ppl(self):
        return self.sum_lp / np.maximum(self.count, 1)

    def extra(self):
        return {"neg_ppl": self._neg_ppl().tolist()}


class STBoNStrategy(DecodeStrategy):
    """Self-Truncation BoN (Wang et al. 2025): decode until the earliest
    point of pairwise difference + a fixed buffer window, then keep the
    branch most consistent with the others and truncate the rest.

    Consistency here = mean pairwise cosine similarity of the branches'
    buffer-window-averaged next-token distributions (the paper uses
    latent-embedding consistency; distribution-space consistency is the
    closest signal our engine already materializes — noted in DESIGN.md).
    """

    name = "stbon"

    def __init__(self, buffer_window: int = 16):
        self.buffer_window = buffer_window

    def begin(self, params, cfg, kcfg, *, bos_id, frontend=None):
        super().begin(params, cfg, kcfg, bos_id=bos_id, frontend=frontend)
        n = kcfg.num_branches
        self.diverged = np.eye(n, dtype=bool)
        self.cutoff_hit: Optional[int] = None
        self.prob_acc = np.zeros((n, cfg.vocab_size), np.float64)
        self.prob_cnt = 0
        self.truncated = False

    def step(self, logits, in_tokens, out_tokens, branch_ids, done,
             done_prev, step_idx, picked_lp=None):
        kcfg = self.kcfg
        keep = None
        if not self.truncated:
            self.diverged |= out_tokens[:, None] != out_tokens[None, :]
            if self.cutoff_hit is None and (np.all(self.diverged)
                                            or step_idx >= kcfg.max_cutoff):
                self.cutoff_hit = step_idx
            if self.cutoff_hit is not None:
                probs = np.asarray(
                    jax.nn.softmax(logits.astype(jnp.float32), axis=-1),
                    np.float64)
                self.prob_acc += probs
                self.prob_cnt += 1
                if step_idx >= self.cutoff_hit + self.buffer_window:
                    keep = np.array([int(np.argmax(self._consistency()))])
                    self.truncated = True
        bids = branch_ids if keep is None else branch_ids[keep]
        stop = (self.truncated and bool(done[bids[0]])) or bool(np.all(done[bids]))
        # EOS-emitting steps count (~done_prev), matching greedy/BoN —
        # a branch's own EOS token is part of its generated sequence
        return StepDecision(counted=~done_prev, keep=keep, stop=stop)

    def _consistency(self):
        mean_p = self.prob_acc / max(self.prob_cnt, 1)
        norm = np.linalg.norm(mean_p, axis=-1, keepdims=True)
        unit = mean_p / np.maximum(norm, 1e-12)
        sim = unit @ unit.T
        n = self.prob_acc.shape[0]
        return (sim.sum(-1) - 1.0) / max(n - 1, 1)

    def choose(self, branch_ids, done):
        """If every branch hit EOS before ``cutoff + buffer_window``
        forced a truncation, select by the consistency accumulated so
        far instead of silently falling back to branch 0. Before any
        divergence (no cutoff, no signal accumulated) all branches are
        prefix-identical, so branch 0 is the deliberate tie-break."""
        if self.truncated:
            return int(branch_ids[0])
        if self.prob_cnt > 0:
            return int(branch_ids[int(np.argmax(self._consistency()))])
        return int(branch_ids[0])

    def decided_branch(self, branch_ids, done):
        # after self-truncation only the consistency winner survives and
        # choose() is pinned to it; before that the pick can still move
        return int(branch_ids[0]) if self.truncated else None

    def extra(self):
        return {"cutoff": self.cutoff_hit}


class KappaStrategy(DecodeStrategy):
    """The paper's KAPPA controller: latent-informativeness scoring with
    scheduled pruning and bucketed cache compaction (DESIGN.md §2).

    Two controller backends behind the same host-side decisions:

      * **local** (single-request engine loop, or ``fused_sampling=False``
        schedulers): this strategy owns a jitted per-request
        ``kappa_step`` — one dispatch and one blocking ``np.asarray``
        sync per step.
      * **pooled** (the batched scheduler path): the scheduler attaches a
        :class:`PooledKappaController` slot; the controller math runs in
        the scheduler's single fused tick dispatch and this strategy only
        reads its slice of the published host mirrors — zero device work
        and zero syncs here. ``ctrl_rows`` maps the request's current
        (compaction-survivor) row order onto its slot's controller rows;
        compaction just shrinks the map, the pooled state is never
        gathered (dropped rows are dead and masked — see core.kappa).
    """

    name = "kappa"

    def begin(self, params, cfg, kcfg, *, bos_id, frontend=None):
        super().begin(params, cfg, kcfg, bos_id=bos_id, frontend=frontend)
        self._begin_args = (params, cfg, jnp.int32(bos_id),
                            frontend[:1] if frontend is not None else None)
        self.state = None            # local backend, created on first use
        self.log_q = None
        self.chain = cache_lib.bucket_chain(kcfg.num_branches)
        self.pool: Optional[PooledKappaController] = None
        self.slot: Optional[int] = None
        self.ctrl_rows: Optional[np.ndarray] = None

    # ------------------------------------------------- controller backends

    def attach_pool(self, pool: PooledKappaController, slot: int,
                    n_rows: int) -> None:
        self.pool, self.slot = pool, slot
        self.ctrl_rows = np.arange(n_rows)
        # the pooled tick computes signals from the pool logits directly;
        # the scheduler can skip this request's per-tick logits gather
        self.needs_step_logits = False

    def release_pool(self) -> None:
        if self.pool is not None:
            self.pool.release(self.slot)
            self.pool = self.slot = self.ctrl_rows = None
            self._pool_released = True

    def _local_state(self):
        if getattr(self, "_pool_released", False):
            # result() must run BEFORE release_pool(); lazily building a
            # fresh local state here would silently report branch 0 /
            # zero trajectories instead of the pooled outcome
            raise RuntimeError(
                "KappaStrategy read after its pooled-controller slot was "
                "released — call result() before release_pool()")
        if self.state is None:
            params, cfg, bos, fe = self._begin_args
            self.log_q = _bos_log_q(params, cfg, bos, fe)
            self.state = kappa_lib.init_state(self.kcfg)
        return self.state

    # ---------------------------------------------------------------- step

    def step(self, logits, in_tokens, out_tokens, branch_ids, done,
             done_prev, step_idx, picked_lp=None):
        kcfg = self.kcfg
        if self.pool is not None:
            # controller already stepped in the scheduler's fused tick
            # dispatch; read this request's slice of the host mirrors
            alive = self.pool.alive[self.slot][self.ctrl_rows]
            traj = self.pool.traj[self.slot][self.ctrl_rows]
        else:
            # controller contract: ``tokens`` are the tokens JUST sampled
            # (out_tokens) — feeding last step's tokens delays the
            # adaptive cutoff one step past true all-pairwise divergence
            self.state = _kappa_controller(self._local_state(), logits,
                                           jnp.asarray(out_tokens),
                                           self.log_q, kcfg)
            # ONE fused blocking transfer for both controller outputs —
            # the local-path twin of the pooled tick's single device_get
            # repro-lint: disable-next-line=sync-discipline
            alive, traj = jax.device_get((self.state.alive,
                                          self.state.traj))
        # ~done_prev: a branch's own EOS-emitting step is logged/counted,
        # the same accounting greedy and BoN use
        counted = alive & ~done_prev

        keep = None
        rows = len(branch_ids)
        if kcfg.compaction:
            n_alive = int(np.sum(alive))
            bucket = cache_lib.next_bucket(self.chain, max(n_alive, 1), rows)
            if bucket < rows:
                order = np.argsort(~alive * 1_000_000 - traj)  # alive best first
                keep = np.sort(order[:bucket])
                if self.pool is not None:
                    self.ctrl_rows = self.ctrl_rows[keep]
                else:
                    self.state = kappa_lib.compact_state(self.state,
                                                         jnp.asarray(keep))
                alive = alive[keep]

        # termination on the post-compaction view
        bids = branch_ids if keep is None else branch_ids[keep]
        live = bids[alive]
        stop = (len(live) == 1 and bool(done[live[0]])) \
            or bool(np.all(done[bids] | ~alive))
        return StepDecision(counted=counted, keep=keep, stop=stop)

    # ------------------------------------------------------------ selection

    def _alive_traj(self):
        if self.pool is not None:
            return (self.pool.alive[self.slot][self.ctrl_rows],
                    self.pool.traj[self.slot][self.ctrl_rows])
        st = self._local_state()
        # one fused transfer instead of two sequential blocking reads
        # repro-lint: disable-next-line=sync-discipline
        return jax.device_get((st.alive, st.traj))

    def choose(self, branch_ids, done):
        alive, traj = self._alive_traj()
        masked = np.where(alive, traj, -np.inf)
        return int(branch_ids[int(np.argmax(masked))])

    def decided_branch(self, branch_ids, done):
        # pruning is monotone (a pruned branch never revives), so once a
        # single survivor remains it IS the final choose() pick
        alive, traj = self._alive_traj()
        if int(np.sum(alive)) != 1:
            return None
        masked = np.where(alive, traj, -np.inf)
        return int(branch_ids[int(np.argmax(masked))])

    def extra(self):
        if self.pool is not None:
            cutoff = int(self.pool.cutoff[self.slot])
            traj = self.pool.traj[self.slot][self.ctrl_rows]
        else:
            st = self._local_state()
            # repro-lint: disable-next-line=sync-discipline
            cut_np, traj = jax.device_get((st.cutoff, st.traj))
            cutoff = int(cut_np)
        return {"cutoff": cutoff, "traj": traj.tolist()}


_STRATEGIES = {
    "greedy": GreedyStrategy,
    "bon": BoNStrategy,
    "stbon": STBoNStrategy,
    "kappa": KappaStrategy,
}


def make_strategy(name: str, **kw) -> DecodeStrategy:
    return _STRATEGIES[name](**kw)


# ----------------------------------------------------------- request state

def raw_key(keys):
    """Raw ``(..., 2)`` uint32 key data of ``keys``, typed or raw; keys
    of a wider impl (e.g. rbg's 4 words) are refused rather than
    misread."""
    if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
        keys = jax.random.key_data(keys)
    if keys.shape[-1] != 2:
        raise ValueError(
            f"request RNG uses a {keys.shape[-1]}-word key impl; the "
            "serving stack supports 2-word (threefry) keys only")
    return keys


class RequestState:
    """Method-agnostic host state of one in-flight request.

    Owns the RNG stream, the done mask, the token log, and the
    logical/compute/byte accounting. The driver (engine loop or
    scheduler) owns the device cache; it applies ``StepDecision.keep``
    to its own row storage (gather for a dedicated cache, slot freeing
    for the shared pool).

    A scheduler with fused sampling holds the stream itself, in a device
    table, from activation (after :meth:`first_tokens`) until the
    request leaves the pool; ``rng`` is stale over that time and nothing
    reads it (a replay starts a new state from the submission RNG)."""

    def __init__(self, strategy: DecodeStrategy, params, cfg: ModelConfig,
                 kcfg: KappaConfig, prompt_len: int, rng, *, eos_id: int,
                 bos_id: int, max_seq: int, n_prefix: int, frontend=None):
        self.strategy = strategy
        self.cfg = cfg
        self.kcfg = kcfg
        self.eos_id = eos_id
        self.max_seq = max_seq
        self.rng = rng
        strategy.begin(params, cfg, kcfg, bos_id=bos_id, frontend=frontend)
        self.n = strategy.rows(kcfg)
        self.log = TokenLog(self.n, kcfg.max_new_tokens + 1)
        self.branch_ids = np.arange(self.n)
        self.pos = prompt_len + n_prefix
        self.step = 0
        self.logical = 0
        self.compute = 0
        self.compactions: List[int] = []
        self.peak = cache_lib.used_cache_bytes(cfg, self.n, self.pos, max_seq)
        self.done: Optional[np.ndarray] = None
        self.cur: Optional[np.ndarray] = None
        self.finished = False

    def first_tokens(self, pf_logits) -> np.ndarray:
        """Sample the fan-out tokens from the prefill logits."""
        keys0 = self.step_keys()
        logits0 = jnp.broadcast_to(pf_logits, (self.n, pf_logits.shape[-1]))
        cur = sampler.sample_rows(keys0, logits0, self._greedy_mask(self.n),
                                  self.kcfg)
        self.cur = np.asarray(cur)
        self.done = self.strategy.init_done(self.cur, self.eos_id)
        self.strategy.observe_prefill(logits0, self.cur)
        self.log.append(self.branch_ids, self.cur, np.ones(self.n, bool))
        self.logical += self.n
        self.compute += self.n
        if np.all(self.done) or self.kcfg.max_new_tokens <= 1:
            self.finished = True
        return self.cur

    def step_keys(self):
        """Advance this request's RNG stream and derive one sampling key
        per live row: the first tokens and the engine loop
        (:meth:`sample_and_advance`) use them. The fused scheduler tick
        steps the stream the same way on the device
        (:class:`repro.serving.sampler.RowStreams`), so tokens match
        across modes.

        Returned keys are always raw (n, 2) uint32 key data — new-style
        *threefry* typed keys (``jax.random.key``'s default impl) are
        unwrapped (:func:`raw_key`), so either flavor the caller
        submitted works."""
        self.rng, kk = jax.random.split(self.rng)
        return raw_key(jax.random.split(kk, len(self.branch_ids)))

    def _greedy_mask(self, n: int):
        return jnp.full((n,), self.strategy.greedy)

    def sample_and_advance(self, logits) -> StepDecision:
        """Single-request path: one ``sample_rows`` dispatch for this
        request's rows, then the shared host-side bookkeeping."""
        keys = self.step_keys()
        toks = sampler.sample_rows(keys, logits,
                                   self._greedy_mask(len(self.branch_ids)),
                                   self.kcfg)
        return self.advance(logits, np.asarray(toks))

    def advance(self, logits, tokens: np.ndarray,
                picked_lp: Optional[np.ndarray] = None) -> StepDecision:
        """Host-side work for one decode step given this request's
        per-branch logits and pre-sampled next tokens (sampled with this
        request's :meth:`step_keys`). ``picked_lp`` optionally carries the
        picked-token log-probs when the scheduler already extracted them
        for the whole pool in one dispatch (rows where ``done`` was
        already set are never consumed, so the raw-token values are
        fine). The caller must apply ``decision.keep`` to its cache
        rows."""
        nxt_np = np.asarray(tokens)
        done_prev = self.done[self.branch_ids].copy()
        nxt_np = np.where(done_prev, self.eos_id, nxt_np)
        self.done[self.branch_ids] |= (nxt_np == self.eos_id)
        self.pos += 1
        self.step += 1
        dec = self.strategy.step(logits, self.cur, nxt_np, self.branch_ids,
                                 self.done, done_prev, self.step,
                                 picked_lp=picked_lp)
        self.log.append(self.branch_ids, nxt_np, dec.counted)
        self.logical += int(np.sum(dec.counted))
        self.compute += len(self.branch_ids)
        self.cur = nxt_np
        if dec.keep is not None and len(dec.keep) < len(self.branch_ids):
            # bytes are monotone in pos at fixed row count, so the peak
            # over a constant-rows stretch is its last step: sample it
            # right before the rows shrink (and again in result()) —
            # this keeps the per-step host path free of byte accounting
            self._observe_peak()
        if dec.keep is not None:
            self.branch_ids = self.branch_ids[dec.keep]
            self.cur = self.cur[dec.keep]
            self.compactions.append(len(dec.keep))
        if dec.stop or self.step >= self.kcfg.max_new_tokens - 1:
            self.finished = True
        return dec

    def _observe_peak(self) -> None:
        self.peak = max(self.peak, cache_lib.used_cache_bytes(
            self.cfg, len(self.branch_ids), self.pos, self.max_seq))

    def result(self) -> GenResult:
        self._observe_peak()
        chosen = self.strategy.choose(self.branch_ids, self.done)
        toks = self.log.buf[chosen, :self.log.len[chosen]]
        toks = toks[toks != -1].tolist()
        return GenResult(
            tokens=toks, chosen_branch=chosen, all_tokens=self.log.buf,
            lengths=self.log.len.copy(), logical_tokens=self.logical,
            compute_tokens=self.compute, peak_cache_bytes=self.peak,
            steps=self.step, compactions=self.compactions,
            extra=self.strategy.extra())
