"""Cache utilities: batch-axis bookkeeping, compaction gathers, byte
accounting.

Cache pytrees from repro.models.init_cache have two leaf families:
  "stack" / "xkv_stack" leaves: (K, B, ...) — batch is axis 1
  "rem"   / "xkv_rem"   leaves: (B, ...)    — batch is axis 0

Bucketed compaction (the TPU-native replacement for PyTorch's eager
per-branch KV freeing, DESIGN.md §2): when the number of live branches
falls to the next power-of-two bucket, gather live rows into a smaller
cache. Each bucket size is a distinct compiled shape; the bucket chain
N → 2^⌈log2 N⌉-1 → … → 1 bounds recompilation.
"""
from __future__ import annotations

import heapq
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn


def _map_batched(cache: Dict[str, Any], fn_stack, fn_rem):
    out = {}
    for key, val in cache.items():
        if key.endswith("stack"):
            out[key] = jax.tree.map(fn_stack, val)
        else:
            out[key] = jax.tree.map(fn_rem, val)
    return out


def _map_batched2(cache: Dict[str, Any], other: Dict[str, Any],
                  fn_stack, fn_rem):
    out = {}
    for key, val in cache.items():
        if key.endswith("stack"):
            out[key] = jax.tree.map(fn_stack, val, other[key])
        else:
            out[key] = jax.tree.map(fn_rem, val, other[key])
    return out


def gather_batch(cache, idx):
    """Select branch rows ``idx`` from every cache leaf."""
    return _map_batched(cache, lambda a: a[:, idx], lambda a: a[idx])


def scatter_batch(pool, idx, sub):
    """Write ``sub``'s branch rows into pool rows ``idx`` — the inverse
    of :func:`gather_batch`, used by the continuous-batching scheduler to
    install a freshly prefilled request into free slots of its fixed
    (rows, max_seq) device pool (DESIGN.md §4)."""
    return _map_batched2(pool, sub,
                         lambda a, b: a.at[:, idx].set(b),
                         lambda a, b: a.at[idx].set(b))


def scatter_batch_prefix(pool, idx, sub):
    """Like :func:`scatter_batch`, but ``sub``'s leaves may be *shorter*
    than the pool's on their non-batch axes (a prompt-sized prefill
    cache installed into max_seq-sized pool rows): each leaf writes only
    its own extent, leaving the rows' tails untouched. Stale data beyond
    a request's written positions is never read — decode writes position
    p before attending with ``kv_pos <= p``, and ring-slot validity
    masks unwritten slots. ``sub`` may be batch-1 (broadcast into all
    ``idx`` rows) or match ``len(idx)``."""
    def st(a, b):
        sl = (slice(None), idx) + tuple(slice(0, s) for s in b.shape[2:])
        return a.at[sl].set(b)

    def rm(a, b):
        sl = (idx,) + tuple(slice(0, s) for s in b.shape[1:])
        return a.at[sl].set(b)

    return _map_batched2(pool, sub, st, rm)


def broadcast_batch(cache, n: int):
    """Replicate a batch-1 cache to n branches (post-prefill fan-out)."""
    def rep(a, axis):
        reps = [1] * a.ndim
        reps[axis] = n
        return jnp.tile(a, reps)
    return _map_batched(cache, lambda a: rep(a, 1), lambda a: rep(a, 0))


def cache_bytes(cache) -> int:
    """Total bytes held by the cache pytree (the branch-scaling part of
    peak memory — our static-shape analogue of the paper's M_peak)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(cache))


def _kv_token_bytes(cfg) -> int:
    """Exact bytes one (token, kv-head) K *or* V entry costs: hd values
    in the cache dtype, plus one fp32 absmax scale per token-head when
    quantized. Integer math — the old amortized-per-element float
    (``1 + 4/hd``) drifted under ``int()`` truncation whenever the head
    dim wasn't a power of two, so accounting disagreed with the
    allocator's ``leaf.nbytes`` truth."""
    hd = cfg.resolved_head_dim
    if cfg.kv_cache_dtype == "int8":
        return hd * 1 + 4                      # int8 values + fp32 scale
    return hd * jnp.dtype(cfg.dtype).itemsize


def _kv_itemsize(cfg) -> float:
    """Per-element KV byte cost (quantization-aware), kept for display /
    ratio math; byte *accounting* uses the exact :func:`_kv_token_bytes`."""
    return _kv_token_bytes(cfg) / cfg.resolved_head_dim


def used_cache_bytes(cfg, rows: int, pos: int, max_seq: int, *,
                     skip_global: bool = False) -> int:
    """Paged-allocator view of cache memory: bytes actually *referenced*
    with ``rows`` live branch rows after ``pos`` positions.

    The paper's peak-memory numbers come from PyTorch's dynamically grown
    KV tensors; a TPU serving stack gets the same effect with a paged KV
    allocator (pages freed on branch prune / never allocated past pos).
    This analytic accounting is the static-shape analogue used for the
    M_cost metric. ``skip_global=True`` drops the global-attention term —
    the paged scheduler charges that part from allocator truth instead
    (owned pages × :func:`page_bytes`, shared pages once)."""
    it = jnp.dtype(cfg.dtype).itemsize
    tb_kv = _kv_token_bytes(cfg)
    hd = cfg.resolved_head_dim
    total = 0
    for bt in cfg.block_types():
        if bt == "global":
            if skip_global:
                continue
            total += rows * min(pos, max_seq) * cfg.num_kv_heads * 2 * tb_kv
        elif bt == "local":
            w = min(cfg.window_size, max_seq)
            total += rows * min(pos, w) * cfg.num_kv_heads * 2 * tb_kv
        elif bt == "recurrent":
            total += rows * (cfg.d_model * 4 + cfg.d_model * 3 * it)  # h fp32 + conv
        elif bt == "rwkv6":
            total += rows * (cfg.num_heads * hd * hd * 4 + 2 * cfg.d_model * it)
    if cfg.is_encoder_decoder:
        total += cfg.num_layers * rows * cfg.encoder_seq_len \
            * cfg.num_kv_heads * hd * 2 * it
    return int(total)


def per_request_bytes(cfg, rows_pos: Dict[Any, tuple], max_seq: int
                      ) -> Dict[Any, int]:
    """Per-request paged-view byte accounting over a shared row pool:
    ``rows_pos`` maps request id -> (occupied rows, current pos). Each
    request is charged only for the slots it owns, referenced up to its
    own position — the scheduler's analogue of the single-request
    ``used_cache_bytes`` accounting."""
    return {rid: used_cache_bytes(cfg, r, p, max_seq)
            for rid, (r, p) in rows_pos.items()}


# ----------------------------------------------------------- paged pool
#
# DESIGN.md §5: the paged scheduler replaces the contiguous (rows,
# max_seq) reservation with fixed-size pages handed out from a free
# list. Freeing a pruned branch returns its pages immediately — no
# gather/compaction on the scheduler path — and admission is counted in
# pages, so rows of different lengths share the pool.


class PageAllocator:
    """Host-side page bookkeeping for the shared device page pool,
    with per-page reference counts for copy-on-write prefix sharing.

    ``num_pages`` allocatable physical pages of ``page_size`` token slots
    each; physical index ``num_pages`` is the shared *trash* page (the
    device pool is allocated with one extra page). Block tables are
    (rows, max_pages) int32 in *device form*: owned logical pages map to
    real physical pages, everything else aliases the trash page, so
    attention validity stays purely positional (kv_pos <= pos).

    ``ref`` counts how many block tables reference each physical page.
    Fan-out branches alias the fully-written prompt pages (``ref`` = N)
    and privately own everything they write (``ref`` = 1 — the COW
    invariant :meth:`write_page` enforces); :meth:`free_row` returns a
    page to the free list only when its last reference drops.

    The free list is a min-heap: freeing is O(log F) per page (not a
    full sort on the hot pruning path) and allocation always hands out
    the smallest free physical id, so page placement is a deterministic
    function of the alloc/free history."""

    def __init__(self, num_pages: int, page_size: int, rows: int,
                 max_pages: int, fault_plan=None):
        if num_pages < 1:
            raise ValueError("need at least one allocatable page")
        self.num_pages = num_pages
        self.page_size = page_size
        self.trash = num_pages
        self.rows = rows
        self.max_pages = max_pages
        self.free_pages: List[int] = list(range(num_pages))  # min-heap
        self.block = np.full((rows, max_pages), self.trash, np.int32)
        self.owned = np.zeros((rows,), np.int32)   # block-table entries/row
        self.ref = np.zeros((num_pages,), np.int32)
        # pin references held by the radix prefix cache (a pin is an
        # ordinary ``ref`` plus this attribution mark, so the invariant
        # checkers can split refcounts into table refs + pins)
        self.pinned = np.zeros((num_pages,), np.int32)
        # fault injection: ``holdback`` free pages are embargoed for the
        # current tick — ``can_alloc`` (and hence admission/eviction
        # decisions) see a smaller heap, but the raw ``free_count``
        # accounting is untouched so leak checks stay exact.
        self.fault_plan = fault_plan
        self.holdback = 0

    def begin_tick(self, tick: int) -> int:
        """Consult the fault plan for this tick's allocator-exhaustion
        embargo; returns the holdback so callers can count injections."""
        self.holdback = (self.fault_plan.page_holdback(tick)
                         if self.fault_plan is not None else 0)
        return self.holdback

    # ------------------------------------------------------------ queries

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` positions of one row."""
        return -(-int(n_tokens) // self.page_size)

    @property
    def free_count(self) -> int:
        return len(self.free_pages)

    @property
    def used_count(self) -> int:
        return self.num_pages - len(self.free_pages)

    @property
    def avail_count(self) -> int:
        """Pages actually allocatable this tick (free minus embargo)."""
        return max(0, len(self.free_pages) - self.holdback)

    def can_alloc(self, n_pages: int) -> bool:
        return self.avail_count >= n_pages

    def row_pages(self, row: int) -> np.ndarray:
        """Physical pages referenced by ``row``'s block table."""
        return self.block[row, :int(self.owned[row])]

    # ---------------------------------------------------------- lifecycle

    def alloc_pages(self, n_pages: int) -> List[int]:
        """Pop ``n_pages`` free pages (smallest physical ids first). The
        pages are unreferenced until installed into a block table via
        :meth:`set_row_pages`."""
        if not self.can_alloc(n_pages):
            raise ValueError(f"out of pages: need {n_pages}, "
                             f"free {len(self.free_pages)}"
                             + (f" (holdback {self.holdback})"
                                if self.holdback else ""))
        return [heapq.heappop(self.free_pages) for _ in range(n_pages)]

    def set_row_pages(self, row: int, pages: Sequence[int]) -> None:
        """Install ``pages`` as ``row``'s block table (shared prefix pages
        may appear in several rows' tables; each installation takes one
        reference)."""
        if self.owned[row]:
            raise ValueError(f"row {row} already owns {self.owned[row]} pages")
        if len(pages) > self.max_pages:
            raise ValueError(f"{len(pages)} pages > max_pages={self.max_pages}")
        n = len(pages)
        self.block[row, :n] = pages
        self.block[row, n:] = self.trash
        self.owned[row] = n
        for p in pages:
            self.ref[int(p)] += 1

    def alloc_row(self, row: int, n_pages: int) -> np.ndarray:
        """Hand ``n_pages`` fresh private pages to ``row``."""
        if self.owned[row]:
            raise ValueError(f"row {row} already owns {self.owned[row]} pages")
        if n_pages > self.max_pages:
            raise ValueError(f"{n_pages} pages > max_pages={self.max_pages}")
        pages = np.array(self.alloc_pages(n_pages), np.int32)
        self.set_row_pages(row, pages)
        return pages

    def append_page(self, row: int) -> int:
        """Lazy growth: hand ``row`` one more private page (the next
        decode page, acquired when its position crosses a page
        boundary)."""
        n = int(self.owned[row])
        if n >= self.max_pages:
            raise ValueError(f"row {row} already at max_pages={self.max_pages}")
        p = self.alloc_pages(1)[0]
        self.block[row, n] = p
        self.owned[row] = n + 1
        self.ref[p] = 1
        return p

    def free_row(self, row: int) -> None:
        """Drop every reference ``row`` holds; pages whose last reference
        this was go back on the free heap (O(log F) each)."""
        for p in self.block[row, :int(self.owned[row])]:
            p = int(p)
            self.ref[p] -= 1
            if self.ref[p] == 0:
                heapq.heappush(self.free_pages, p)
        self.block[row] = self.trash
        self.owned[row] = 0

    # -------------------------------------------------------- pinned pages

    def pin_page(self, page: int) -> None:
        """Take a pin reference on ``page`` (the radix prefix cache's
        claim): the page survives every block table dropping it and
        returns to the free heap only after :meth:`unpin_page`. Pinning
        requires the page to be live (referenced) — a pin adopts an
        existing page, it never resurrects a freed one."""
        page = int(page)
        if not (0 <= page < self.num_pages):
            raise ValueError(f"cannot pin page {page}")
        if self.ref[page] == 0:
            raise ValueError(f"cannot pin unreferenced page {page}")
        self.ref[page] += 1
        self.pinned[page] += 1

    def unpin_page(self, page: int) -> None:
        """Drop a pin reference; the page goes back on the free heap
        when that was its last reference (O(log F), like
        :meth:`free_row`)."""
        page = int(page)
        if self.pinned[page] < 1:
            raise ValueError(f"page {page} is not pinned")
        self.pinned[page] -= 1
        self.ref[page] -= 1
        if self.ref[page] == 0:
            heapq.heappush(self.free_pages, page)

    # --------------------------------------------------------- COW guard

    def write_page(self, rows: np.ndarray, pos: np.ndarray) -> np.ndarray:
        """Physical page each of ``rows`` writes its token at ``pos`` into,
        with the COW invariant enforced: the write page must be inside the
        row's owned table AND referenced by that row alone (refcount 1) —
        a decode write can never land on a page shared with a sibling
        branch."""
        rows = np.asarray(rows)
        lp = np.asarray(pos) // self.page_size
        if np.any(lp >= self.owned[rows]):
            bad = rows[lp >= self.owned[rows]]
            raise AssertionError(
                f"rows {bad.tolist()} write past their allocated pages "
                "(lazy growth missed a page-boundary crossing)")
        phys = self.block[rows, lp]
        shared = self.ref[phys] != 1
        if np.any(shared):
            raise AssertionError(
                f"COW violation: rows {rows[shared].tolist()} would write "
                f"to shared pages {phys[shared].tolist()} "
                f"(refcounts {self.ref[phys][shared].tolist()})")
        return phys.astype(np.int32)


# ---------------------------------------------------- radix prefix cache
#
# DESIGN.md §7: cross-request prefix sharing. Completed (or preempted)
# requests publish their fully-written prompt pages — and, on the
# KAPPA/ST-BoN winner path, the surviving generated prefix — into a
# radix tree keyed on token ids at page granularity. Admission walks the
# tree and aliases every matched page into the new request's block table
# (one table ref per sharer, the tree keeps its pin), so chunked prefill
# starts at the first uncached token. When the free heap runs dry, the
# least-recently-hit pin-only leaves are released BEFORE any request is
# preempted.


class _RadixNode:
    """One cached page. The edge key is the page's ``page_size``-token id
    tuple relative to the parent chain's prefix; ``page`` is the pinned
    physical page holding that prefix extent's K/V."""

    __slots__ = ("key", "page", "parent", "children", "last_hit")

    def __init__(self, key, page, parent):
        self.key = key
        self.page = page
        self.parent = parent
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.last_hit = 0


class RadixPrefixCache:
    """Cross-request radix tree over token-id prefixes, page-granular.

    Nodes pin refcounted pages in a :class:`PageAllocator` (one pin per
    node, taken at publish time before the publisher's block table drops
    its reference — the page never transits the free heap). Keying on
    the token ids from position 0 guarantees a matched page holds K/V
    for exactly the positions a re-prefill would write, so aliasing it
    is bitwise-equivalent to recomputation.

    Only *full* pages are cacheable: a partially-written boundary page
    mixes prefix content with slack a sharer would have to COW-copy
    anyway, and its content is not a pure function of a page-granular
    token key. Eviction (:meth:`evict_one`) releases the
    least-recently-hit leaf whose page the tree is the sole referent of;
    pages still aliased by live block tables are never candidates —
    unpinning them would free nothing and forget reusable content."""

    def __init__(self, alloc: PageAllocator, page_size: int):
        self.alloc = alloc
        self.page_size = page_size
        self.root = _RadixNode((), None, None)
        self._nodes = 0
        self._clock = 0                      # monotonic hit/publish stamp
        self.evictions = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _key(self, tokens: np.ndarray, k: int) -> tuple:
        s = k * self.page_size
        return tuple(int(t) for t in tokens[s:s + self.page_size])

    def _iter_nodes(self):
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            yield node
            stack.extend(node.children.values())

    @property
    def pinned_count(self) -> int:
        """Pages currently pinned by the tree (= node count)."""
        return self._nodes

    @property
    def evictable_count(self) -> int:
        """Pages the tree could hand back under pressure: pin-only
        pages (no live block-table references). Rows alias contiguous
        prefixes, so a pin-only node's whole subtree is pin-only too and
        reachable leaf-by-leaf — this count is achievable, not just an
        upper bound."""
        return sum(1 for n in self._iter_nodes()
                   if int(self.alloc.ref[n.page])
                   == int(self.alloc.pinned[n.page]))

    def lookup(self, tokens) -> List[int]:
        """Physical pages of the longest cached page-granular prefix of
        ``tokens`` (empty list on a miss), LRU-stamping every matched
        node. The caller must alias the pages into a block table (taking
        its own references) before anything else can trigger eviction."""
        toks = np.asarray(tokens)
        node, pages = self.root, []
        stamp = self._tick()
        k = 0
        while (k + 1) * self.page_size <= len(toks):
            child = node.children.get(self._key(toks, k))
            if child is None:
                break
            child.last_hit = stamp
            pages.append(child.page)
            node = child
            k += 1
        return pages

    def publish(self, tokens, pages: Sequence[int]) -> int:
        """Pin ``pages`` — the block-table pages backing ``tokens``, one
        per full page, in order — into the tree under their token keys.
        Extents already cached are left alone (the earlier copy wins;
        the content is identical by construction), so republishing a
        shared preamble is idempotent. Returns the number of pages newly
        pinned."""
        toks = np.asarray(tokens)
        node, new = self.root, 0
        stamp = self._tick()
        for k, page in enumerate(pages):
            key = self._key(toks, k)
            if len(key) < self.page_size:
                break
            child = node.children.get(key)
            if child is None:
                child = _RadixNode(key, int(page), node)
                self.alloc.pin_page(int(page))
                node.children[key] = child
                self._nodes += 1
                new += 1
            child.last_hit = stamp
            node = child
        return new

    def evict_one(self) -> "int | None":
        """Release the least-recently-hit evictable leaf; returns the
        physical page handed back to the free heap, or None when nothing
        is evictable (every cached page is still aliased by a live
        table — the caller falls through to preemption)."""
        best = None
        for node in self._iter_nodes():
            if node.children:
                continue
            if int(self.alloc.ref[node.page]) \
                    != int(self.alloc.pinned[node.page]):
                continue
            if best is None or node.last_hit < best.last_hit:
                best = node
        if best is None:
            return None
        del best.parent.children[best.key]
        self.alloc.unpin_page(best.page)
        self._nodes -= 1
        self.evictions += 1
        return best.page

    def drop(self) -> int:
        """Unpin every cached page and empty the tree (teardown); pages
        whose pin was the last reference return to the free heap.
        Returns the number of nodes dropped — after this the allocator
        must account for every page again (the zero-leak check)."""
        n = 0
        for node in list(self._iter_nodes()):
            self.alloc.unpin_page(node.page)
            n += 1
        self.root = _RadixNode((), None, None)
        self._nodes = 0
        return n


def _map_layer_entries(cfg, cache: Dict[str, Any], other: Dict[str, Any],
                       fn) -> Dict[str, Any]:
    """Map ``fn(block_type, is_stack, entry, other_entry)`` over per-layer
    cache entries (cross-attn K/V entries get block_type "xkv")."""
    pattern = cfg.layer_pattern
    P = len(pattern)
    out = {
        "stack": tuple(fn(pattern[j], True, e, o) for j, (e, o)
                       in enumerate(zip(cache["stack"], other["stack"]))),
        "rem": tuple(fn(pattern[j % P], False, e, o) for j, (e, o)
                     in enumerate(zip(cache["rem"], other["rem"]))),
    }
    if "xkv_stack" in cache:
        out["xkv_stack"] = tuple(fn("xkv", True, e, o) for e, o
                                 in zip(cache["xkv_stack"], other["xkv_stack"]))
        out["xkv_rem"] = tuple(fn("xkv", False, e, o) for e, o
                               in zip(cache["xkv_rem"], other["xkv_rem"]))
    return out


def _to_pages(name: str, b, page_size: int):
    """A contiguous sub-cache leaf (..., n, S, KV[, hd]) of a global layer
    cut into the pool's head-major pages, rows flattened:
    (..., n*S/ps, KV, ps, hd) values or (..., n*S/ps, KV, 1, ps) int8
    scales (``k_s``/``v_s``)."""
    scale = name in ("k_s", "v_s")
    seq_ax = b.ndim - (2 if scale else 3)
    flat = b.reshape(b.shape[:seq_ax - 1] + (-1,) + b.shape[seq_ax + 1:])
    return attn.pages_from_seq(flat, page_size, scale=scale)


def install_paged(cfg, pool, row_idx, phys_flat, sub, page_size: int):
    """Install a freshly prefilled contiguous sub-cache into the paged
    pool — the paged analogue of :func:`scatter_batch`.

    ``row_idx``: (n,) pool row slots receiving the request's branches.
    ``phys_flat``: (n * max_pages,) physical page per (row, logical page),
    trash-aliased for unowned logical pages. Global-attention leaves
    scatter page-wise (the sub-cache's sequence axis is reshaped to
    (max_pages, page_size) head-major pages and written through the page
    list; duplicate trash writes are garbage-on-garbage). Every per-row
    leaf family (ring, recurrent, rwkv6, cross-KV) scatters into the row
    slots."""
    def per_entry(bt, is_stack, entry, sub_entry):
        if bt == "global":
            def leaf(name, a):
                br = _to_pages(name, sub_entry[name], page_size)
                return (a.at[:, phys_flat] if is_stack
                        else a.at[phys_flat]).set(br.astype(a.dtype))
            return {name: leaf(name, a) for name, a in entry.items()}
        def leaf_row(a, b):
            return a.at[:, row_idx].set(b) if is_stack else a.at[row_idx].set(b)
        return jax.tree.map(leaf_row, entry, sub_entry)

    return _map_layer_entries(cfg, pool, sub, per_entry)


def install_paged_shared(cfg, pool, row_idx, src_idx, phys, sub1,
                         page_size: int):
    """Install a batch-1 prefill into the paged pool with prefix sharing —
    no N-way ``broadcast_batch`` tile, no N-way scatter.

    ``row_idx``: (n,) pool row slots receiving the request's branches.
    ``src_idx``: (M,) logical source pages of the single prefilled row.
    ``phys``: (M,) destination physical pages. Fully-written prompt pages
    appear ONCE (all n branch block tables alias them); the partially-
    written boundary page at ``prompt_len % page_size`` appears once per
    branch, so each branch gets a private copy-on-write copy to receive
    its divergent decode writes. Global leaves scatter the prefill, cut
    into head-major pages, through that (src, phys) map; every
    per-row leaf family (ring, recurrent, rwkv6, cross-KV) broadcasts the
    batch-1 state into the n row slots."""
    def per_entry(bt, is_stack, entry, sub_entry):
        if bt == "global":
            def leaf(name, a):
                br = _to_pages(name, sub_entry[name], page_size)
                if is_stack:
                    return a.at[:, phys].set(br[:, src_idx].astype(a.dtype))
                return a.at[phys].set(br[src_idx].astype(a.dtype))
            return {name: leaf(name, a) for name, a in entry.items()}

        def leaf_row(a, b):            # b batch-1, broadcast over row_idx
            return a.at[:, row_idx].set(b) if is_stack else a.at[row_idx].set(b)
        return jax.tree.map(leaf_row, entry, sub_entry)

    return _map_layer_entries(cfg, pool, sub1, per_entry)


def copy_pages(cfg, pool, src_pages, dst_pages):
    """Device page copy inside the paged pool's global-attention leaves:
    ``dst_pages[i] <- src_pages[i]``. Used when chunked prefill
    finalizes a fan-out admission — each sibling branch gets a private
    copy-on-write duplicate of the partially-written prompt boundary
    page the prefill wrote (DESIGN.md §6)."""
    def per_entry(bt, is_stack, entry, _):
        if bt != "global":
            return entry

        def leaf(a):
            if is_stack:
                return a.at[:, dst_pages].set(a[:, src_pages])
            return a.at[dst_pages].set(a[src_pages])
        return jax.tree.map(leaf, entry)

    return _map_layer_entries(cfg, pool, pool, per_entry)


def install_rows_aux(cfg, pool, row_idx, aux):
    """Install a batch-1 aux cache's per-row leaf families (ring /
    recurrent / rwkv6 / cross-KV state threaded through chunked prefill)
    into the paged pool's ``row_idx`` slots, broadcasting across the
    fan-out. Global-attention leaves are untouched — their prompt K/V
    already lives in allocator-owned pages (DESIGN.md §6). Aux leaves
    shorter than the pool's (a ring sized to a short prompt) write only
    their own extent, like :func:`scatter_batch_prefix`."""
    def per_entry(bt, is_stack, entry, aux_entry):
        if bt == "global":
            return entry

        def leaf(a, b):
            if is_stack:
                sl = (slice(None), row_idx) + tuple(slice(0, s)
                                                    for s in b.shape[2:])
            else:
                sl = (row_idx,) + tuple(slice(0, s) for s in b.shape[1:])
            return a.at[sl].set(b)
        return jax.tree.map(leaf, entry, aux_entry)

    return _map_layer_entries(cfg, pool, aux, per_entry)


def page_bytes(cfg, page_size: int) -> int:
    """Bytes one physical page holds across every global-attention layer
    (K + V values plus, under int8, the per-token-head fp32 scale
    leaves) — the unit of the paged allocator's own byte accounting.
    Exact integer math: ``page_bytes(cfg, ps) * num_pages`` equals the
    summed ``leaf.nbytes`` of the pool's global-layer leaves (minus the
    trash page)."""
    n_global = sum(1 for bt in cfg.block_types() if bt == "global")
    return (n_global * page_size * cfg.num_kv_heads * 2
            * _kv_token_bytes(cfg))


def bucket_chain(n: int) -> List[int]:
    """Descending bucket sizes: n, then powers of two below n, down to 1."""
    out = [n]
    b = 1
    while b < n:
        b <<= 1
    b >>= 1
    while b >= 1:
        if b < n:
            out.append(b)
        b >>= 1
    return out


def next_bucket(chain: List[int], alive: int, current: int) -> int:
    """Smallest bucket in the chain that still fits ``alive`` branches and
    is smaller than ``current`` (or ``current`` if no shrink possible)."""
    best = current
    for b in chain:
        if b < best and b >= alive:
            best = b
    return best
