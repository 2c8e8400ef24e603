"""Block-paged KV cache: a fixed page pool shared by all in-flight sequences,
and beside it what other kinds of layer keep (window pools, recurrent state).

The vLLM/PagedAttention (SOSP '23) memory design mapped onto the static-shape
XLA world: each layer owns one `(n_pages, n_kv_heads, page_size, head_dim)`
device array and every sequence owns an int32 row of page ids into it. Pages
are head-major so that one kv head's page is a whole `(page_size, head_dim)`
tile: the paged kernels' K/V blocks must cover the array's last two
dimensions (Mosaic's block rule), which a token-major page cannot give per
head. The pool shape never changes, so ONE compiled decode step serves every mix of
sequence lengths; allocation is pure host bookkeeping over a free-list, and
a finished request's pages return to the pool immediately at retirement.

Pages are REFCOUNTED: prefix sharing (PrefixCache below) maps the same
physical page into many sequences' tables, so `free` is a decref and a page
only returns to the free-list when its last owner lets go. A write into a
shared page goes through `PageAllocator.fork` + `PagedKVCache.copy_page`
(copy-on-write; docs/serving.md).

Page 0 is reserved as the NULL page: unallocated page-table entries and idle
decode slots point at it, keeping every gather/DMA in-bounds (the attention
masks its values out via seq_lens; see executors/pallasex.py).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..observability import events as _obs
from ..observability import metrics as _obs_metrics

NULL_PAGE = 0


# ---------------------------------------------------------------------------
# What a layer caches. A served layer (serving/runner.py) declares one of
# these as its ``cache``; PagedKVCache allocates what the declarations add up
# to and the scheduler keeps its books by them. ``None`` is a layer that
# caches nothing.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PagedKV:
    """The layer owns a key pool ``(n_pages, heads, page_size, k_dim)`` and a
    value pool ``(n_pages, heads, page_size, v_dim)``. With ``window`` the
    layer reads key positions ``> pos - window`` only, its pools are cut from
    the smaller window allocation, and the scheduler frees a page as soon as
    every position in it is older than that."""

    heads: int
    k_dim: int
    v_dim: int
    window: Optional[int] = None

    @property
    def kind(self) -> str:
        return "window" if self.window else "full"


@dataclass(frozen=True)
class PagedLatent:
    """The layer owns ONE pool ``(n_pages, page_size, row)`` with no head axis:
    a token's row is what latent attention caches for it (``width`` numbers:
    the normed latent and the roped shared key; values are a slice of the same
    row), padded with zeros to whole 128-lane groups. Its pages are those of
    every position, handed out by the same allocator as a ``PagedKV`` without
    a window."""

    width: int

    kind = "full"
    window = None

    @property
    def row(self) -> int:
        return -(-self.width // 128) * 128


@dataclass(frozen=True)
class ReadsKV:
    """The layer caches nothing and reads the pools of layer ``of``."""

    of: int


@dataclass(frozen=True)
class Recurrent:
    """Per-slot arrays ``(max_batch, *shape)`` for each of ``shapes``: state of
    constant size that every program rewrites and that starts from zero when
    a slot takes a new sequence."""

    shapes: Tuple[Tuple[int, ...], ...]
    dtype: object = jnp.float32

    def bytes_per_slot(self) -> int:
        return sum(math.prod(s) for s in self.shapes) * jnp.dtype(self.dtype).itemsize



class OutOfPages(Exception):
    """The pool cannot satisfy an allocation; the scheduler queues the
    request until retirements return pages."""


class PageAllocator:
    """Refcounting free-list allocator over page ids [1, n_pages); page 0 is
    the reserved null page and is never handed out.

    alloc() hands out pages at refcount 1; incref() adds an owner (prefix
    sharing); free() is a DECREF — the page returns to the free-list only
    when the count reaches zero. The double-free check and the refcount
    bookkeeping live in one place (free), so a shared page freed by one
    owner can never re-enter the free list while other owners hold it."""

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError(f"need at least 2 pages (1 usable + null), got {n_pages}")
        self.n_pages = n_pages
        # LIFO free-list: recently-freed pages are re-used first (their pool
        # slices are most likely still warm in cache hierarchies that care).
        # The mirror set makes free()'s double-free check O(1) — retirement
        # runs inside the decode iteration loop, so freeing k pages must not
        # scan a production-sized free list k times.
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._free_set = set(self._free)
        self._rc: Dict[int, int] = {}  # page id -> live owner count

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return (self.n_pages - 1) - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise OutOfPages(f"requested {n} pages, {len(self._free)} free "
                             f"of {self.n_pages - 1} usable")
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        for p in out:
            self._rc[p] = 1
        return out

    def incref(self, page: int) -> None:
        """Add an owner to an ALLOCATED page (prefix sharing: a new sequence
        or the prefix cache maps an existing physical page)."""
        if page in self._free_set or page not in self._rc:
            raise ValueError(f"incref of unallocated page {page}")
        self._rc[page] += 1

    def refcount(self, page: int) -> int:
        return self._rc.get(page, 0)

    def free(self, pages: List[int]) -> None:
        seen = set()
        for p in pages:
            if not (0 < p < self.n_pages):
                raise ValueError(f"freeing invalid page id {p}")
            if p in self._free_set or p in seen or p not in self._rc:
                # a duplicate WITHIN the call is a double free too: letting
                # it through would hand the same page to two sequences later.
                # (Callers hold at most one reference per page per free()
                # call; a multi-ref owner decrefs across separate calls.)
                raise ValueError(f"double free of page {p}")
            seen.add(p)
        released = []
        for p in pages:
            self._rc[p] -= 1
            if self._rc[p] == 0:
                del self._rc[p]
                released.append(p)
        self._free.extend(released)
        self._free_set.update(released)

    def fork(self, page: int) -> int:
        """Copy-on-write fork: detach THIS owner from a (possibly shared)
        page before writing into it. With other owners present, allocates a
        fresh page, drops this owner's reference on the old one, and returns
        the new id — the caller must then `PagedKVCache.copy_page(old, new)`
        and patch its page table. A sole owner gets the SAME id back (no
        other reader, writing in place is safe and no copy is paid)."""
        if page in self._free_set or page not in self._rc:
            raise ValueError(f"fork of unallocated page {page}")
        if self._rc[page] == 1:
            return page
        new = self.alloc(1)[0]
        self._rc[page] -= 1
        return new

    def utilization(self) -> float:
        usable = self.n_pages - 1
        return self.n_used / usable if usable else 0.0


class _PrefixNode:
    __slots__ = ("key", "page", "children", "parent")

    def __init__(self, key: Tuple[int, ...], page: int, parent):
        self.key = key          # the page's page_size prompt tokens
        self.page = page        # physical page id (cache holds one ref)
        self.children: Dict[Tuple[int, ...], "_PrefixNode"] = {}
        self.parent = parent


class PrefixCache:
    """Prefix -> page-id map: a trie over FULL prompt pages, keyed by each
    page's token tuple (content-keyed, so two prompts sharing a system
    prefix hit the same chain whatever request produced it).

    * `match(prompt)` walks the trie page by page, increfs every matched
      page on the caller's behalf, and additionally probes a PARTIAL tail:
      a prompt whose last (< page_size) tokens are a prefix of some cached
      page's tokens is fully covered — the scheduler then skips prefill
      entirely and re-decodes only the last prompt token (the write that
      triggers the copy-on-write fork).
    * `insert(prompt, pages)` registers a freshly prefilled request's full
      prompt pages; the cache holds its OWN reference on each registered
      page, so donors can retire without invalidating the chain.
    * Eviction is LRU over trie nodes (leaves first, so chains stay
      connected) and runs under pool pressure via `evict_until` — an evicted
      page is only decref'd, so sequences still sharing it are untouched.
    """

    def __init__(self, allocator: PageAllocator, page_size: int):
        self.allocator = allocator
        self.page_size = page_size
        self._root: Dict[Tuple[int, ...], _PrefixNode] = {}
        self._lru: Dict[_PrefixNode, None] = {}  # insertion-ordered; end = newest

    def __len__(self) -> int:
        return len(self._lru)

    def _touch(self, node: _PrefixNode) -> None:
        self._lru.pop(node, None)
        self._lru[node] = None

    def match(self, prompt: np.ndarray) -> Tuple[List[int], int]:
        """(shared_pages, covered_tokens) for a prompt; every returned page
        has been incref'd for the caller (who must free them like any other
        page it owns). covered_tokens == len(prompt) means full coverage
        (possibly via a partial-tail hit on the last page)."""
        ps = self.page_size
        L = len(prompt)
        pages: List[int] = []
        children = self._root
        node = None
        n_full = L // ps
        for i in range(n_full):
            key = tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])
            nxt = children.get(key)
            if nxt is None:
                break
            node = nxt
            self._touch(node)
            self.allocator.incref(node.page)
            pages.append(node.page)
            children = node.children
        covered = len(pages) * ps
        if covered == L:
            return pages, covered
        if len(pages) == L // ps and L % ps:
            # partial tail: the remaining (< page_size) prompt tokens may be
            # the LEADING tokens of some cached full page — sharing it covers
            # the whole prompt; the first decode write CoW-forks it
            tail = tuple(int(t) for t in prompt[n_full * ps:])
            for key, child in children.items():
                if key[:len(tail)] == tail:
                    self._touch(child)
                    self.allocator.incref(child.page)
                    pages.append(child.page)
                    return pages, L
        return pages, covered

    def insert(self, prompt: np.ndarray, pages: List[int]) -> int:
        """Register the FULL prompt pages of a prefilled request (partial
        last pages are never registered — they would mix prompt and
        generated tokens). Existing nodes are touched, new ones incref
        their page. Returns the number of newly registered pages."""
        ps = self.page_size
        n_full = len(prompt) // ps
        children = self._root
        parent = None
        added = 0
        for i in range(min(n_full, len(pages))):
            key = tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])
            node = children.get(key)
            if node is None:
                self.allocator.incref(pages[i])
                node = _PrefixNode(key, pages[i], parent)
                children[key] = node
                added += 1
            self._touch(node)
            children = node.children
            parent = node
        return added

    def _evict(self, node: _PrefixNode) -> None:
        siblings = node.parent.children if node.parent is not None else self._root
        siblings.pop(node.key, None)
        self._lru.pop(node, None)
        self.allocator.free([node.page])

    def evict_until(self, n_needed: int) -> bool:
        """Drop LRU leaf nodes until the allocator can serve `n_needed`
        pages (or nothing evictable remains). Only the cache's OWN reference
        is dropped: pages still mapped by live sequences survive; pages only
        the cache held return to the free-list."""
        while not self.allocator.can_alloc(n_needed):
            victim = next((n for n in self._lru if not n.children), None)
            if victim is None:
                return False
            self._evict(victim)
        return True

    def clear(self) -> None:
        while self._lru:
            victim = next(n for n in self._lru if not n.children)
            self._evict(victim)


class PagedKVCache:
    """The cached state of every layer plus the allocators that parcel the
    page pools out.

    ``layers`` holds one declaration a layer (``PagedKV``, ``PagedLatent``,
    ``ReadsKV``, ``Recurrent`` or ``None``); ``state`` holds one tuple of device
    arrays a layer: ``(k pool, v pool)``, ``(latent pool,)``, ``()`` for a layer
    that reads another's pools or caches nothing, the per-slot arrays of a
    recurrent layer. Pools of
    layers without a window are indexed by the pages of ``allocator``, pools
    of window layers by those of ``window_allocator``: one allocation covers
    all layers of a kind. A pool that several layers read exists once, in the
    state of the layer that owns it.

    The device arrays are FUNCTIONAL state: the decode/prefill programs
    return updated state and the scheduler re-binds it each step (same
    discipline as the dense engine's KVCache tuples). Every program and
    `copy_page` CONSUME the state they are given (buffer donation): an array
    read out of `state` before a dispatch is deleted after it, so read it
    after `rebind`, never across a dispatch.
    """

    def __init__(self, n_layer: int, n_pages: int, page_size: int,
                 n_kv_heads: int, head_dim: int, dtype=jnp.bfloat16,
                 allocator: Optional[PageAllocator] = None, *,
                 layers: Optional[Sequence] = None, max_batch: int = 0,
                 prefill_pages: int = 0, device=None):
        # a plain GPT: every layer owns one pool pair of one shape
        self.layers = (tuple(layers) if layers is not None else
                       (PagedKV(n_kv_heads, head_dim, head_dim),) * n_layer)
        self.n_layer = len(self.layers)
        self.n_pages = n_pages
        self.page_size = page_size
        self.dtype = dtype
        self.max_batch = max_batch
        # where the state lives. The engine names the weights' device, so that fresh pools are
        # committed to it as the pools a program returns are: ``jax.jit`` builds an executable
        # anew for an operand that is committed where it was not
        self.device = device
        windows = {d.window for d in self.layers if isinstance(d, PagedKV) and d.window}
        if len(windows) > 1:
            raise ValueError(f"window layers of one model must share one window, got {sorted(windows)}")
        self.window = windows.pop() if windows else None
        # the window pools hold what a sequence's window spans, for every slot,
        # and beside it the ``prefill_pages`` one prefill program writes before
        # the scheduler trims them (one program runs at a time); plus the null page
        self.n_window_pages = (1 + max_batch * (self.window // page_size + 1) + prefill_pages
                               if self.window else 0)
        self.reset_pools()
        # a draft-model cache (speculative decoding) shares the TARGET
        # cache's allocator: one allocation covers both pools, page ids and
        # page tables are identical across the two
        self.allocator = allocator if allocator is not None else PageAllocator(n_pages)
        self.window_allocator = PageAllocator(self.n_window_pages) if self.window else None
        self._copy_cfn = None

    @staticmethod
    def pages_for(n_tokens: int, page_size: int) -> int:
        return max(1, math.ceil(n_tokens / page_size))

    def _fresh(self, decl) -> tuple:
        def zeros(shape, dtype):
            return jnp.zeros(shape, dtype, device=self.device)

        if isinstance(decl, PagedKV):
            n = self.n_window_pages if decl.window else self.n_pages
            return (zeros((n, decl.heads, self.page_size, decl.k_dim), self.dtype),
                    zeros((n, decl.heads, self.page_size, decl.v_dim), self.dtype))
        if isinstance(decl, PagedLatent):
            return (zeros((self.n_pages, self.page_size, decl.row), self.dtype),)
        if isinstance(decl, Recurrent):
            return tuple(zeros((self.max_batch, *shape), decl.dtype) for shape in decl.shapes)
        return ()

    def reset_pools(self) -> None:
        """Fresh zeroed state: at construction, and after a failed step whose
        program had already consumed the old arrays (`pools_deleted`)."""
        self.state = tuple(self._fresh(d) for d in self.layers)

    def _pools(self, which: int) -> tuple:
        return tuple(s[which] for s, d in zip(self.state, self.layers) if isinstance(d, PagedKV))

    @property
    def k_pages(self) -> tuple:
        """The key pools of the layers that own one, in layer order."""
        return self._pools(0)

    @property
    def v_pages(self) -> tuple:
        return self._pools(1)

    def _arrays(self) -> list:
        return [a for arrs in self.state for a in arrs]

    def pools_deleted(self) -> bool:
        """True when a program consumed the state and its result was never
        rebound (the dispatch failed after execution began): every cached
        key, value and recurrent state is gone."""
        return any(a.is_deleted() for a in self._arrays())

    def rebind(self, state) -> None:
        """Adopt the updated state returned by a compiled step. The arrays
        being replaced are the ones that step was given: with the bus on,
        count whether it consumed them (`serve.pool_donated`) or left them
        alive, which means XLA copied each before writing
        (`serve.pool_copied`)."""
        if _obs.enabled():
            _obs_metrics.record_serve(
                "pool_donated" if all(a.is_deleted() for a in self._arrays())
                else "pool_copied")
        self.state = tuple(tuple(arrs) for arrs in state)

    def recurrent_bytes_per_slot(self) -> int:
        return sum(d.bytes_per_slot() for d in self.layers if isinstance(d, Recurrent))

    def copy_page(self, src: int, dst: int) -> None:
        """Device-copy one page's rows across every layer without a window (the
        copy-on-write body after `PageAllocator.fork`). One cached jax.jit
        program — src and dst ride as traced scalars, so CoW never recompiles;
        the state is donated, so one fork copies one page and not the whole
        pool."""
        import jax

        if self._copy_cfn is None:
            full = [isinstance(d, (PagedKV, PagedLatent)) and not d.window for d in self.layers]

            def _copy(state, s, d):
                return tuple(tuple(a.at[d].set(a[s]) for a in arrs) if is_full else arrs
                             for arrs, is_full in zip(state, full))

            self._copy_cfn = jax.jit(_copy, donate_argnums=(0,))
        self.rebind(self._copy_cfn(self.state, jnp.asarray(src, jnp.int32),
                                   jnp.asarray(dst, jnp.int32)))

    def utilization(self) -> float:
        return self.allocator.utilization()

    def page_table_row(self, pages: List[int], n_pages_max: int) -> np.ndarray:
        """A sequence's page-table row, padded with the null page."""
        row = np.full((n_pages_max,), NULL_PAGE, np.int32)
        row[: len(pages)] = np.asarray(pages, np.int32)
        return row
