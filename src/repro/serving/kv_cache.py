"""Paged KV cache: shared page pools + host-side page-table allocator.

This is the DeServe §4.2 memory layout (Figure 3), adapted to the TPU memory
hierarchy.  The page id space of each attention layer's pool is partitioned:

      [0, n_local)                          — local pools (never offloaded)
      [n_local, n_local + n_global)         — global pool G0
      [n_local + n_global, n_local + 2·n_global) — global pool G1

Microbatch ``m`` allocates its overflow pages from global pool ``G_{m % 2}``;
the complementary pool is swapped to host memory by the double-buffer
offloader (``repro.core.offload``) while the resident one feeds compute.

Device-side state is a cache pytree compatible with ``repro.models.model``:
attention layers get ``{"k_pages","v_pages","page_table", ...}`` (pools
stacked over scan periods), sliding-window layers keep bounded dense rings,
recurrent layers keep O(1) states.  Bookkeeping (free lists, per-sequence
page lists) is host-side Python — identical to vLLM's split of concerns.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ATTN_KINDS, ModelConfig
from repro.models.common import Runtime, make_layer_plan
from repro.models.model import _kind_cache


@dataclass(frozen=True)
class PoolConfig:
    page_size: int = 16
    n_local_pages: int = 64           # shared by all microbatches' local pools
    n_global_pages: int = 0           # per global pool (2 pools total)
    max_pages_per_seq: int = 16

    @property
    def n_pages(self) -> int:
        return self.n_local_pages + 2 * self.n_global_pages

    def global_range(self, pool_id: int) -> range:
        s = self.n_local_pages + pool_id * self.n_global_pages
        return range(s, s + self.n_global_pages)


class PageAllocator:
    """Host-side free-list allocator over the partitioned page id space.

    Page 0 is reserved as a *scratch* page: released slots' page tables point
    at it, so the (masked, harmless) decode writes of inactive slots can
    never corrupt pages that have been reallocated to live sequences.

    Pages are **refcounted**: a page may be owned by several slots at once
    (prefix caching shares fully-prefilled prompt blocks) and retained by
    the :class:`PrefixCache` on top, so ``release`` *decrements* — a page
    only returns to its free list when the last owner lets go.  Returning
    a page that is already free, or releasing a slot that owns nothing,
    raises: a silent double-free would eventually grant one page to two
    live sequences."""

    def __init__(self, pool: PoolConfig):
        self.pool = pool
        assert pool.n_local_pages >= 2, "need >= 2 local pages (page 0 is scratch)"
        self._free_local: List[int] = list(range(1, pool.n_local_pages))
        self._free_global: Dict[int, List[int]] = {
            0: list(pool.global_range(0)),
            1: list(pool.global_range(1)),
        }
        self._seq_pages: Dict[int, List[int]] = {}
        # page -> owner count (slots listing it + one per cache retain);
        # a page is in _refs iff it is NOT on a free list
        self._refs: Dict[int, int] = {}

    # -- queries ------------------------------------------------------------

    def free_local(self) -> int:
        return len(self._free_local)

    def free_global(self, pool_id: int) -> int:
        return len(self._free_global[pool_id])

    def pages_of(self, slot: int) -> List[int]:
        return list(self._seq_pages.get(slot, ()))

    def refcount(self, p: int) -> int:
        return self._refs.get(p, 0)

    # -- allocation ---------------------------------------------------------

    def allocate(self, slot: int, n_pages: int, *,
                 global_pool: Optional[int] = None) -> List[int]:
        """Allocate ``n_pages`` for ``slot``: local pages first, overflow from
        ``global_pool`` (if given).  Raises MemoryError when exhausted."""
        got: List[int] = []
        while len(got) < n_pages and self._free_local:
            got.append(self._free_local.pop())
        while len(got) < n_pages and global_pool is not None and \
                self._free_global[global_pool]:
            got.append(self._free_global[global_pool].pop())
        if len(got) < n_pages:
            for p in got:        # roll back (never granted, refs never set)
                self._give_back(p)
            raise MemoryError(
                f"page pool exhausted: need {n_pages}, got {len(got)} "
                f"(local free={self.free_local()}, "
                f"global={ {i: self.free_global(i) for i in (0, 1)} })")
        for p in got:
            self._refs[p] = 1
        self._seq_pages.setdefault(slot, []).extend(got)
        return got

    def extend(self, slot: int, *, global_pool: Optional[int] = None) -> int:
        return self.allocate(slot, 1, global_pool=global_pool)[0]

    def adopt(self, slot: int, pages: List[int]) -> None:
        """Bind already-owned ``pages`` to ``slot`` as a *shared* prefix:
        each page's refcount is incremented, never re-granted from a free
        list.  Must run before ``allocate`` for the slot so the shared
        pages head its table row (page ``i`` maps positions
        ``[i*page_size, (i+1)*page_size)``)."""
        if self._seq_pages.get(slot):
            raise ValueError(
                f"adopt: slot {slot} already owns pages "
                f"{self._seq_pages[slot]} — shared prefix pages must come "
                "first in the table row")
        for p in pages:
            if p not in self._refs:
                raise ValueError(
                    f"adopt: page {p} is not currently owned — a free "
                    "page cannot be shared (stale prefix-cache entry?)")
        for p in pages:
            self._refs[p] += 1
        self._seq_pages[slot] = list(pages)

    def retain(self, p: int) -> None:
        """Add a non-slot owner (the prefix cache) to an owned page."""
        if p not in self._refs:
            raise ValueError(f"retain: page {p} is not currently owned")
        self._refs[p] += 1

    def drop(self, p: int) -> bool:
        """Release a ``retain`` reference; returns True when the page
        actually went back to its free list (last owner)."""
        return self._decref(p)

    def release(self, slot: int) -> None:
        """Release every page ``slot`` owns (decrement — shared pages stay
        live for their other owners).  Releasing a slot that owns nothing
        raises: the engine frees exactly once per occupied slot, so a
        second release is a bookkeeping bug, not a no-op."""
        if slot not in self._seq_pages:
            raise KeyError(
                f"release: slot {slot} owns no pages (double release, or "
                "a slot that was never allocated)")
        for p in self._seq_pages.pop(slot):
            self._decref(p)

    def _decref(self, p: int) -> bool:
        n = self._refs.get(p)
        if n is None:
            raise ValueError(
                f"page {p} released but not owned (double free)")
        if n > 1:
            self._refs[p] = n - 1
            return False
        del self._refs[p]
        self._give_back(p)
        return True

    def _give_back(self, p: int) -> None:
        if p < self.pool.n_local_pages:
            target = self._free_local
        elif p in self.pool.global_range(0):
            target = self._free_global[0]
        else:
            target = self._free_global[1]
        if p in target:
            raise ValueError(
                f"page {p} returned to the free list twice — a later "
                "allocate would grant one page to two sequences")
        target.append(p)

    # -- page table ---------------------------------------------------------

    def table_row(self, slot: int) -> np.ndarray:
        row = np.zeros((self.pool.max_pages_per_seq,), np.int32)
        pages = self._seq_pages.get(slot, ())
        row[: len(pages)] = pages
        return row


@dataclass
class _PrefixEntry:
    page: int
    children: int = 0                 # longer cached prefixes extending this
    last_use: int = 0                 # LRU clock (lookups + inserts)


class PrefixCache:
    """Prefix-block index over the paged KV pools (vLLM-style, host-side).

    Requests sharing a system-prompt prefix hit the same pages instead of
    re-prefilling: the index maps each *full-page* token prefix (the exact
    token tuple, chain of ``page_size`` blocks) to the page holding its KV.
    Paged attention KV at position ``t`` is a deterministic function of
    ``tokens[:t+1]`` alone, so blocks written by different slots for the
    same token prefix are interchangeable.

    Only **local** pages are ever registered: global-pool content is
    parity-swapped per microbatch by the §4.2 offloader, so a cross-slot
    share spanning microbatches would be clobbered by the next swap.

    Matches are capped at ``prompt_len - 1`` tokens — the final prompt
    position must always be prefilled to produce the first-token logits.
    Cached pages carry one ``PageAllocator.retain`` reference each; LRU
    leaf eviction (``evict``) drops them when the pool runs dry."""

    def __init__(self, alloc: PageAllocator):
        self.alloc = alloc
        self.page_size = alloc.pool.page_size
        self.n_local_pages = alloc.pool.n_local_pages
        self._entries: Dict[tuple, _PrefixEntry] = {}
        self._clock = 0
        self.hit_requests = 0
        self.miss_requests = 0
        self.hit_tokens = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def pages_retained(self) -> List[int]:
        """Pages the cache holds a retain reference on (one per entry)."""
        return [e.page for e in self._entries.values()]

    def match(self, prompt: List[int]) -> List[int]:
        """Pages covering the longest cached full-page prefix of
        ``prompt`` (possibly empty), in table-row order."""
        self._clock += 1
        pages: List[int] = []
        n_full = (len(prompt) - 1) // self.page_size
        for i in range(n_full):
            e = self._entries.get(tuple(prompt[: (i + 1) * self.page_size]))
            if e is None:
                break
            e.last_use = self._clock
            pages.append(e.page)
        if pages:
            self.hit_requests += 1
            self.hit_tokens += len(pages) * self.page_size
        else:
            self.miss_requests += 1
        return pages

    def insert(self, prompt: List[int], pages: List[int]) -> int:
        """Register a fully-prefilled sequence's prompt blocks (``pages``
        in table-row order).  Existing entries win — two requests that
        prefilled the same prefix concurrently keep the incumbent's page.
        Returns the number of pages newly retained."""
        self._clock += 1
        added = 0
        n_full = min((len(prompt) - 1) // self.page_size, len(pages))
        parent: Optional[_PrefixEntry] = None
        for i in range(n_full):
            key = tuple(prompt[: (i + 1) * self.page_size])
            e = self._entries.get(key)
            if e is None:
                p = pages[i]
                if p >= self.n_local_pages:
                    break       # global pages parity-swap per mb: unshareable
                self.alloc.retain(p)
                e = _PrefixEntry(page=p, last_use=self._clock)
                self._entries[key] = e
                if parent is not None:
                    parent.children += 1
                added += 1
            else:
                e.last_use = self._clock
            parent = e
        return added

    def evict(self, n_pages: int) -> int:
        """Drop LRU *leaf* entries until ``n_pages`` pages actually
        returned to the free lists (entries whose pages are still shared
        by live slots free nothing) or the cache is empty.  Returns the
        number of pages freed."""
        freed = 0
        while freed < n_pages and self._entries:
            key = min((k for k, e in self._entries.items()
                       if e.children == 0),
                      key=lambda k: self._entries[k].last_use)
            e = self._entries.pop(key)
            if len(key) > self.page_size:
                parent = self._entries.get(key[:-self.page_size])
                if parent is not None:
                    parent.children -= 1
            if self.alloc.drop(e.page):
                freed += 1
            self.evictions += 1
        return freed

    def clear(self) -> int:
        """Drop every entry (shutdown / tests); returns pages freed."""
        return self.evict(len(self._entries) + 1) if self._entries else 0

    @property
    def hit_rate(self) -> float:
        total = self.hit_requests + self.miss_requests
        return self.hit_requests / total if total else 0.0


# ---------------------------------------------------------------------------
# Cache pytree construction
# ---------------------------------------------------------------------------


def _paged_kind_cache(cfg: ModelConfig, batch: int, pool: PoolConfig,
                      rt: Runtime, lead: tuple = ()) -> dict:
    cd = rt.compute_dtype
    Hk, Dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "k_pages": jnp.zeros(lead + (pool.n_pages, pool.page_size, Hk, Dh), cd),
        "v_pages": jnp.zeros(lead + (pool.n_pages, pool.page_size, Hk, Dh), cd),
        "page_table": jnp.zeros(lead + (batch, pool.max_pages_per_seq),
                                jnp.int32),
    }


def build_paged_caches(cfg: ModelConfig, batch: int, pool: PoolConfig,
                       rt: Runtime, place=None) -> dict:
    """Engine cache pytree: paged pools for full-attention kinds, dense rings
    for sliding-window kinds, O(1) states for recurrent kinds.  ``place``
    (shape tree -> sharding tree) builds each leaf where it will live."""
    build = functools.partial(_build_paged_caches, cfg, batch, pool, rt)
    if place is None:
        return build()
    return jax.jit(build, out_shardings=place(jax.eval_shape(build)))()


def _build_paged_caches(cfg: ModelConfig, batch: int, pool: PoolConfig,
                        rt: Runtime) -> dict:
    plan = make_layer_plan(cfg.num_layers, cfg.block_pattern)

    def kind_cache(kind: str, lead: tuple):
        if kind in ("attn", "global"):
            return _paged_kind_cache(cfg, batch, pool, rt, lead)
        # "local" (bounded ring) and recurrent kinds: window-capacity dense
        cap = cfg.window_size if kind == "local" and cfg.window_size else \
            pool.max_pages_per_seq * pool.page_size
        return _kind_cache(kind, cfg, batch, cap, rt, lead)

    scan = [kind_cache(k, (plan.n_periods,)) for k in plan.period_kinds] \
        if plan.n_periods else []
    tail = [kind_cache(k, ()) for k in plan.tail_kinds]
    return {"scan": scan, "tail": tail}


def _map_paged_leaves(caches: dict, fn):
    """Apply ``fn(layer_cache_dict, stacked: bool)`` to every attention-kind
    sub-dict in the cache pytree, returning a new pytree."""
    def one(c, stacked):
        if isinstance(c, dict) and ("k_pages" in c or "pos" in c):
            return fn(c, stacked)
        return c
    return {
        "scan": [one(c, True) for c in caches["scan"]],
        "tail": [one(c, False) for c in caches["tail"]],
    }


def set_page_table(caches: dict, table: np.ndarray) -> dict:
    """Broadcast the host page table (B, max_pages) into every paged layer."""
    dev = jnp.asarray(table, jnp.int32)

    def fn(c, stacked):
        if "page_table" not in c:
            return c
        t = c["page_table"]
        new = jnp.broadcast_to(dev[None], t.shape) if stacked else dev
        return {**c, "page_table": new}
    return _map_paged_leaves(caches, fn)


def reset_slot(caches: dict, cfg: ModelConfig, slot: int,
               rt: Runtime) -> dict:
    """Clear per-slot state when a decode slot is reassigned: ring positions
    back to -1, recurrent states back to init.  Paged pools need no clearing
    (validity is governed by seq_lens)."""
    def clear(c, stacked):
        if "k_pages" in c:
            return c
        out = dict(c)
        idx = (slice(None), slot) if stacked else (slot,)
        if "pos" in c:
            out["pos"] = c["pos"].at[idx].set(-1)
            return out
        for name, leaf in c.items():      # recurrent states
            init = 1e-6 if name == "n" and leaf.ndim == (2 + int(stacked)) \
                else 0.0
            out[name] = leaf.at[idx].set(init)
        return out
    return _map_paged_leaves(caches, clear)


def _split_shared(c: dict):
    """Split one layer cache dict into (shared pool leaves, per-slot
    leaves).  Pool leaves (``*_pages``) are batch-global; everything else
    (page tables, rings, recurrent states) has a batch axis."""
    shared = {k: v for k, v in c.items() if k.endswith("_pages")}
    per = {k: v for k, v in c.items() if not k.endswith("_pages")}
    return shared, per


def slot_view(caches: dict, start, size: int) -> dict:
    """A ``size``-row view of the batch axis starting at ``start`` (traced
    values ok).  Per-slot leaves are batched on axis 1 in "scan" (period-
    stacked) and axis 0 in "tail"; shared page pools pass through whole."""
    out = {"scan": [], "tail": []}
    for part, axis in (("scan", 1), ("tail", 0)):
        for c in caches[part]:
            shared, per = _split_shared(c)
            view = jax.tree.map(lambda x: jax.lax.dynamic_slice_in_dim(
                x, start, size, axis=axis), per)
            out[part].append({**shared, **view})
    return out


def slot_merge(caches: dict, view: dict, start) -> dict:
    """Splice an updated row view back into the batch-wide caches.  Pool
    leaves are taken from the view (decode/prefill write KV into them);
    per-slot rows are spliced at ``start``."""
    out = {"scan": [], "tail": []}
    for part, axis in (("scan", 1), ("tail", 0)):
        for c_old, c_new in zip(caches[part], view[part]):
            shared_new, per_new = _split_shared(c_new)
            _, per_old = _split_shared(c_old)
            merged = jax.tree.map(
                lambda f, p: jax.lax.dynamic_update_slice_in_dim(
                    f, p.astype(f.dtype), start, axis=axis),
                per_old, per_new)
            out[part].append({**shared_new, **merged})
    return out


def kv_bytes_per_page(cfg: ModelConfig, pool: PoolConfig,
                      dtype_bytes: int = 2) -> int:
    """Bytes one page occupies across all paged layers (k+v)."""
    n_paged = sum(1 for k in cfg.layer_kinds() if k in ("attn", "global"))
    return (2 * n_paged * pool.page_size * cfg.num_kv_heads * cfg.head_dim
            * dtype_bytes)


def global_slice(pool: PoolConfig, pool_id: int) -> slice:
    r = pool.global_range(pool_id)
    return slice(r.start, r.stop)
