"""Paged KV cache: device pools + the host-side page allocator.

The device side is a per-layer pool pytree (``model.init_kv_pools``)
shaped ``[num_pages, page_size, heads * head_dim]`` whose contents the
jitted prefill/decode steps update functionally (ops/paged_attention);
the host side here owns which pages belong to whom: a free list, the
per-slot page assignments, and the occupancy/eviction accounting. Page
0 is the reserved trash page (masked writes land there) and is never
handed out.

Pages are REFCOUNTED so the prefix cache can share immutable full
pages across sequences (serving/generation/prefix_cache.py): ``alloc``
hands out pages at refcount 1, ``retain`` adds a sharer (a second
sequence mapping the page into its block table, or the prefix index
pinning a published page), and ``release``/``free`` drop one
reference — a page returns to the free list only when its LAST
reference goes away. Freeing a *shared* page therefore decrements
instead of double-returning it (the eviction-accounting bug class
``assert_no_leaks`` exists to catch).

Two kinds of layer (``model.kv_cache_spec()["kinds"]``): a ``full``
layer keeps the whole context, and everything above is about its pool.
A ``window`` layer keeps the last ``window`` positions, so a sequence
holds a *ring* of at most ``ring_pages`` pages of it however long it
grows (logical page ``n`` in ring entry ``n % ring_pages``;
ops/paged_attention.py), and its pool is sized from the lanes, not from
the context: ``1 + max_batch * ring_pages`` pages, no second knob. All
full layers share one page numbering and one table, all window layers
another; this module owns both: the layout of the one table row the
engine feeds (the full table's columns, then the ring's), allocation
and freeing. Ring pages are a sequence's own, never shared: the prefix
cache cannot index a layer that forgets.

Thread-safety: the engine's worker thread is the only mutator; the
allocator itself is plain data guarded by the engine lock.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

__all__ = ["PagedKVCache", "window_of"]


def window_of(spec: dict) -> Optional[int]:
    """The window of a model's ``window`` kind of layer, from its
    ``kv_cache_spec()``; None where every layer keeps the whole
    context (or the model declares no kinds)."""
    kind = (spec.get("kinds") or {}).get("window")
    return int(kind["window"]) if kind else None


class PagedKVCache:
    """Host bookkeeping for one pool pytree.

    ``num_pages`` INCLUDES the trash page, so ``capacity`` (allocatable
    pages) is ``num_pages - 1``. ``alloc`` is all-or-nothing: a request
    that cannot get its full reservation gets nothing, so admission
    control can retry later without partial-reservation leaks.
    """

    def __init__(self, model, num_pages: int, page_size: int,
                 dtype=None, mesh=None, max_batch: Optional[int] = None):
        if num_pages < 2:
            raise ValueError("need at least one allocatable page plus "
                             "the trash page")
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        # dtype may be the string "int8" — pools then carry per-slot
        # absmax scales alongside int8 values (ops/paged_attention)
        self.kv_dtype = dtype if isinstance(dtype, str) else ""
        # the window kind: a ring a lane, a pool sized from the lanes
        self.window = window_of(model.kv_cache_spec())
        self.ring_pages = 0
        self.window_num_pages = 0
        if self.window is None:
            self.k, self.v = model.init_kv_pools(self.num_pages,
                                                 self.page_size, dtype)
        else:
            if not max_batch:
                raise ValueError(
                    "a model with window layers sizes their pool from "
                    "the lanes: PagedKVCache needs max_batch")
            from ...ops.paged_attention import ring_pages
            self.ring_pages = ring_pages(self.window, self.page_size)
            self.window_num_pages = 1 + int(max_batch) * self.ring_pages
            self.k, self.v = model.init_kv_pools(
                self.num_pages, self.page_size, dtype,
                window_pages=self.window_num_pages)
        self._window_free: List[int] = list(
            range(self.window_num_pages - 1, 0, -1))
        self._window_held = set()
        self.window_pages_recycled = 0
        # serving mesh (serving/mesh.py): heads-sharded committed
        # placement of the pool leaves. EVERYTHING host-side below —
        # free list, refcounts, block tables — is layout-agnostic and
        # identical with or without a mesh; only device bytes move.
        if mesh is not None:
            from ..mesh import ServingMesh
            smesh = mesh if isinstance(mesh, ServingMesh) \
                else ServingMesh(mesh)
            self.k, self.v = smesh.place_pools(self.k, self.v)
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._ref: Dict[int, int] = {}      # page -> live reference count
        self.evicted_pages_total = 0

    # ---- geometry ----
    @property
    def capacity(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        """Pages some sequence or the prefix index holds, of either
        kind: 0 is what a drained server shows."""
        return self.capacity - len(self._free) + len(self._window_held)

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` positions."""
        return max(1, math.ceil(tokens / self.page_size))

    # ---- the window kind ----
    @property
    def window_capacity(self) -> int:
        return max(0, self.window_num_pages - 1)

    def table_width(self, max_seq_len: int) -> int:
        """Columns of the one block-table row a sequence has: the full
        layers' table, then the window layers' ring."""
        return self.pages_for(max_seq_len) + self.ring_pages

    def alloc_window(self, tokens: int) -> Optional[List[int]]:
        """The ring pages of a sequence that will hold ``tokens``
        positions at most: what it needs, never more than
        ``ring_pages``. [] for a model without window layers; None (and
        nothing taken) if the pool is short, which ``max_batch`` lanes
        cannot make it."""
        if self.window is None:
            return []
        n = min(self.ring_pages, self.pages_for(tokens))
        if n > len(self._window_free):
            return None
        taken = self._window_free[-n:]
        del self._window_free[-n:]
        self._window_held.update(taken)
        return taken

    def release_window(self, pages: List[int]) -> None:
        for p in pages:
            if p not in self._window_held:
                raise RuntimeError(
                    f"double free: window page {p} is not held")
            self._window_held.remove(p)
            self._window_free.append(p)

    def fill_row(self, row, pages: List[int],
                 window_pages: List[int]) -> None:
        """Write a sequence's pages into its block-table row."""
        row[:] = 0
        row[:len(pages)] = pages
        if window_pages:
            at = len(row) - self.ring_pages
            row[at:at + len(window_pages)] = window_pages

    def note_positions(self, lo: int, hi: int) -> None:
        """A sequence wrote (or skipped past) positions ``lo .. hi-1``
        of its window layers: count the ring entries that now hold a
        later page than their first (``window_pages_recycled``)."""
        if self.window is None or hi <= lo:
            return
        first = max(-(-lo // self.page_size), self.ring_pages)
        last = (hi - 1) // self.page_size
        self.window_pages_recycled += max(0, last - first + 1)

    def by_kind(self) -> dict:
        """``metrics_snapshot()["engine"]["kv"]``: pages in use and
        capacity of each kind's pool, and the ring entries that came
        to hold a later page than their first."""
        kinds = {"full": (self.capacity - len(self._free), self.capacity)}
        if self.window is not None:
            kinds["window"] = (len(self._window_held),
                               self.window_capacity)
        return {"pages_in_use": {k: v[0] for k, v in kinds.items()},
                "capacity": {k: v[1] for k, v in kinds.items()},
                "window_pages_recycled": self.window_pages_recycled}

    def pool_bytes(self) -> int:
        """Device bytes resident in the K+V pools (quantized pools
        count their scale planes — that is the honest cost the sizing
        math and shardcheck's projection gate both work from)."""
        import jax
        return sum(int(a.size) * int(a.dtype.itemsize)
                   for a in jax.tree_util.tree_leaves((self.k, self.v)))

    # ---- allocation ----
    def alloc(self, n_pages: int) -> Optional[List[int]]:
        """Take ``n_pages`` from the free list (each at refcount 1), or
        None (and take nothing) if fewer are free."""
        if n_pages > len(self._free):
            return None
        taken = self._free[-n_pages:]
        del self._free[-n_pages:]
        for p in taken:
            self._ref[p] = 1
        return taken

    def retain(self, pages: List[int]) -> None:
        """Add one reference to each already-allocated page — a second
        sequence sharing a cached prefix page, or the prefix index
        pinning a published page."""
        for p in pages:
            if self._ref.get(p, 0) < 1:
                raise ValueError(f"retain of unallocated page {p}")
            self._ref[p] += 1

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def release(self, pages: List[int]) -> int:
        """Drop one reference per page; pages whose last reference goes
        away return to the free list (their eviction from the pool —
        contents stay as garbage until rewritten; correctness relies on
        block tables, not on zeroing). Returns the number of pages
        actually freed, which for shared pages is less than
        ``len(pages)``."""
        freed = 0
        for p in pages:
            if not 0 < p < self.num_pages:
                raise ValueError(f"page {p} out of range")
            n = self._ref.get(p, 0)
            if n < 1:
                raise RuntimeError(
                    f"double free: page {p} has no live references")
            if n == 1:
                del self._ref[p]
                self._free.append(p)
                freed += 1
            else:
                self._ref[p] = n - 1
        self.evicted_pages_total += freed
        if len(self._free) > self.capacity:
            raise RuntimeError("double free: free list exceeds capacity")
        return freed

    def free(self, pages: List[int]) -> int:
        """Return a finished sequence's references. Alias of
        ``release`` — kept because "free" is the engine-side verb; a
        SHARED page is only decremented here, never pushed back onto
        the free list while another sequence (or the prefix index)
        still maps it."""
        return self.release(pages)

    # ---- invariants ----
    def leak_check(self) -> dict:
        """Accounting snapshot: free + referenced must cover capacity
        exactly, with no page both free and referenced. Cheap enough
        for /statusz."""
        free_set = set(self._free)
        overlap = sorted(free_set & set(self._ref))
        bad_refs = sorted(p for p, n in self._ref.items() if n < 1)
        window_leaked = self.window_capacity - len(self._window_free) \
            - len(self._window_held)
        overlap += sorted(set(self._window_free) & self._window_held)
        return {
            "capacity": self.capacity,
            "free": len(self._free),
            "referenced": len(self._ref),
            "leaked": self.capacity - len(self._free) - len(self._ref)
            + window_leaked,
            "double_booked": overlap,
            "nonpositive_refcounts": bad_refs,
            "ok": (len(self._free) + len(self._ref) == self.capacity
                   and not window_leaked and not overlap
                   and not bad_refs),
        }

    def assert_no_leaks(self) -> None:
        """Raise if any page is neither free nor referenced (or both) —
        the refcount-leak tripwire tests and /statusz run after
        admit/share/finish/evict cycles."""
        chk = self.leak_check()
        if not chk["ok"]:
            raise AssertionError(f"KV page accounting leak: {chk}")
