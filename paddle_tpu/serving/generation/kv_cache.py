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

A third kind, ``state``: a layer that keeps a recurrent state a
sequence (a state-space mixer's convolution tail and SSM state;
``bytes_per_slot`` however long the sequence grows) holds one *slot* of
its pools a sequence. Those pools too are sized from the lanes, ``1 +
max_batch`` slots with slot 0 the trash slot, and the slot is the last
column of the table row: a prefill row is not a lane, so a program
finds a row's slot in its table row. A slot is a sequence's own, handed
out at admission and taken back with its pages; what it held is not
cleared (a prefill writes the slot from zeros, it never reads it).

A model whose ``full`` kind lists no layer (every layer keeps a state)
holds no pages: a sequence needs none, and its table row is the state
slot alone.

A latent attention (``kinds["full"]["latent"]``) keeps the full kind's
pages and its one table, but a layer's pool holds one row a token for
every head (the latent and the rotary key part all heads share) and no
V: its bytes are reported as ``pool_bytes["latent"]`` and compared with
``kv_cache_spec()`` when the pools are built, as the state pools' are.

Thread-safety: the engine's worker thread is the only mutator; the
allocator itself is plain data guarded by the engine lock.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

__all__ = ["PagedKVCache", "window_of", "has_state", "latent_of"]


def window_of(spec: dict) -> Optional[int]:
    """The window of a model's ``window`` kind of layer, from its
    ``kv_cache_spec()``; None where every layer keeps the whole
    context (or the model declares no kinds)."""
    kind = (spec.get("kinds") or {}).get("window")
    return int(kind["window"]) if kind else None


def has_state(spec: dict) -> bool:
    """Whether a model's ``kv_cache_spec()`` names layers of the
    ``state`` kind."""
    return "state" in (spec.get("kinds") or {})


def latent_of(spec: dict) -> Optional[int]:
    """The width of a latent attention's cached row (the ``full`` kind's
    ``latent``: one row a token for every head, no V), from a model's
    ``kv_cache_spec()``; None where the full layers cache K and V."""
    full = (spec.get("kinds") or {}).get("full") or {}
    return full.get("latent")


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
        spec = model.kv_cache_spec()
        # the full kind: no layer of it, no page a sequence
        self.paged = bool(((spec.get("kinds") or {}).get("full") or {})
                          .get("layers", True))
        self.window = window_of(spec)
        self.ring_pages = 0
        self.window_num_pages = 0
        # the state kind: a slot a lane, pools sized from the lanes
        self.state_num_slots = 0
        by_lanes = {}       # what init_kv_pools is told beyond the pages
        state = has_state(spec)
        if (self.window is not None or state) and not max_batch:
            raise ValueError(
                "a model with window or state layers sizes their pools "
                "from the lanes: PagedKVCache needs max_batch")
        if self.window is not None:
            from ...ops.paged_attention import ring_pages
            self.ring_pages = ring_pages(self.window, self.page_size)
            self.window_num_pages = 1 + int(max_batch) * self.ring_pages
            by_lanes["window_pages"] = self.window_num_pages
        if state:
            self.state_num_slots = 1 + int(max_batch)
            by_lanes["state_slots"] = self.state_num_slots
        self.k, self.v = model.init_kv_pools(self.num_pages,
                                             self.page_size, dtype,
                                             **by_lanes)
        self._window_free: List[int] = list(
            range(self.window_num_pages - 1, 0, -1))
        self._window_held = set()
        self.window_pages_recycled = 0
        self._state_free: List[int] = list(
            range(self.state_num_slots - 1, 0, -1))
        self._state_held = set()
        self._pool_bytes_by_kind = self._bytes_by_kind(spec)
        if state:
            # the arrays, not the spec's word: a state pool in another
            # precision than kv_cache_spec() states is a slot of other
            # bytes, and is refused here
            want = int(spec["kinds"]["state"]["bytes_per_slot"])
            got = self._pool_bytes_by_kind["state"] / self.state_num_slots
            if got != want:
                raise ValueError(
                    f"the state pools hold {got:g} bytes a slot and "
                    f"kv_cache_spec() states {want}: the pools' shapes "
                    f"or types are not the spec's")
        self.latent = latent_of(spec)
        if self.latent:
            # likewise a latent pool: one row a token for all heads
            want = int(model.kv_cache_spec(
                self.kv_dtype)["kv_bytes_per_token"])
            got = self._pool_bytes_by_kind["latent"] / (
                self.num_pages * self.page_size)
            if got != want:
                raise ValueError(
                    f"the latent pools hold {got:g} bytes a token and "
                    f"kv_cache_spec() states {want}: the pools' shapes "
                    f"or types are not the spec's")
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
        """Pages and state slots some sequence or the prefix index
        holds, of every kind: 0 is what a drained server shows."""
        return self.capacity - len(self._free) + len(self._window_held) \
            + len(self._state_held)

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` positions (none where no
        layer keeps pages)."""
        if not self.paged:
            return 0
        return max(1, math.ceil(tokens / self.page_size))

    # ---- the window kind ----
    @property
    def window_capacity(self) -> int:
        return max(0, self.window_num_pages - 1)

    def table_width(self, max_seq_len: int) -> int:
        """Columns of the one block-table row a sequence has: the full
        layers' table, then the window layers' ring, then the state
        slot."""
        return self.pages_for(max_seq_len) + self.ring_pages \
            + self.state_columns

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

    # ---- the state kind
    @property
    def state_capacity(self) -> int:
        return max(0, self.state_num_slots - 1)

    @property
    def state_columns(self) -> int:
        """Columns the state slot takes of a table row: 1, or 0 for a
        model without state layers."""
        return 1 if self.state_num_slots else 0

    def alloc_state(self) -> Optional[int]:
        """The slot of a sequence's recurrent state. 0 (no slot) for a
        model without state layers; None if none is free, which
        ``max_batch`` lanes cannot make it."""
        if not self.state_num_slots:
            return 0
        if not self._state_free:
            return None
        slot = self._state_free.pop()
        self._state_held.add(slot)
        return slot

    def release_state(self, slot: int) -> None:
        if not slot:
            return
        if slot not in self._state_held:
            raise RuntimeError(f"double free: state slot {slot} is not "
                               f"held")
        self._state_held.remove(slot)
        self._state_free.append(slot)

    def fill_row(self, row, pages: List[int], window_pages: List[int],
                 state_slot: int = 0) -> None:
        """Write a sequence's pages and state slot into its
        block-table row."""
        row[:] = 0
        row[:len(pages)] = pages
        if window_pages:
            at = len(row) - self.ring_pages - self.state_columns
            row[at:at + len(window_pages)] = window_pages
        if self.state_columns:
            row[-1] = state_slot

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
        """``metrics_snapshot()["engine"]["kv"]``: pages (of the
        ``state`` kind: slots) in use and capacity of each kind's
        pool, the pools' device bytes by kind, and the ring entries
        that came to hold a later page than their first."""
        kinds = {"full": (self.capacity - len(self._free), self.capacity)}
        if self.window is not None:
            kinds["window"] = (len(self._window_held),
                               self.window_capacity)
        if self.state_num_slots:
            kinds["state"] = (len(self._state_held), self.state_capacity)
        return {"pages_in_use": {k: v[0] for k, v in kinds.items()},
                "capacity": {k: v[1] for k, v in kinds.items()},
                "pool_bytes": self._pool_bytes_by_kind,
                "window_pages_recycled": self.window_pages_recycled}

    def _bytes_by_kind(self, spec: dict) -> dict:
        """Device bytes of each kind's pools (the layers
        ``kv_cache_spec()["kinds"]`` lists under it; one stacked pool
        is all ``full``; a latent attention's full pools are named
        ``latent``)."""
        import jax

        def nbytes(tree):
            return sum(int(a.size) * int(a.dtype.itemsize)
                       for a in jax.tree_util.tree_leaves(tree))

        kinds = spec.get("kinds")
        if not kinds or not isinstance(self.k, list):
            return {"full": nbytes((self.k, self.v))}
        return {"latent" if what.get("latent") else kind:
                sum(nbytes((self.k[i], self.v[i])) for i in what["layers"])
                for kind, what in kinds.items()}

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
        if not n_pages:
            return []
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
        state_leaked = self.state_capacity - len(self._state_free) \
            - len(self._state_held)
        overlap += sorted(set(self._state_free) & self._state_held)
        return {
            "capacity": self.capacity,
            "free": len(self._free),
            "referenced": len(self._ref),
            "leaked": self.capacity - len(self._free) - len(self._ref)
            + window_leaked + state_leaked,
            "double_booked": overlap,
            "nonpositive_refcounts": bad_refs,
            "ok": (len(self._free) + len(self._ref) == self.capacity
                   and not window_leaked and not state_leaked
                   and not overlap
                   and not bad_refs),
        }

    def assert_no_leaks(self) -> None:
        """Raise if any page is neither free nor referenced (or both) —
        the refcount-leak tripwire tests and /statusz run after
        admit/share/finish/evict cycles."""
        chk = self.leak_check()
        if not chk["ok"]:
            raise AssertionError(f"KV page accounting leak: {chk}")
