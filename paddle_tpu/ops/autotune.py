"""Kernel autotuning — block-size search with a persistent cache.

Reference: paddle/phi/kernels/autotune/ (gpu-timer based algo selection +
cache for conv algos / layout). TPU-native: Pallas grid/block choices are
the tunable axis; candidates are timed on the real device at first use
per (kernel, shape-key) and the winner is cached (in-process + on-disk
json so later processes skip the search).
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Sequence, Tuple

import jax

from ..framework import place as _place

_CACHE_ENV = "PADDLE_TPU_AUTOTUNE_CACHE"
_cache: Dict[str, list] = {}
_loaded = False


def _cache_path() -> str:
    """Where ``PADDLE_TPU_AUTOTUNE_CACHE`` says, else inside the
    checkout next to the compile caches."""
    from ..compile_cache import cache_root
    return os.environ.get(_CACHE_ENV) or os.path.join(
        cache_root(), "autotune.json")


def _load():
    global _loaded
    if _loaded:
        return
    _loaded = True
    try:
        with open(_cache_path()) as f:
            _cache.update(json.load(f))
    except FileNotFoundError:
        pass


def _save():
    path = _cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(_cache, f)
    os.replace(tmp, path)


def enabled() -> bool:
    """Autotuning only makes sense on the chip (interpret-mode timings
    are meaningless) and is opt-out via FLAGS."""
    from ..framework.flags import flag_value
    return bool(flag_value("FLAGS_use_autotune")) and _place.on_tpu()


def _cache_key(kernel: str, key: Sequence) -> str:
    return f"{kernel}/{'_'.join(map(str, key))}"


def cached(kernel: str, key: Sequence):
    """Prior tuning result for (kernel, key), or None — usable from traced
    code where timing is impossible."""
    _load()
    hit = _cache.get(_cache_key(kernel, key))
    return tuple(hit) if hit else None


def pick(kernel: str, key: Sequence, candidates: List[Tuple],
         make_fn: Callable[[Tuple], Callable], args,
         warmup: int = 1, iters: int = 3) -> Tuple:
    """Return the fastest candidate configuration for ``kernel`` at
    ``key``, timing each with ``make_fn(cand)(*args)`` on first use.

    A candidate may fail only by asking for more of a device resource
    than there is (a tile too large for VMEM: the compiler's
    RESOURCE_EXHAUSTED) — that is what the search is meant to skip.
    Any other failure is the kernel's and is raised; so is a search in
    which no candidate compiled."""
    _load()
    ck = _cache_key(kernel, key)
    if ck in _cache:
        return tuple(_cache[ck])
    if not enabled() or len(candidates) == 1:
        return candidates[0]
    best, best_t, refused = None, float("inf"), []
    for cand in candidates:
        try:
            fn = make_fn(cand)
            out = fn(*args)
            jax.block_until_ready(out)     # compile + warm
            for _ in range(max(warmup - 1, 0)):
                out = fn(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / iters
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            refused.append(f"{cand}: {str(e).splitlines()[0][:200]}")
            continue
        if dt < best_t:
            best, best_t = cand, dt
    if best is None:
        raise RuntimeError(
            f"autotune {ck}: none of {len(candidates)} candidates "
            f"fits the device: " + "; ".join(refused))
    _cache[ck] = list(best)
    _save()
    return best


# ------------------- fused paged serving kernels (pallas_paged_attention)
#
# Their blocks are constants: a serving call sits inside a trace, where
# nothing can be timed, and a server does not time kernels when it
# starts. What is here was found on a v5e at the benchmark's serving
# shapes, 16-slot pages, one call of one layer over pools laid out
# ``[pages, page_size, kv_heads * head_dim]`` (PERF.md section 6, PR 30;
# PR 26 for the pools that had a heads axis): 16 heads of 128 in f32
# over 128-page tables, 7 of 16 lanes live with 2,697 positions; 16
# heads of 64 in f32 over 64-page tables, 32 lanes with 12,810; 28 query
# heads over 4 K/V heads of 128 in bf16, 32 lanes with 125,150 positions
# (576-page tables) or the last 4,096 of each (a ring of 257).

# VMEM the decode kernel's page buffers may take (K and V, two slots
# each): half the 16 MiB a v5e program gets unasked.
PAGED_DECODE_VMEM_BYTES = 8 * 1024 * 1024
# Pages a decode grid program copies and attends at a time. More pages
# amortize the loop and the running softmax's rescale over more
# positions; fewer waste less of a lane's last, part-filled chunk.
# One row for all heads on the vector unit, ms a call: 64-wide heads
# 2 pages 0.232, 4 0.191, 8 0.204, 16 0.212 (the grid kernel 0.485);
# 128-wide heads 4 0.074, 8 0.076 (the grid kernel 0.380).
PAGED_DECODE_PAGES_PER_CHUNK = 4
# The same where a K/V head serves a group of query heads and the MXU
# carries a chunk in two products: a longer chunk is a better-filled
# product. ms a call, whole context / ring: 4 pages 1.040 / 0.944,
# 8 0.728 / 0.687, 16 0.596 / 0.576, 32 0.531 / 0.529, 64 0.515 /
# 0.524; the kernel's page copies are unrolled, so it compiles in 1.8,
# 2.7, 4.4, 7.6-14.3 and 15-27 s: 16 has nine tenths of the gain.
PAGED_DECODE_PAGES_PER_CHUNK_MXU = 16


def paged_decode_chunk(page_size: int, lanes: int, itemsize: int,
                       pages_per_seq: int, on_mxu: bool = False,
                       override=None) -> int:
    """Pages per chunk of the decode kernel for a pool whose page is
    ``[page_size, lanes]`` (``paged_attention.kv_pool_shape``): the
    constant of whoever attends a chunk (the vector unit, or the MXU
    where there are groups of query heads), cut to what the table holds
    and to what fits the kernel's VMEM (a buffer pads to the native
    tile: 8 sublanes of 32 bits, 128 lanes)."""
    if override is not None:
        return _chunk_override(override)
    chunk = PAGED_DECODE_PAGES_PER_CHUNK_MXU if on_mxu \
        else PAGED_DECODE_PAGES_PER_CHUNK
    return _chunk_fit(chunk, 4, page_size, lanes, itemsize, pages_per_seq)


# The latent kernel's (``_latent_decode_kernel``: a lane's 20 query
# heads in one MXU product over a chunk) at the latent cell's shape:
# 128 lanes, 640-lane bfloat16 rows (576 live), 16-slot pages, 257-page
# tables over 32,897 pages, contexts drawn as ``backlog-reasoning``
# holds them (mean 1,396 positions), on a v5e. ms a call, the copies
# started in loops, a lane's first chunk started by the program before
# it / by its own:
#   pages a chunk          16            32            64            128
#   a product, the chunk   0.661/0.704   0.525/0.565   0.475/0.560   0.485
#   ... its live pages
#       rounded up to 32   -             -             0.466         0.441
#       rounded up to 16   -             0.528/0.565   0.463/0.544   0.442
# The unrolled kernel before them (16 pages, its own first chunk):
# 0.716. At 64 pages, copies in groups of 8 and waits a set bit of the
# count read 0.479; a wait a page 0.597, a loop of single copies 0.562,
# a third or fourth slot 0.49. Under a wait a page, products of 16
# pages in a loop read 0.790 against 0.638 for one. Each compiles in
# 1-3 s.
PAGED_LATENT_PAGES_PER_CHUNK = 128
# Page copies started back to back in one step of the loop that starts
# a chunk's (8 read the same at 64 pages, 4 3% slower).
PAGED_LATENT_COPY_GROUP = 16
# A chunk's product takes its live pages rounded up to this many.
PAGED_LATENT_PRODUCT_STEP = 32


def paged_latent_chunk(page_size: int, lanes: int, itemsize: int,
                       pages_per_seq: int, override=None) -> Tuple[int, int]:
    """``(pages per chunk, pages per product step)`` of the latent
    decode kernel for a pool whose page is ``[page_size, lanes]``: its
    own constants, the chunk cut to what the table holds and to what
    fits the kernel's VMEM (one pool, two slots), the step a divisor of
    the chunk. ``override`` names the chunk."""
    chunk = _chunk_override(override) if override is not None else \
        _chunk_fit(PAGED_LATENT_PAGES_PER_CHUNK, 2, page_size, lanes,
                   itemsize, pages_per_seq)
    step = PAGED_LATENT_PRODUCT_STEP
    return chunk, step if chunk % step == 0 else chunk


def _chunk_override(override) -> int:
    if override < 1:
        raise ValueError(f"pages_per_chunk must be >= 1, got {override}")
    return int(override)


def _chunk_fit(chunk: int, buffers: int, page_size: int, lanes: int,
               itemsize: int, pages_per_seq: int) -> int:
    """``chunk`` cut to what the table holds and to what ``buffers``
    chunk buffers may take of PAGED_DECODE_VMEM_BYTES (a buffer pads to
    the native tile: 8 sublanes of 32 bits, 128 lanes)."""
    sublanes = 8 * max(1, 4 // itemsize)
    page_bytes = (-(-page_size // sublanes) * sublanes
                  * -(-lanes // 128) * 128 * itemsize)
    fit = PAGED_DECODE_VMEM_BYTES // (buffers * page_bytes)
    return int(max(1, min(chunk, pages_per_seq, fit)))


# Pages a grid-kernel program attends for decode (a tile of that many
# table-steered blocks): fewer grid steps a lane, more blocks a step to
# steer. 64-wide heads, ms a call of 24 layers (PR 26): 1 page 17.6, 2
# 15.0, 4 14.0, 8 14.3, 16 14.9 (the gather: 63.3). At 128-wide heads
# the same kernel takes 8.7 at 4 pages, the page-copying kernel 1.83:
# every table slot, live or dead, costs this kernel some 0.18 us of
# block steering. Over the folded pool (PR 30) it reads the same: 0.485
# and 0.380 ms a layer, so it serves quantized pools, windows of
# queries and rows narrower than a lane tile, and no float decode.
PAGED_DECODE_PAGES_PER_TILE = 4


def paged_block_candidates(kind: str, seq: int, num_heads: int,
                           head_dim: int, page_size: int,
                           pages_per_seq: int,
                           quantized: bool = False) -> List[Tuple]:
    """Every legal ``(block_q, block_h, pages_per_tile)`` of the grid
    kernel (chunked windows; decode over quantized pools).

    Legal means what the TPU lowering takes: the last two dims of a
    block are the whole array's or multiples of the native (8, 128)
    tile. Heads is the second-minor dim of the q blocks
    ``[.., block_h, D]``, the major factor of the minor dim of the pool
    blocks ``[page_size, block_h * D]`` and the minor dim of a
    quantized pool's scale blocks ``[page_size, block_h]``; block_q is
    the second-minor dim of the ``[block_q, 1]`` position/valid
    columns.

    - block_q tiles the query window (decode is structurally S == 1;
      chunked windows tile at multiples of 8 up to the 128-row register
      tile, the same ladder flash uses, or take the window whole);
    - block_h is the head-block per grid program: all heads first (the
      default — always legal), then 8-multiples that divide them
      (128-multiples under quantized pools);
    - pages_per_tile makes the K-tile a page-size multiple: a tile
      spanning n table-adjacent pages is realized as n table-steered
      block loads per program (pool pages are not address-adjacent, so
      a bigger BlockSpec cannot express it).
    """
    if kind == "decode":
        bqs = [1]
    else:
        bqs = sorted({c for c in (8, 16, 32, 64, 128)
                      if c <= seq and seq % c == 0} | {seq})
    tile = 128 if quantized else 8
    bhs = [num_heads] + [c for c in (8, 16, 32, 64, 128)
                         if c % tile == 0 and c < num_heads
                         and num_heads % c == 0]
    ppts = [c for c in (1, 2, 4, 8) if pages_per_seq % c == 0] or [1]
    return [(bq, bh, ppt) for bq in bqs for bh in bhs for ppt in ppts]


def paged_blocks(kind: str, seq: int, num_heads: int, head_dim: int,
                 page_size: int, pages_per_seq: int, *,
                 overrides=(None, None, None)) -> Tuple[int, int, int]:
    """``(block_q, block_h, pages_per_tile)`` for one grid-kernel call:
    explicit overrides, else all heads and, for a window, 8-multiples
    of its rows up to 128 with one page a tile; for decode, the largest
    tile up to PAGED_DECODE_PAGES_PER_TILE pages that divides the
    table."""
    if kind == "decode":
        defaults = (1, num_heads, max(
            c for c in (1, 2, 4, 8)
            if c <= PAGED_DECODE_PAGES_PER_TILE and pages_per_seq % c == 0))
    else:
        defaults = (_fit_block_q(seq), num_heads, 1)
    bq, bh, ppt = (o if o is not None else d
                   for o, d in zip(overrides, defaults))
    if seq % bq or num_heads % bh or pages_per_seq % ppt:
        raise ValueError(
            f"paged blocks (block_q={bq}, block_h={bh}, "
            f"pages_per_tile={ppt}) must divide (seq={seq}, "
            f"heads={num_heads}, pages_per_seq={pages_per_seq})")
    return int(bq), int(bh), int(ppt)


def _fit_block_q(seq: int, cap: int = 128) -> int:
    """Largest 8-multiple power of two up to ``cap`` that divides the
    window, else the window whole (both legal tiles)."""
    fits = [c for c in (8, 16, 32, 64, 128) if c <= cap and seq % c == 0]
    return max(fits) if fits else seq
