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

# Kernel names under which paged block choices persist in the cache.
PAGED_KERNELS = ("paged_decode", "paged_chunked")


def paged_block_candidates(kind: str, seq: int, num_heads: int,
                           head_dim: int, page_size: int,
                           pages_per_seq: int,
                           quantized: bool = False) -> List[Tuple]:
    """Block-size table for the fused paged kernels: every legal
    ``(block_q, block_h, pages_per_tile)``.

    Legal means what the TPU lowering takes: the last two dims of a
    block are the whole array's or multiples of the native (8, 128)
    tile. Heads is the second-minor dim of the q and pool blocks
    ``[.., block_h, D]`` and the minor dim of a quantized pool's scale
    blocks ``[page_size, block_h]``; block_q is the second-minor dim
    of the ``[block_q, 1]`` position/valid columns.

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
    ppts = [c for c in (1, 2, 4) if pages_per_seq % c == 0] or [1]
    return [(bq, bh, ppt) for bq in bqs for bh in bhs for ppt in ppts]


def paged_blocks(kind: str, seq: int, num_heads: int, head_dim: int,
                 page_size: int, pages_per_seq: int, *, dtype: str = "",
                 quantized: bool = False,
                 overrides=(None, None, None)) -> Tuple[int, int, int]:
    """Resolve ``(block_q, block_h, pages_per_tile)`` for one paged
    kernel call: explicit overrides win, then a persisted
    ``pretune_paged`` result, then conservative defaults. Serving calls
    sit inside a trace where timing is impossible, and ``enabled()`` is
    False off-TPU — interpret mode must never trigger the timer (the
    guard tests/test_pallas_paged.py self-tests)."""
    kern = "paged_decode" if kind == "decode" else "paged_chunked"
    hit = None
    if enabled():
        hit = cached(kern, (seq, num_heads, head_dim, page_size,
                            pages_per_seq, dtype, bool(quantized)))
    defaults = (1 if kind == "decode" else _fit_block_q(seq),
                num_heads, 1) if hit is None else hit
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
