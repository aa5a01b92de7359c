"""Fused Pallas paged-attention serving kernels (TPU).

ROADMAP item 2: the decode hot path used to materialize the whole
``[B, pages*page_size, H, D]`` context with ``gather_pool`` before
attending (ops/paged_attention.py) — an HBM round-trip per generated
token per layer. These kernels read K/V *through the block table inside
the kernel* instead: the grid's innermost (arbitrary) dimension walks a
sequence's logical pages, a ``PrefetchScalarGridSpec`` scalar-prefetch
block table steers each page tile's ``BlockSpec`` index map at the pool
directly, and a FlashAttention-style online softmax (running max /
denominator in VMEM scratch, Dao et al. 2022) accumulates across page
tiles — no gathered context ever exists.

One kernel body serves both serving kinds that read through the table:

- ``decode``: S == 1, mask ``t < ctx_len[b]`` (PagedAttention decode,
  Kwon et al. SOSP '23);
- ``chunked``: arbitrary S window (shared-prefix suffix prefill and the
  spec-decode verify window) with the per-(row, position) causality
  mask ``t <= positions[b, s] & valid[b, s]``.

Serving ``prefill`` does not read the pool at all — it routes through
the existing ``pallas_attention.mha`` flash kernel (``prefill_flash``).

Grid: ``(B, H/block_h, S/block_q, P/pages_per_tile)`` — one program
per (row, head-block, q-block) accumulating over page tiles. The block
sizes come from ``ops/autotune.py``'s paged tables; a K-tile spanning
``pages_per_tile`` pages is realized by passing the pool that many
times with per-subtile index maps (table-adjacent pages are not
pool-adjacent, so one BlockSpec cannot cover them).

Masking parity with the pure-JAX reference (kept in
ops/paged_attention.py as the oracle the kernels are tested against):

- trash page / stale table entries: tiles past a row's context load
  whatever the table points at (often page 0, the trash page) and are
  masked with -1e30 exactly like the gathered path — except the kernel
  also *skips* tiles with ``page*page_size >= ctx_len[b]`` via
  ``pl.when``, which changes nothing for live rows (a fully-masked
  tile's online-softmax contribution is exp(-1e30 - m) == 0) but means
  fully-dead rows (ctx 0 / valid all-False) emit zeros where the
  reference emits a uniform average of garbage. Both are discarded by
  contract; parity tests compare live rows only.

Quantized pools (``(int8 values, f32 scales)`` tuples — see
ops/paged_attention.py) dequantize inside the tile load: the int8 page
tile and its per-(slot, head) scales are fetched through the same block
table and widened to f32 right before the QK^T dot.

The kernels compile for the chip on a TPU and run in interpret mode
on any other backend (``framework.place.on_tpu``), which keeps tier-1
CPU coverage of every kernel path; tests/test_chip_compile.py compiles
them for a described v5e at gpt3_1p3b widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import place as _place
from .pallas_attention import LANES, NEG_INF, _i0
from .paged_attention import is_quantized_pool

__all__ = ["paged_attention", "prefill_flash", "supported",
           "pretune_paged"]


def supported(q, k_pool, block_tables, page_size: int, kind: str) -> bool:
    """Can the fused kernel serve this call? Only structure decides:
    the kind, the ranks, and pages of at least two slots (Mosaic
    refuses the one-slot tile). Tile legality is not a property of the
    shapes but of the blocks, and ``autotune.paged_blocks`` only picks
    legal ones: all heads per block, the window whole or in 8-multiples.
    So any (heads, head_dim, page_size >= 2) compiles for the chip —
    tests/test_chip_compile.py holds the gpt3_1p3b widths to that for
    f32/bf16/int8 pools — short of a tile that outgrows VMEM, which the
    compiler refuses by name. A caller that asked for the kernel and
    gets False here raises; nothing answers in the kernel's place."""
    if kind not in ("decode", "chunked"):
        return False
    if q.ndim != 4 or page_size < 2:
        return False
    values = k_pool[0] if is_quantized_pool(k_pool) else k_pool
    return values.ndim == 4 and block_tables.ndim == 2


def _paged_kernel(tables_ref, ctx_ref, q_ref, pos_ref, val_ref, *refs,
                  page_size, ppt, scale, kind, quantized):
    """Grid program for one (row, head-block, q-block, page-tile).

    Scalar prefetch: tables [B, P] i32 (also feeds the K/V index maps),
    ctx [B] i32. q_ref: [block_q, block_h, D]; pos/val: [block_q] i32;
    then ``ppt`` K tiles [page_size, block_h, D] (+ ppt scale tiles
    [page_size, block_h] when quantized), same for V; o_ref like q_ref;
    scratch m/l [block_h, block_q, LANES] and acc [block_h, block_q, D]
    carry the online softmax across the (sequential) page-tile dim.
    """
    o_ref, m_ref, l_ref, acc_ref = refs[-4], refs[-3], refs[-2], refs[-1]
    kv = refs[:-4]
    if quantized:
        k_tiles, k_scales = kv[0:ppt], kv[ppt:2 * ppt]
        v_tiles, v_scales = kv[2 * ppt:3 * ppt], kv[3 * ppt:4 * ppt]
    else:
        k_tiles, v_tiles = kv[0:ppt], kv[ppt:2 * ppt]
        k_scales = v_scales = (None,) * ppt

    b = pl.program_id(0)
    pt = pl.program_id(3)
    npt = pl.num_programs(3)
    block_q, block_h, d = q_ref.shape

    @pl.when(pt == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx_b = ctx_ref[b]
    t_page = jax.lax.broadcasted_iota(jnp.int32, (block_q, page_size), 1)
    if kind == "chunked":
        pos = pos_ref[...]
        live = val_ref[...]

    for j in range(ppt):
        # static unroll over the sub-pages of this K-tile; each has its
        # own table-steered BlockSpec (pages are not pool-adjacent)
        start = (pt * ppt + j) * page_size

        def _tile(j=j, start=start):
            @pl.when(start < ctx_b)   # skip tiles past the context
            def _update():
                t_glob = start + t_page                  # [bq, T]
                if kind == "decode":
                    mask = t_glob < ctx_b
                else:
                    mask = (t_glob <= pos) & (live > 0)
                # static unroll over the head block: rank-2 dots only
                # (Mosaic's MXU path; no batched dot_general)
                for i in range(block_h):
                    k_t = k_tiles[j][:, i, :]            # [T, D]
                    v_t = v_tiles[j][:, i, :]
                    q_i = q_ref[:, i, :]                 # [bq, D]
                    if quantized:
                        k_t = k_t.astype(jnp.float32) \
                            * k_scales[j][:, i][:, None]
                        v_t = v_t.astype(jnp.float32) \
                            * v_scales[j][:, i][:, None]
                        q_i = q_i.astype(jnp.float32)
                    s = jax.lax.dot_general(
                        q_i, k_t, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    s = s * jnp.float32(scale)
                    s = jnp.where(mask, s, jnp.float32(NEG_INF))
                    m_prev = m_ref[i, :, 0]
                    l_prev = l_ref[i, :, 0]
                    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
                    p = jnp.exp(s - m_new[:, None])
                    alpha = jnp.exp(m_prev - m_new)
                    l_new = alpha * l_prev + jnp.sum(p, axis=1)
                    acc_ref[i] = acc_ref[i] * alpha[:, None] + \
                        jax.lax.dot_general(
                            p.astype(v_t.dtype), v_t,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                    m_ref[i] = jax.lax.broadcast_in_dim(
                        m_new, (block_q, LANES), (0,))
                    l_ref[i] = jax.lax.broadcast_in_dim(
                        l_new, (block_q, LANES), (0,))
        _tile()

    @pl.when(pt == npt - 1)
    def _emit():
        for i in range(block_h):
            l = jnp.maximum(l_ref[i, :, 0], jnp.float32(1e-30))
            o_ref[:, i, :] = acc_ref[i] / l[:, None]


def paged_attention(q, k_pool, v_pool, block_tables, ctx_len, valid,
                    positions, *, page_size: int, kind: str, scale: float,
                    block_q=None, block_h=None, pages_per_tile=None):
    """Fused read-through-table paged attention (decode/chunked).

    q: [B, S, H, D]; pools: [num_pages, page_size, H, D] (or quantized
    tuples); block_tables: [B, P] i32 (entries must be valid pool page
    ids — the engine guarantees this; the trash page is maskable but an
    id >= num_pages is not); ctx_len: [B]; valid: [B, S] bool;
    positions: [B, S] i32. The caller has already written this step's
    K/V into the pools (write-then-read, same as the reference).
    Returns [B, S, H, D] in q.dtype.
    """
    from . import autotune

    b, s, h, d = q.shape
    p = block_tables.shape[1]
    quantized = is_quantized_pool(k_pool)
    bq, bh, ppt = autotune.paged_blocks(
        kind, s, h, d, page_size, p, dtype=str(q.dtype),
        quantized=quantized,
        overrides=(block_q, block_h, pages_per_tile))

    tables = block_tables.astype(jnp.int32)
    ctx = ctx_len.astype(jnp.int32)
    # [B, S, 1]: a (block_q, 1) column is a legal tile (the last dim is
    # the whole array's) and broadcasts against the [block_q, T] scores
    pos = positions.astype(jnp.int32)[..., None]
    val = valid.astype(jnp.int32)[..., None]

    if quantized:
        k_vals, k_sc = k_pool
        v_vals, v_sc = v_pool
    else:
        k_vals, v_vals = k_pool, v_pool

    # index maps (scalar-prefetch refs ride after the grid indices)
    def q_map(bi, hb, qb, pt, ts, cs):
        return (bi, qb, hb, _i0())

    def row_map(bi, hb, qb, pt, ts, cs):
        return (bi, qb, _i0())

    def kv_map(j):
        def _map(bi, hb, qb, pt, ts, cs):
            return (ts[bi, pt * ppt + j], _i0(), hb, _i0())
        return _map

    def sc_map(j):
        def _map(bi, hb, qb, pt, ts, cs):
            return (ts[bi, pt * ppt + j], _i0(), hb)
        return _map

    q_spec = pl.BlockSpec((None, bq, bh, d), q_map)
    row_spec = pl.BlockSpec((None, bq, 1), row_map)
    tile_spec = lambda j: pl.BlockSpec((None, page_size, bh, d), kv_map(j))  # noqa: E731
    scale_spec = lambda j: pl.BlockSpec((None, page_size, bh), sc_map(j))  # noqa: E731

    in_specs = [q_spec, row_spec, row_spec]
    inputs = [q, pos, val]
    in_specs += [tile_spec(j) for j in range(ppt)]
    inputs += [k_vals] * ppt
    if quantized:
        in_specs += [scale_spec(j) for j in range(ppt)]
        inputs += [k_sc] * ppt
    in_specs += [tile_spec(j) for j in range(ppt)]
    inputs += [v_vals] * ppt
    if quantized:
        in_specs += [scale_spec(j) for j in range(ppt)]
        inputs += [v_sc] * ppt

    kernel = functools.partial(
        _paged_kernel, page_size=page_size, ppt=ppt,
        scale=float(scale), kind=kind, quantized=quantized)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // bh, s // bq, p // ppt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bq, bh, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((bh, bq, LANES), jnp.float32),
            pltpu.VMEM((bh, bq, LANES), jnp.float32),
            pltpu.VMEM((bh, bq, d), jnp.float32),
        ])

    def _run(tables, ctx, *inputs):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            # f32 out, cast by the caller below: Mosaic has no layout
            # for a 16-bit [block_q, D] row store when D is not a
            # whole 128-lane tile
            out_shape=jax.ShapeDtypeStruct((b, s, h, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary"),
            ),
            interpret=not _place.on_tpu(),
        )(tables, ctx, *inputs)

    # pallas_call has no JVP rule, but eager dispatch records ops under
    # jax.vjp whenever autograd is live — give the kernel an explicit
    # inference-only vjp so the forward trace succeeds and only an
    # actual backward() through it fails.
    call = jax.custom_vjp(_run)
    call.defvjp(lambda *a: (_run(*a), None), _nondiff_bwd)
    return call(tables, ctx, *inputs).astype(q.dtype)


def _nondiff_bwd(_res, _g):
    raise NotImplementedError(
        "fused paged attention kernels are inference-only (serving "
        "path); train with the pure-JAX reference attention instead")


def prefill_flash(q, k, v, scale, use_flash: bool = True):
    """Serving-prefill routing onto the ``pallas_attention.mha`` flash
    kernel. Prefill never reads the pool (its K/V are right in the
    window), so the fused paged kernels add nothing — but the default
    ``attention_bshd`` gate only *prefers* flash above
    FLAGS_flash_min_seqlen, a training-tuned crossover that serving
    windows rarely reach. With FLAGS_decode_pallas_attention the
    operator asked for kernels, so route any mha-shaped window straight
    to the kernel: on TPU when ``flash_attention.supported`` holds, and
    in interpret mode (CPU tier-1) whenever blocks fit, falling back to
    the dense reference otherwise."""
    from .flash_attention import (attention_bshd, flash_attention_bshd,
                                  supported as flash_ok)
    sq, sk = q.shape[1], k.shape[1]
    if not _place.on_tpu():
        # causal mha masks top-left aligned windows only, and its
        # blocks must be 128-lane multiples — sub-128 bucketed windows
        # take the dense reference instead
        if sq == sk and sq % 128 == 0:
            from .pallas_attention import mha
            qt = jnp.swapaxes(q, 1, 2)
            kt = jnp.swapaxes(k, 1, 2)
            vt = jnp.swapaxes(v, 1, 2)
            out = mha(qt, kt, vt, causal=True, sm_scale=scale,
                      block_q=128, block_k=128)
            return jnp.swapaxes(out, 1, 2)
    elif flash_ok(q, k, v, None, True):
        return flash_attention_bshd(q, k, v, causal=True, scale=scale)
    return attention_bshd(q, k, v, causal=True, scale=scale,
                          use_flash=use_flash)


def pretune_paged(kind, batch, seq, num_heads, head_dim, page_size,
                  pages_per_seq, dtype="float32", quantized=False):
    """Eagerly time the paged block-size candidates on the real device
    and persist the winner where traced serving calls will find it
    (mirror of flash_attention.pretune). No-op off-TPU / with autotune
    disabled — interpret mode must never time kernels (the 'interpret
    skips autotune' guard, self-tested in tests/test_pallas_paged.py).
    """
    from . import autotune
    from .paged_attention import quantize_kv_rows

    if not autotune.enabled():
        return None
    cands = autotune.paged_block_candidates(
        kind, seq, num_heads, head_dim, page_size, pages_per_seq,
        quantized=quantized)
    if len(cands) <= 1:
        return cands[0] if cands else None
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    num_pages = 1 + batch * pages_per_seq
    q = jax.random.normal(
        keys[0], (batch, seq, num_heads, head_dim), jnp.float32
    ).astype(dtype)
    pool_shape = (num_pages, page_size, num_heads, head_dim)
    kp = jax.random.normal(keys[1], pool_shape, jnp.float32).astype(dtype)
    vp = jax.random.normal(keys[2], pool_shape, jnp.float32).astype(dtype)
    if quantized:
        kq, ks = quantize_kv_rows(kp.reshape(-1, num_heads, head_dim))
        vq, vs = quantize_kv_rows(vp.reshape(-1, num_heads, head_dim))
        kp = (kq.reshape(pool_shape), ks.reshape(pool_shape[:2] + (num_heads,)))
        vp = (vq.reshape(pool_shape), vs.reshape(pool_shape[:2] + (num_heads,)))
    tables = (1 + jnp.arange(batch * pages_per_seq, dtype=jnp.int32)
              ).reshape(batch, pages_per_seq)
    ctx = jnp.full((batch,), pages_per_seq * page_size, jnp.int32)
    pos = jnp.broadcast_to(
        jnp.arange(seq, dtype=jnp.int32), (batch, seq)) + (
        pages_per_seq * page_size - seq)
    val = jnp.ones((batch, seq), jnp.int32)
    sm = 1.0 / (head_dim ** 0.5)

    def make_fn(c):
        bq, bh, ppt = c
        return jax.jit(functools.partial(
            paged_attention, page_size=page_size, kind=kind, scale=sm,
            block_q=bq, block_h=bh, pages_per_tile=ppt))

    kern = "paged_decode" if kind == "decode" else "paged_chunked"
    return autotune.pick(
        kern,
        (seq, num_heads, head_dim, page_size, pages_per_seq,
         str(jnp.dtype(dtype)), bool(quantized)),
        cands, make_fn, (q, kp, vp, tables, ctx, val, pos))
