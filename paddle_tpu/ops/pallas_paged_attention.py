"""Fused Pallas paged-attention serving kernels (TPU).

The pure-JAX body (ops/paged_attention.py) materializes the whole
``[B, pages*page_size, H, D]`` context with ``gather_pool`` before it
attends: an HBM round trip of every slot a block table *can* address,
per generated token per layer, whatever the context is. These kernels
read K/V *through the block table* instead, for the pages a lane's
``ctx_len`` says are live, with a FlashAttention-style online softmax
(Dao et al. 2022) kept on chip — no gathered context ever exists. On a
TPU they are the path (``paged_attention.kernel_by_default``); the pure
body stays as the oracle they are tested against.

Three kernels:

- ``_decode_kernel`` — ``decode`` (S == 1, mask ``t < ctx_len[b]``;
  PagedAttention decode, Kwon et al. SOSP '23) over float pools. Grid
  ``(B,)``: one program a lane, an in-kernel loop over
  ``ceil(ctx_len / page_size)`` pages in chunks of
  ``autotune.paged_decode_chunk`` pages, copied HBM -> VMEM by the
  program's own double-buffered DMAs from a pool that stays in HBM
  (``memory_space=pl.ANY``). A dead page costs nothing, a dead lane one
  empty grid step. One query row against a page is 0.5 FLOP a byte, so
  the scores and the weighted sum run on the vector unit in f32: no
  M = 1 matmul. A page is a dense ``[page_size, kv_heads * head_dim]``
  tile of the pool (``paged_attention.kv_pool_shape``), so heads of any
  width whose row is whole 128-lane tiles take it
  (``decode_copies_pages``); a head is never sliced out of a page: the
  arithmetic keeps every head's numbers in that head's lanes
  (``_head_sums``; for a group of query heads a K/V head, one
  block-diagonal product a chunk, ``_attend_group``).
- ``_paged_kernel`` — ``chunked`` windows (shared-prefix suffix prefill
  and the spec-decode verify window, mask ``t <= positions[b, s] &
  valid[b, s]``), and decode over quantized pools, rows that are not
  whole lane tiles, or named grid blocks (float pools on the vector
  unit as above, a page a ``BlockSpec``).
  Grid ``(B, H/block_h, S/block_q, P/pages_per_tile)``:
  the innermost (arbitrary) dimension walks a sequence's logical pages,
  the scalar-prefetched block table steers each page tile's
  ``BlockSpec`` index map at the pool (a tile past the context names
  the last live page again and is neither copied nor attended), scores
  and weighted sum of a window are MXU dots per head, a head's
  columns a lane slice of the page tile. A K-tile spanning
  ``pages_per_tile`` pages is realized
  by passing the pool that many times with per-subtile index maps
  (table-adjacent pages are not pool-adjacent, so one BlockSpec cannot
  cover them).
- ``_latent_decode_kernel`` — ``decode`` over a latent pool (one row a
  token for every head of a latent attention,
  ``paged_attention.paged_latent_attention_update``): ``_decode_kernel``'s
  online softmax, a lane's absorbed queries of all heads against a chunk
  of rows in one MXU product, the values the rows' first ``value_dim``
  lanes; its page copies are issued in loops, chunks of
  ``autotune.paged_latent_chunk`` pages, and run on from each live lane
  into the next.

Serving ``prefill`` does not read the pool at all and is not routed
here.

Masking parity with the pure-JAX reference:

- trash page / stale table entries: the grid kernel's tiles past a
  row's context load whatever the table points at (often page 0, the
  trash page) and are *skipped* via ``pl.when``; the decode kernel never
  copies them. Within the last live page, slots past the context are
  masked with -1e30 exactly like the gathered path. Skipping changes
  nothing for live rows (a fully-masked tile's online-softmax
  contribution is exp(-1e30 - m) == 0) but means fully-dead rows (ctx 0
  / valid all-False) emit zeros where the reference emits a uniform
  average of garbage. Both are discarded by contract; parity tests
  compare live rows only.

Quantized pools (``(int8 values, f32 scales)`` tuples — see
ops/paged_attention.py) dequantize inside the grid kernel's tile load:
the int8 page tile and its per-(slot, head) scales are fetched through
the same block table and widened to f32 right before the QK^T dot.

The kernels compile for the chip on a TPU and run in interpret mode
on any other backend (``framework.place.on_tpu``) when a caller names
them (``use_pallas=True``), which keeps tier-1 CPU coverage of every
kernel path; tests/test_chip_compile.py compiles them for a described
v5e at gpt3_1p3b and gpt2-medium widths.
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

__all__ = ["paged_attention", "supported", "decode_copies_pages",
           "paged_latent_decode"]


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
    return values.ndim == 3 and block_tables.ndim == 2


def _head_sums(x, head_dim):
    """``x`` [T, H * head_dim], heads folded into the lanes: in every
    lane the sum over its head's ``head_dim`` lanes. Lanes are taken a
    group at a time, the narrowest run of whole lane tiles that is
    whole heads (one head of 128 or 256, two of 64), so no head is cut
    out of its tile: a group is summed over its lanes, under a mask for
    each head that shares it."""
    lanes = x.shape[1]
    if head_dim % LANES == 0:
        width = head_dim
    elif LANES % head_dim == 0 and lanes % LANES == 0:
        width = LANES
    else:
        width = lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    groups = []
    for lo in range(0, lanes, width):
        g = x[:, lo:lo + width]
        if width == head_dim:
            groups.append(jnp.broadcast_to(
                jnp.sum(g, axis=1, keepdims=True), g.shape))
            continue
        out = jnp.zeros_like(g)
        for h in range(width // head_dim):
            mine = (lane >= h * head_dim) & (lane < (h + 1) * head_dim)
            out = jnp.where(mine, jnp.sum(jnp.where(mine, g, 0.0), axis=1,
                                          keepdims=True), out)
        groups.append(out)
    return groups[0] if len(groups) == 1 else \
        jnp.concatenate(groups, axis=1)


def _attend_one_row(q, k, v, live, m, l, acc, head_dim):
    """One query row of every head against a tile of positions, folded
    into a running softmax: at 0.5 FLOP a byte the vector unit carries
    it in f32 for all heads at once, where the MXU would be fed two
    matmuls of one row a head. Everything is laid out as a pool row is,
    a head's numbers in (and repeated over) that head's lanes. q:
    [1, H*D], scaled; k/v: [T, H*D] f32; live: [T, 1] bool; m/l/acc:
    [1, H*D]. Returns the new (m, l, acc)."""
    s = jnp.where(live, _head_sums(k * q, head_dim), jnp.float32(NEG_INF))
    m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    return (m_new, alpha * l + jnp.sum(p, axis=0, keepdims=True),
            alpha * acc + jnp.sum(p * v, axis=0, keepdims=True))


def _paged_kernel(tables_ref, ctx_ref, q_ref, pos_ref, val_ref, *refs,
                  page_size, ppt, scale, kind, quantized, head_dim,
                  group=1, window=None):
    """Grid program for one (row, head-block, q-block, page-tile).

    Scalar prefetch: tables [B, P] i32 (also feeds the K/V index maps),
    ctx [B] i32. q_ref: [block_q, block_h, D]; pos/val: [block_q] i32;
    then ``ppt`` K tiles [page_size, block_h / group * D], a K/V head's
    columns a lane slice (+ ppt scale tiles [page_size, block_h / group]
    when quantized), same for V; o_ref like q_ref; scratch m/l
    [block_h, block_q, LANES] and acc [block_h, block_q, D] carry the
    online softmax across the (sequential) page-tile dim. Decode over
    float pools attends its one row for all heads at once
    (``_attend_one_row``): q_ref, o_ref and the scratch are then
    [1, block_h * D], laid out as a pool row. With ``group`` > 1 a
    K/V tile holds ``block_h / group`` heads, each serving ``group``
    consecutive query heads. With ``window`` the table is a ring: tile
    entry ``r`` holds the newest logical page congruent to it, and a
    query sees its last ``window`` positions only.
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
    d = head_dim
    # _attend_one_row
    on_vpu = kind == "decode" and not quantized and group == 1

    @pl.when(pt == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx_b = ctx_ref[b]
    if on_vpu:
        q_all = q_ref[...].astype(jnp.float32) * jnp.float32(scale)
        t_col = jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
    else:
        block_q, block_h = q_ref.shape[0], q_ref.shape[1]
        t_page = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 1)
    if kind == "chunked":
        pos = pos_ref[...]
        live = val_ref[...]

    def _vpu_page(j, start):
        m_ref[...], l_ref[...], acc_ref[...] = _attend_one_row(
            q_all, k_tiles[j][...].astype(jnp.float32),
            v_tiles[j][...].astype(jnp.float32), start + t_col < ctx_b,
            m_ref[...], l_ref[...], acc_ref[...], d)

    def _mxu_page(j, start):
        t_glob = start + t_page                              # [bq, T]
        if kind == "decode":
            mask = t_glob < ctx_b
            if window is not None:
                mask = mask & (t_glob >= ctx_b - jnp.int32(window))
        else:
            mask = (t_glob <= pos) & (live > 0)
            if window is not None:
                mask = mask & (pos - t_glob < jnp.int32(window))
        # static unroll over the head block: rank-2 dots only
        # (Mosaic's MXU path; no batched dot_general)
        for i in range(block_h):
            g = i // group
            k_t = k_tiles[j][:, g * d:(g + 1) * d]           # [T, D]
            v_t = v_tiles[j][:, g * d:(g + 1) * d]
            q_i = q_ref[:, i, :]                             # [bq, D]
            if quantized:
                k_t = k_t.astype(jnp.float32) * k_scales[j][:, g][:, None]
                v_t = v_t.astype(jnp.float32) * v_scales[j][:, g][:, None]
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

    for j in range(ppt):
        # static unroll over the sub-pages of this K-tile; each has its
        # own table-steered BlockSpec (pages are not pool-adjacent)
        if window is None:
            start = (pt * ppt + j) * page_size
            live_tile = start < ctx_b          # skip tiles past the context
        else:
            # ring entry -> the newest logical page congruent to it;
            # negative where the lane never wrote that entry
            last = (ctx_b - 1) // jnp.int32(page_size)
            entry = pt * ppt + j
            start = (last - (last - entry) % jnp.int32(
                tables_ref.shape[1])) * page_size
            live_tile = (start >= 0) & (start + page_size
                                        > ctx_b - jnp.int32(window))
        pl.when(live_tile)(functools.partial(
            _vpu_page if on_vpu else _mxu_page, j, start))

    @pl.when(pt == npt - 1)
    def _emit():
        if on_vpu:
            l = jnp.maximum(l_ref[...], jnp.float32(1e-30))
            o_ref[...] = acc_ref[...] / l
            return
        for i in range(block_h):
            l = jnp.maximum(l_ref[i, :, 0], jnp.float32(1e-30))
            o_ref[:, i, :] = acc_ref[i] / l[:, None]


def decode_copies_pages(row_lanes: int, quantized: bool) -> bool:
    """Whether decode takes the kernel that copies a lane's live pages
    itself (``_decode_kernel``): float pools whose row (``kv_heads *
    head_dim`` lanes) is whole 128-lane tiles, so that a page lands in
    its buffer as dense tiles. A narrower row can be copied by a
    ``BlockSpec`` alone, which is the grid kernel's; so is a quantized
    pool's."""
    return not quantized and row_lanes % LANES == 0


def _attend_group(q, k, v, live, m, l, acc, scale):
    """Every query row against a tile of positions where a K/V head
    serves a group of query heads, folded into a running softmax: two
    MXU dots with f32 accumulation. A query row is laid out as a pool
    row, its numbers in its K/V head's lanes and zeros in the others
    (block-diagonal), so the product over the whole row is the product
    with that head and nothing is sliced out of a page; likewise only
    its K/V head's lanes of its ``acc`` row mean anything. q: [Hq, H*D]
    and k/v: [T, H*D], both in the pool's type; live: [1, T]; m/l:
    [Hq, 1]; acc: [Hq, H*D]. Returns the new (m, l, acc)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Hq, T]
    s = jnp.where(live, s * jnp.float32(scale), jnp.float32(NEG_INF))
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
            alpha * acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))


def _decode_kernel(tables_ref, ctx_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, ksem, vsem, *, page_size, chunk, scale,
                   head_dim, window=None):
    """Grid program for one lane: every live page of its context, and
    no other, is copied HBM -> VMEM by this program's own DMAs (two
    slots: chunk c+1 is in flight while chunk c is attended), and the
    one query row meets it chunk by chunk (``_attend_one_row``; where
    a K/V head serves a group of query heads, ``_attend_group``). A
    dead lane starts no copy and emits zeros. With ``window``
    the live pages are those that hold the lane's last ``window``
    positions, and the table is a ring (logical page ``n`` in entry
    ``n % P``).

    Scalar prefetch: tables [B, P] i32, ctx [B] i32. q_ref/o_ref:
    [rows, H*D], laid out as a pool row: one row for all heads, or a
    block-diagonal row a query head where there are groups;
    k_hbm/v_hbm: the whole pools [num_pages, page_size, H*D], left in
    HBM; kbuf/vbuf: [2, chunk*page_size, H*D]; ksem/vsem: a DMA
    semaphore a slot.
    """
    b = pl.program_id(0)
    ps = page_size
    ctx = ctx_ref[b]
    width = tables_ref.shape[1]
    # divisors are explicit i32: under jax_enable_x64 a python int would
    # reach floor_divide / remainder as i64, which Mosaic cannot lower
    end_page = (ctx + (ps - 1)) // jnp.int32(ps)
    if window is None:
        first_page = None
        n_pages = jnp.minimum(end_page, width)
    else:
        first_page = jnp.maximum(ctx - jnp.int32(window), 0) // jnp.int32(ps)
        n_pages = jnp.minimum(end_page - first_page, width)
    n_chunks = (n_pages + (chunk - 1)) // jnp.int32(chunk)
    grouped = q_ref.shape[0] > 1

    @pl.when(b == 0)
    def _init():
        # slots of a part-filled chunk keep what an earlier chunk left
        # there, which is finite and weighs exp(-1e30 - m) == 0; what
        # VMEM held before the first copy need not be finite
        vbuf[...] = jnp.zeros_like(vbuf)
        if grouped:
            kbuf[...] = jnp.zeros_like(kbuf)   # it meets the MXU

    def each_live_page(c, slot, fn):
        for j in range(chunk):        # static: a chunk is a few pages
            @pl.when(c * chunk + j < n_pages)
            def _page(j=j):
                if window is None:
                    page = tables_ref[b, c * chunk + j]
                else:
                    page = tables_ref[
                        b, (first_page + c * chunk + j) % jnp.int32(width)]
                dst = pl.ds(j * ps, ps)
                fn(pltpu.make_async_copy(k_hbm.at[page],
                                         kbuf.at[slot, dst],
                                         ksem.at[slot]))
                fn(pltpu.make_async_copy(v_hbm.at[page],
                                         vbuf.at[slot, dst],
                                         vsem.at[slot]))

    @pl.when(n_chunks > 0)
    def _first():
        each_live_page(_i0(), _i0(), lambda dma: dma.start())

    if grouped:
        q = q_ref[...].astype(kbuf.dtype)
    else:
        q = q_ref[...].astype(jnp.float32) * jnp.float32(scale)

    def _attend(c, carry):
        slot = c % jnp.int32(2)

        @pl.when(c + 1 < n_chunks)
        def _next():
            each_live_page(c + 1, 1 - slot, lambda dma: dma.start())

        each_live_page(c, slot, lambda dma: dma.wait())
        t0 = c * (chunk * ps)
        if window is not None:
            t0 = t0 + first_page * ps
        # positions down the sublanes beside a row of heads, along the
        # lanes beside the MXU's [Hq, T] scores
        t = t0 + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk * ps) if grouped else (chunk * ps, 1),
            1 if grouped else 0)
        live = t < ctx
        if window is not None:
            live = live & (t >= ctx - jnp.int32(window))
        if grouped:
            return _attend_group(q, kbuf[slot], vbuf[slot], live, *carry,
                                 scale)
        return _attend_one_row(q, kbuf[slot].astype(jnp.float32),
                               vbuf[slot].astype(jnp.float32), live,
                               *carry, head_dim)

    rows, lanes = q_ref.shape
    stat = (rows, 1) if grouped else (1, lanes)
    _, l, acc = jax.lax.fori_loop(
        0, n_chunks, _attend,
        (jnp.full(stat, NEG_INF, jnp.float32),
         jnp.zeros(stat, jnp.float32),
         jnp.zeros((rows, lanes), jnp.float32)))
    o_ref[...] = acc / jnp.maximum(l, jnp.float32(1e-30))


def _fold_queries(q, kv_heads):
    """q [B, Hq, D] laid out as pool rows. Hq == kv_heads: one row of
    all heads, [B, 1, Hq*D]. A group of query heads a K/V head: a row a
    query head, its numbers in its K/V head's lanes and zeros in the
    others (block-diagonal), [B, Hq, kv_heads*D]."""
    b, hq, d = q.shape
    if hq == kv_heads:
        return q.reshape(b, 1, hq * d)
    own = jnp.eye(kv_heads, dtype=q.dtype)[:, None, :, None]
    return (q.reshape(b, kv_heads, hq // kv_heads, 1, d) * own).reshape(
        b, hq, kv_heads * d)


def _unfold_outputs(out, hq, kv_heads):
    """The inverse for what the kernel emits: [B, 1, Hq*D] or, with
    groups, [B, Hq, kv_heads*D] of which a row's own K/V head's lanes
    are its output. Returns [B, Hq, D]."""
    b, lanes = out.shape[0], out.shape[2]
    if hq == kv_heads:
        return out.reshape(b, hq, lanes // hq)
    d = lanes // kv_heads
    return jnp.einsum(
        "bhgkd,hk->bhgd",
        out.reshape(b, kv_heads, hq // kv_heads, kv_heads, d),
        jnp.eye(kv_heads, dtype=out.dtype)).reshape(b, hq, d)


def _paged_decode(q, k_pool, v_pool, tables, ctx, *, page_size, scale,
                  pages_per_chunk, window=None):
    """The page-copying decode kernel: q [B, Hq, D], pools [num_pages,
    page_size, H*D], Hq a multiple of H. Returns [B, Hq, D] f32."""
    from . import autotune

    b, hq, d = q.shape
    lanes = k_pool.shape[2]
    chunk = autotune.paged_decode_chunk(
        page_size, lanes, k_pool.dtype.itemsize, tables.shape[1],
        on_mxu=hq * d != lanes, override=pages_per_chunk)
    q = _fold_queries(q, lanes // d)
    row_spec = pl.BlockSpec((None,) + q.shape[1:],
                            lambda bi, ts, cs: (bi, _i0(), _i0()))
    buf = pltpu.VMEM((2, chunk * page_size, lanes), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b,),
        in_specs=[row_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_spec,
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))])
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, chunk=chunk,
        scale=float(scale), head_dim=d,
        **({} if window is None else {"window": int(window)}))

    def _run(tables, ctx, q, k_pool, v_pool):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
            # sequential: the buffers are zeroed by the first program
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=not _place.on_tpu(),
        )(tables, ctx, q, k_pool, v_pool)

    return _unfold_outputs(
        _inference_only(_run)(tables, ctx, q, k_pool, v_pool), hq,
        lanes // d)


def _latent_decode_kernel(tables_ref, ctx_ref, q_ref, pool_hbm, o_ref, buf,
                          sem, slot_ref, *, page_size, chunk, group, step,
                          scale, value_dim):
    """Grid program for one lane of a latent pool (one row a token for
    every head: ``paged_attention.paged_latent_attention_update``):
    every live page of its context, and no other, is copied HBM -> VMEM
    by DMAs issued in loops, a chunk at a time into two slots (chunk
    c+1 is in flight while chunk c is attended), and all the lane's
    heads' absorbed queries meet a chunk in one MXU product folded into
    a running softmax (``_attend_group``), the values being the rows'
    first ``value_dim`` lanes. A chunk's product takes its live pages
    rounded up to ``step``. A dead lane starts no copy and emits zeros.

    The copies run on across lanes: the programs run in order, and the
    one that attends a lane's last chunk starts the first chunk of the
    next live lane into the other slot, which ``slot_ref`` hands on; the
    first program starts the first live lane's. So the DMA engine is not
    idle over a lane's last product, its output and the next program's
    start.

    Scalar prefetch: tables [B, P] i32, ctx [B] i32. q_ref: [H, L] in
    the pool's type, zero past the row's values; pool_hbm: [num_pages,
    page_size, L], left in HBM; o_ref: [H, value_dim] f32; buf: [2,
    chunk*page_size, L]; sem: a DMA semaphore a slot; slot_ref: [1] i32
    in SMEM, the slot that holds the lane's first chunk."""
    b = pl.program_id(0)
    lanes = ctx_ref.shape[0]
    ps = page_size

    def pages(lane):
        return jnp.minimum((ctx_ref[lane] + (ps - 1)) // jnp.int32(ps),
                           tables_ref.shape[1])

    def next_live(lane):
        """The first lane after ``lane`` with a context, else ``lanes``."""
        return jax.lax.while_loop(
            lambda n: (n < lanes)
            & (ctx_ref[jnp.minimum(n, lanes - 1)] == 0),
            lambda n: n + 1, lane + 1)

    def start(lane, c, slot, n_pages):
        """Start the copies of the live pages of ``lane``'s chunk ``c``:
        whole groups of ``group``, each unrolled, then one at a time."""
        first = c * chunk
        live = jnp.minimum(n_pages - first, chunk)

        def copy(j):
            pltpu.make_async_copy(
                pool_hbm.at[tables_ref[lane, first + j]],
                buf.at[slot, pl.ds(pl.multiple_of(j * ps, ps), ps)],
                sem.at[slot]).start()

        def _group(i, carry):
            for k in range(group):
                copy(i * group + k)
            return carry

        def _page(j, carry):
            copy(j)
            return carry

        whole = live // jnp.int32(group)
        jax.lax.fori_loop(0, whole, _group, 0)
        jax.lax.fori_loop(whole * group, live, _page, 0)

    def wait(slot, live):
        """Wait for ``live`` pages' copies into ``slot``: a wait takes the
        slot's semaphore down by its buffer's bytes, so one wait a set
        bit of the count, of that many pages, covers them all."""
        k = 1
        while k <= chunk:
            @pl.when((live & k) != 0)
            def _wait(k=k):
                rows = buf.at[slot, pl.ds(0, k * ps)]
                pltpu.make_async_copy(rows, rows, sem.at[slot]).wait()
            k *= 2

    @pl.when(b == 0)
    def _init():
        # a product's stale rows weigh exp(-1e30 - m) == 0 but meet the
        # MXU: what VMEM held before the first copy must be finite
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = jnp.int32(0)
        first = next_live(jnp.int32(-1))

        @pl.when(first < lanes)
        def _first():
            start(first, _i0(), _i0(), pages(first))

    ctx = ctx_ref[b]
    n_pages = pages(b)
    n_chunks = (n_pages + (chunk - 1)) // jnp.int32(chunk)
    slot0 = slot_ref[0]
    q = q_ref[...]

    def _attend(c, carry):
        slot = (slot0 + c) % jnp.int32(2)

        @pl.when(c + 1 < n_chunks)
        def _next():
            start(b, c + 1, 1 - slot, n_pages)

        @pl.when(c + 1 == n_chunks)
        def _next_lane():
            slot_ref[0] = 1 - slot
            n = next_live(b)

            @pl.when(n < lanes)
            def _start():
                start(n, _i0(), 1 - slot, pages(n))

        live = jnp.minimum(n_pages - c * chunk, chunk)
        wait(slot, live)

        def product(size):
            def _product(carry):
                rows = buf[slot, pl.ds(0, size * ps)]
                t = c * (chunk * ps) + jax.lax.broadcasted_iota(
                    jnp.int32, (1, size * ps), 1)
                return _attend_group(q, rows, rows[:, :value_dim], t < ctx,
                                     *carry, scale)
            return _product

        return jax.lax.switch((live - 1) // jnp.int32(step),
                              [product(size) for size in
                               range(step, chunk + 1, step)], carry)

    heads = q_ref.shape[0]
    _, l, acc = jax.lax.fori_loop(
        0, n_chunks, _attend,
        (jnp.full((heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, value_dim), jnp.float32)))
    o_ref[...] = acc / jnp.maximum(l, jnp.float32(1e-30))


def paged_latent_decode(q, pool, tables, ctx, *, page_size, scale,
                        value_dim, pages_per_chunk=None):
    """The page-copying decode kernel over a latent pool: q [B, H, W]
    (absorbed queries beside their rotary parts), pool [num_pages,
    page_size, L] (``paged_attention.latent_pool_shape``: a row of W
    values in whole lane tiles, L >= W). Returns [B, H, value_dim] f32:
    each head's softmax-weighted sum of its context's latents."""
    from . import autotune

    b, h, w = q.shape
    lanes = pool.shape[2]
    chunk, step = autotune.paged_latent_chunk(
        page_size, lanes, pool.dtype.itemsize, tables.shape[1],
        override=pages_per_chunk)
    # the row's lanes past its values hold zeros, and so do the queries'
    q = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, 0), (0, lanes - w)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b,),
        in_specs=[pl.BlockSpec((None, h, lanes),
                               lambda bi, ts, cs: (bi, _i0(), _i0())),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, h, value_dim),
                               lambda bi, ts, cs: (bi, _i0(), _i0())),
        scratch_shapes=[pltpu.VMEM((2, chunk * page_size, lanes),
                                   pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    kernel = functools.partial(
        _latent_decode_kernel, page_size=page_size, chunk=chunk,
        group=autotune.PAGED_LATENT_COPY_GROUP, step=step,
        scale=float(scale), value_dim=int(value_dim))

    def _run(tables, ctx, q, pool):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, value_dim), jnp.float32),
            # sequential: a program finds its first chunk started by the
            # one before it, in the slot that one left in SMEM
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=not _place.on_tpu(),
        )(tables, ctx, q, pool)

    return _inference_only(_run)(tables.astype(jnp.int32),
                                 ctx.astype(jnp.int32), q, pool)


def paged_attention(q, k_pool, v_pool, block_tables, ctx_len, valid,
                    positions, *, page_size: int, kind: str, scale: float,
                    block_q=None, block_h=None, pages_per_tile=None,
                    pages_per_chunk=None, window=None):
    """Fused read-through-table paged attention (decode/chunked).

    q: [B, S, Hq, D]; pools: [num_pages, page_size, H*D] (or quantized
    tuples), Hq a multiple of H (a K/V head serves a group of
    consecutive query heads); ``window`` as in
    ``paged_attention_update`` (the table is then a ring); block_tables: [B, P] i32 (entries must be valid pool page
    ids — the engine guarantees this; the trash page is maskable but an
    id >= num_pages is not); ctx_len: [B]; valid: [B, S] bool;
    positions: [B, S] i32. The caller has already written this step's
    K/V into the pools (write-then-read, same as the reference).
    Returns [B, S, H, D] in q.dtype.

    Decode takes the page-copying kernel where ``decode_copies_pages``
    holds; chunked windows, the other decode shapes and a call that
    names grid blocks take the grid kernel.
    """
    from . import autotune

    b, s, h, d = q.shape
    p = block_tables.shape[1]
    tables = block_tables.astype(jnp.int32)
    ctx = ctx_len.astype(jnp.int32)
    quantized = is_quantized_pool(k_pool)
    if quantized:
        k_vals, k_sc = k_pool
        v_vals, v_sc = v_pool
    else:
        k_vals, v_vals = k_pool, v_pool
    group = h * d // k_vals.shape[2]
    grid_blocks = (block_q, block_h, pages_per_tile)
    if kind == "decode" and decode_copies_pages(k_vals.shape[2], quantized) \
            and grid_blocks == (None,) * 3:
        out = _paged_decode(
            q[:, 0], k_pool, v_pool, tables, ctx, page_size=page_size,
            scale=scale, pages_per_chunk=pages_per_chunk, window=window)
        return out[:, None].astype(q.dtype)

    bq, bh, ppt = autotune.paged_blocks(
        kind, s, h, d, page_size, p, overrides=grid_blocks)
    if bh % group:
        raise ValueError(f"a head block of {bh} splits a group of "
                         f"{group} query heads")
    on_vpu = kind == "decode" and not quantized and group == 1
    # [B, S, 1]: a (block_q, 1) column is a legal tile (the last dim is
    # the whole array's) and broadcasts against the [block_q, T] scores
    pos = positions.astype(jnp.int32)[..., None]
    val = valid.astype(jnp.int32)[..., None]

    # index maps (scalar-prefetch refs ride after the grid indices)
    def q_map(bi, hb, qb, pt, ts, cs):
        return (bi, qb, hb, _i0())

    def row_map(bi, hb, qb, pt, ts, cs):
        return (bi, qb, _i0())

    def page_of(bi, pt, j, ts, cs):
        if window is not None:
            return ts[bi, pt * ppt + j]      # a ring: every entry
        # a tile past the row's context names the row's last live page
        # again: the kernel skips it, and a block whose index did not
        # change is not copied, so a dead tile moves no bytes
        last = jnp.maximum(
            (cs[bi] + (page_size - 1)) // jnp.int32(page_size) - 1, 0)
        return ts[bi, jnp.minimum(pt * ppt + j, last)]

    def kv_map(j):
        # pool values and scale planes alike: a page, whole, and the
        # head block's share of its lanes
        def _map(bi, hb, qb, pt, ts, cs):
            return (page_of(bi, pt, j, ts, cs), _i0(), hb)
        return _map

    if on_vpu:
        # the one row of all the block's heads, laid out as a pool row
        q = q.reshape(b, s, h * d)
        q_spec = pl.BlockSpec((None, 1, bh * d),
                              lambda bi, hb, qb, pt, ts, cs: (bi, qb, hb))
    else:
        q_spec = pl.BlockSpec((None, bq, bh, d), q_map)
    row_spec = pl.BlockSpec((None, bq, 1), row_map)
    tile_spec = lambda j: pl.BlockSpec(  # noqa: E731
        (None, page_size, bh // group * d), kv_map(j))
    scale_spec = lambda j: pl.BlockSpec(  # noqa: E731
        (None, page_size, bh // group), kv_map(j))

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
        scale=float(scale), kind=kind, quantized=quantized, head_dim=d,
        **({} if group == 1 else {"group": group}),
        **({} if window is None else {"window": int(window)}))
    if on_vpu:
        scratch = [pltpu.VMEM((1, bh * d), jnp.float32)] * 3
    else:
        scratch = [pltpu.VMEM((bh, bq, LANES), jnp.float32)] * 2 \
            + [pltpu.VMEM((bh, bq, d), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // bh, s // bq, p // ppt),
        in_specs=in_specs, out_specs=q_spec, scratch_shapes=scratch)

    def _run(tables, ctx, *inputs):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            # f32 out, cast by the caller below: Mosaic has no layout
            # for a 16-bit [block_q, D] row store when D is not a
            # whole 128-lane tile
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary"),
            ),
            interpret=not _place.on_tpu(),
        )(tables, ctx, *inputs)

    return _inference_only(_run)(tables, ctx, *inputs).reshape(
        b, s, h, d).astype(q.dtype)


def _inference_only(run):
    """pallas_call has no JVP rule, but eager dispatch records ops under
    jax.vjp whenever autograd is live — give the kernel an explicit
    inference-only vjp so the forward trace succeeds and only an actual
    backward() through it fails."""
    call = jax.custom_vjp(run)
    call.defvjp(lambda *a: (run(*a), None), _nondiff_bwd)
    return call


def _nondiff_bwd(_res, _g):
    raise NotImplementedError(
        "fused paged attention kernels are inference-only (serving "
        "path); train with the pure-JAX reference attention instead")
