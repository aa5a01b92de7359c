"""Paged KV-cache attention (PagedAttention, Kwon et al. SOSP '23).

Decode serving keeps each sequence's K/V in fixed-size *pages* of a
preallocated per-layer pool rather than a contiguous
``[batch, max_seq_len, ...]`` slab, so cache memory scales with live
tokens and a sequence's pages can be scattered anywhere in the pool.
A per-sequence int32 *block table* maps logical position ``p`` to pool
page ``table[p // page_size]`` at offset ``p % page_size``.

Pool layout is ``[num_pages, page_size, kv_heads * head_dim]``: the heads
are folded into the lane axis, head ``h`` in lanes ``h * head_dim ..
(h + 1) * head_dim - 1`` (``kv_pool_shape``; this module alone knows
it). A row is whole 128-lane tiles for every configuration served, so
the TPU's compiler keeps the pool row-major and no program relayouts it
at its boundary; with the heads an axis of their own it made the *page*
axis minor for 64-wide heads and every scatter and every kernel paid a
transposing copy of the whole pool (PERF.md section 6, PR 30).
**Page 0 is the trash page**: the allocator never hands it out, and
every masked write (padding positions, dead batch lanes) is redirected
to a slot inside it, so scatter shapes stay fixed — the XLA-friendly
substitute for dynamic-length writes. Trash-page contents are garbage
and must never be gathered for a live position (the block tables of
live sequences only reference allocated pages).

A latent attention (DeepSeek-V2's multi-head latent attention) keeps
one row a token for all its heads, the normed latent beside the rotary
key part they share, and no V: ``latent_pool_shape`` lays it out,
``paged_latent_attention_update`` writes it and attends over it.

These are pure jax functions; the model layer threads them through
``apply_op`` (models/gpt.py) and the decode engine jits them via
``serving.generation.model_fns``.

Quantized pools (``FLAGS_decode_kv_dtype=int8``): a pool is then the
2-tuple ``(values int8 [num_pages, page_size, H * D], scales f32
[num_pages, page_size, H])`` — symmetric absmax quantization over
head_dim, one scale per written (slot, head). Scales are per-slot
rather than per-whole-page because pages fill incrementally (one token
per decode step): a page-granular absmax would have to requantize the
page's older int8 entries on every append, compounding rounding error
up to page_size times, while per-slot scales quantize each value
exactly once. The ~4x byte saving still holds within the scale
overhead: 1 + 4/head_dim bytes per element vs 4 (3.76x at D=64).
Quantize happens on write (``write_pool``), dequantize on read — in
``gather_pool`` for the pure-JAX path and inside the Pallas tile loads
(ops/pallas_paged_attention.py) for the fused path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["flat_slots", "write_pool", "gather_pool",
           "paged_attention_update", "kernel_by_default", "is_quantized_pool",
           "quantize_kv_rows", "dequantize_kv", "kv_pool_bytes",
           "resolve_kv_dtype", "kv_pool_shape", "kv_pool_heads_spec",
           "new_kv_pool", "latent_pool_shape",
           "paged_latent_attention_update"]

KINDS = ("prefill", "decode", "chunked")
KV_DTYPES = ("", "float32", "bfloat16", "int8")


# ------------------------------------------------------- the pool layout

def kv_pool_shape(num_pages, page_size, kv_heads, head_dim=None):
    """Shape of one resident K or V pool: ``[num_pages, page_size,
    kv_heads * head_dim]``, head ``h`` in lanes ``h * head_dim .. (h +
    1) * head_dim - 1``. Without ``head_dim``: a quantized pool's scale
    plane, ``[num_pages, page_size, kv_heads]``."""
    return (int(num_pages), int(page_size),
            int(kv_heads) * int(1 if head_dim is None else head_dim))


def kv_pool_heads_spec(ndim: int, axis: str = "mp") -> tuple:
    """Partition spec of a pool leaf of ``ndim`` dimensions (leading
    layer axis or not, values or scale plane) with its heads split over
    ``axis``: heads are the major factor of the last dimension of both,
    so equal contiguous blocks of it are whole heads (``kv_heads %
    mp == 0``)."""
    return (None,) * (ndim - 1) + (axis,)


def latent_pool_shape(num_pages, page_size, width):
    """Shape of one latent attention's pool (``paged_latent_attention_
    update``): a token's row of ``width`` values for every head, in
    whole 128-lane tiles (576 values in 640 lanes, the last 64 zeros).
    That costs no byte beside a row of 576 lanes: the chip's tiled
    layout keeps such a row in 640 lanes too, or else makes the page
    axis minor, which puts two copies of the whole pool round every
    program that reads it (compiled for a v5e: PERF.md section 4); and
    the decode kernel copies a page with its own DMA only where the row
    is whole tiles."""
    return (int(num_pages), int(page_size),
            -(-int(width) // 128) * 128)


def new_kv_pool(num_pages, page_size, kv_heads, head_dim, dtype, lead=()):
    """One zeroed pool (K or V) in the resident layout, under ``lead``
    leading axes (a stacked decoder's layers). ``dtype`` "int8" gives
    the quantized ``(values, scales)`` pair."""
    shape = tuple(lead) + kv_pool_shape(num_pages, page_size, kv_heads,
                                        head_dim)
    if isinstance(dtype, str) and dtype == "int8":
        return (jnp.zeros(shape, jnp.int8),
                jnp.zeros(tuple(lead) + kv_pool_shape(
                    num_pages, page_size, kv_heads), jnp.float32))
    return jnp.zeros(shape, dtype)


# ------------------------------------------------------- quantized pools

def resolve_kv_dtype(name):
    """Map a FLAGS_decode_kv_dtype value to an ``init_kv_pools`` dtype:
    '' → None (model dtype), 'int8' → the string marker (tuple pools),
    else the jnp dtype."""
    name = (name or "").strip()
    if name not in KV_DTYPES:
        raise ValueError(
            f"kv dtype must be one of {KV_DTYPES[1:]} (or '' for the "
            f"model dtype), got {name!r}")
    if not name:
        return None
    if name == "int8":
        return "int8"
    return jnp.dtype(name)


def is_quantized_pool(pool) -> bool:
    """True for the (int8 values, f32 scales) tuple representation."""
    return isinstance(pool, (tuple, list)) and len(pool) == 2


def quantize_kv_rows(kv):
    """Symmetric absmax int8 quantization over head_dim.

    kv: [N, H, D] float → (values int8 [N, H, D], scales f32 [N, H]).
    All-zero rows (trash writes, zero-init pools) get scale 0 and
    dequantize back to exact zeros.
    """
    kv32 = kv.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(kv32), axis=-1)
    scale = absmax / jnp.float32(127.0)
    safe = jnp.maximum(scale, jnp.float32(1e-12))[..., None]
    q = jnp.clip(jnp.round(kv32 / safe), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_kv(values, scales, dtype=jnp.float32):
    """Inverse of ``quantize_kv_rows``: values [..., H, D] int8 with
    scales [..., H] → float ``dtype``."""
    return (values.astype(jnp.float32)
            * scales.astype(jnp.float32)[..., None]).astype(dtype)


def kv_pool_bytes(num_pages, page_size, num_heads, head_dim,
                  kv_dtype) -> int:
    """Bytes of ONE pool (K or V) per layer for a given storage dtype,
    including the per-slot-per-head f32 scales when quantized. The
    shardcheck KV-bytes projection and the engine's pool gauges both
    size from here so they can never disagree."""
    values = math.prod(kv_pool_shape(num_pages, page_size, num_heads,
                                     head_dim))
    if (kv_dtype or "") == "int8":
        return values + 4 * math.prod(
            kv_pool_shape(num_pages, page_size, num_heads))
    dt = jnp.dtype(kv_dtype) if kv_dtype else jnp.dtype(jnp.float32)
    return values * dt.itemsize


def flat_slots(block_tables, positions, valid, page_size: int,
               ring: bool = False):
    """Flat pool-slot index for each (row, position): ``page * page_size
    + offset`` through the block table, or a trash-page slot (< page_size)
    where ``valid`` is False. With ``ring`` the table is a ring of its
    ``P`` pages: logical page ``n`` lives in entry ``n % P`` (a window
    layer's table, ``ring_positions``).

    block_tables: [B, P] int32; positions: [B, S] int32; valid: [B, S]
    bool. Returns [B, S] int32.
    """
    page_idx = positions // page_size
    if ring:
        page_idx = page_idx % block_tables.shape[1]
    offset = positions % page_size
    # clip so dead lanes with positions past the table read page 0, not
    # out of bounds (jax clamps gathers, but be explicit)
    page_idx = jnp.clip(page_idx, 0, block_tables.shape[1] - 1)
    pages = jnp.take_along_axis(block_tables, page_idx, axis=1)
    slots = pages * page_size + offset
    return jnp.where(valid, slots, offset)    # trash page = page 0


def _scatter_flat(arr, slots, rows):
    """Scatter ``rows`` into ``arr`` flattened over its (page, slot)
    leading dims."""
    num_pages, page_size = arr.shape[0], arr.shape[1]
    flat = arr.reshape(num_pages * page_size, *arr.shape[2:])
    flat = flat.at[slots].set(rows.astype(arr.dtype))
    return flat.reshape(arr.shape)


def write_pool(pool, slots, kv):
    """Scatter ``kv`` rows into the flattened pool at ``slots``.

    pool: [num_pages, page_size, H * D] (or the quantized (values,
    scales) tuple — this is the quantize-on-write point, a head at a
    time, before the heads are folded); slots: [N] int32 flat slot ids;
    kv: [N, H, D]. Duplicate trash-slot writes are unordered — the
    trash page holds garbage by contract.
    """
    n = kv.shape[0]
    if is_quantized_pool(pool):
        values, scales = pool
        qrows, srows = quantize_kv_rows(kv)
        return (_scatter_flat(values, slots, qrows.reshape(n, -1)),
                _scatter_flat(scales, slots, srows))
    return _scatter_flat(pool, slots, kv.reshape(n, -1))


def _gather_flat(arr, block_tables):
    num_pages, page_size = arr.shape[0], arr.shape[1]
    flat = arr.reshape(num_pages * page_size, *arr.shape[2:])
    slots = (block_tables[:, :, None] * page_size
             + jnp.arange(page_size, dtype=block_tables.dtype)[None, None])
    b = block_tables.shape[0]
    return flat[slots.reshape(b, -1)]


def gather_pool(pool, block_tables, kv_heads: int, out_dtype=None):
    """Gather every slot a block table can address, in logical order,
    the heads unfolded again.

    pool: [num_pages, page_size, H * D] (or the quantized tuple — this
    is the pure-JAX dequantize-on-read point); block_tables: [B, P]
    int32; kv_heads: H. Returns [B, P * page_size, H, D] where gathered
    row ``t`` holds logical position ``t`` of each sequence (pages are
    table-ordered).
    """
    quantized = is_quantized_pool(pool)
    vg = _gather_flat(pool[0] if quantized else pool, block_tables)
    vg = vg.reshape(*vg.shape[:2], kv_heads, vg.shape[2] // kv_heads)
    if quantized:
        return dequantize_kv(vg, _gather_flat(pool[1], block_tables),
                             out_dtype or jnp.float32)
    return vg


def ring_pages(window: int, page_size: int) -> int:
    """Pages a window layer keeps a sequence: the ``window`` positions a
    token attends (itself among them) span at most this many pages,
    wherever in a page the newest one falls."""
    return -(-int(window) // int(page_size)) + 1


def ring_positions(ctx_len, pages: int, page_size: int):
    """The logical position held by each slot a ring table addresses,
    in the order ``gather_pool`` returns them: entry ``r`` of a lane
    whose newest page is ``last = (ctx - 1) // page_size`` holds logical
    page ``last - (last - r) % pages``, the newest one congruent to
    ``r``. Negative where that entry was never written. ctx_len: [B]
    (the written positions included). Returns [B, pages * page_size]."""
    last = (ctx_len.astype(jnp.int32) - 1) // page_size          # [B]
    r = jnp.arange(pages, dtype=jnp.int32)[None, :]
    page = last[:, None] - (last[:, None] - r) % pages            # [B, P]
    offs = jnp.arange(page_size, dtype=jnp.int32)
    return (page[:, :, None] * page_size + offs).reshape(
        ctx_len.shape[0], pages * page_size)


def _masked_attention(q, ks, vs, mask, scale):
    """Attention of a window of queries against gathered context under
    an explicit mask, K/V heads shared by groups of query heads.

    q: [B, S, Hq, D]; ks/vs: [B, T, Hkv, D] with Hq a multiple of Hkv
    (KV head ``g`` serves query heads ``g*G .. g*G+G-1``); mask:
    [B, S, T]. Scores and softmax in f32. Masked slots get -1e30 (an
    all-dead row must stay finite; its output is discarded)."""
    b, s, hq, d = q.shape
    hkv = ks.shape[2]
    qg = q.reshape(b, s, hkv, hq // hkv, d)
    logits = jnp.einsum("bqhgd,bthd->bhgqt", qg, ks,
                        preferred_element_type=jnp.float32) \
        * jnp.float32(scale)
    logits = jnp.where(mask[:, None, None], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqt,bthd->bqhgd", probs, vs)
    return out.reshape(b, s, hq, d)


def _decode_attention(q, ks, vs, ctx_len, scale):
    """Single-position attention against the gathered paged context.

    q: [B, 1, H, D]; ks/vs: [B, T, H, D]; ctx_len: [B] int32 — visible
    context length INCLUDING the just-written position (self-attention
    includes self). Masked slots get -1e30 (not -inf: an all-dead lane
    must stay finite through softmax; its output is discarded).
    """
    logits = jnp.einsum("bqhd,bthd->bhqt", q, ks) * \
        jnp.asarray(scale, q.dtype)
    t = ks.shape[1]
    mask = jnp.arange(t)[None, :] < ctx_len[:, None]       # [B, T]
    logits = jnp.where(mask[:, None, None, :], logits,
                       jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqt,bthd->bqhd", probs, vs)
    return out


def _chunked_attention(q, ks, vs, positions, valid, scale):
    """Window attention against the gathered paged context — the
    decode mask generalized from S == 1 to an arbitrary window.

    q: [B, S, H, D]; ks/vs: [B, T, H, D] (gathered in logical order);
    positions: [B, S] int32 absolute position of each window token;
    valid: [B, S]. Query ``s`` sees every logical slot ``t`` with
    ``t <= positions[b, s]`` — its cached prefix plus the window up to
    and including itself (write-then-gather, so self is present).
    Masked slots get -1e30 (not -inf: a fully-masked dead lane must
    stay finite through softmax; its output is discarded).
    """
    logits = jnp.einsum("bqhd,bthd->bhqt", q, ks) * \
        jnp.asarray(scale, q.dtype)
    t = ks.shape[1]
    mask = (jnp.arange(t)[None, None, :] <= positions[:, :, None]) \
        & valid[:, :, None]                                 # [B, S, T]
    logits = jnp.where(mask[:, None, :, :], logits,
                       jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bhqt,bthd->bqhd", probs, vs)


def _pool_shard_spec(pool):
    """shard_map PartitionSpecs for one pool pytree, its heads on
    'mp' (``kv_pool_heads_spec``), values and quantized scales alike."""
    from jax.sharding import PartitionSpec as P
    return jax.tree_util.tree_map(
        lambda a: P(*kv_pool_heads_spec(a.ndim)), pool)


def _mesh_mp(mesh):
    """Live tensor-parallel degree of a serving mesh (0 when absent or
    degenerate)."""
    if mesh is None:
        return 0
    mp = int(mesh.shape.get("mp", 1))
    return mp if mp > 1 else 0


def _sharded_paged_attention(mesh, q, k_pool, v_pool, block_tables,
                             ctx_len, valid, positions, *, page_size,
                             kind, scale, window=None):
    """Per-shard Pallas dispatch under a live mp mesh: every rank runs
    the fused kernel on ITS heads: a block of q's heads axis, the same
    heads' lanes of the pools' rows (attention is embarrassingly
    parallel over heads — no collective in the body). GSPMD cannot
    partition a pallas_call itself, so this shard_map wrapper is what
    keeps the fused path available under tensor parallelism; the
    kernel sees local shapes, so the autotune block table picks tile
    sizes for H/mp heads."""
    from jax.sharding import PartitionSpec as P

    from ..distributed.mesh_utils import manual_shard_map
    from . import pallas_paged_attention as ppa

    def body(q_loc, kp_loc, vp_loc, tables, ctx, val, pos):
        return ppa.paged_attention(
            q_loc, kp_loc, vp_loc, tables, ctx, val, pos,
            page_size=page_size, kind=kind, scale=scale, window=window)

    qspec = P(None, None, "mp", None)
    in_specs = (qspec, _pool_shard_spec(k_pool), _pool_shard_spec(v_pool),
                P(), P(), P(), P())
    return manual_shard_map(body, mesh, in_specs, qspec)(
        q, k_pool, v_pool, block_tables, ctx_len, valid, positions)


def paged_attention_update(q, k, v, k_pool, v_pool, block_tables,
                           ctx_len, valid, positions, *, page_size: int,
                           kind: str, use_flash: bool = True,
                           use_pallas=None, mesh=None, window=None):
    """One layer's cache-aware attention: write this call's K/V into the
    paged pool, then attend.

    q: [B, S, Hq, D], k/v: [B, S, H, D] (S = prompt window for prefill,
    1 for decode; Hq a multiple of H: KV head ``g`` serves query heads
    ``g*G .. g*G+G-1``); k_pool/v_pool: [num_pages, page_size, H * D]
    (``kv_pool_shape``); block_tables: [B, P];
    ctx_len: [B] visible length including the positions written here;
    valid: [B, S] which fed positions are real; positions: [B, S]
    absolute positions being written.

    ``window`` (None: the whole context) is how many positions a token
    attends, itself among them: position ``i`` sees ``j`` with
    ``0 <= i - j < window``. The block table of such a layer is a
    *ring* of its ``P`` pages (``P >= ring_pages(window, page_size)``):
    logical page ``n`` lives in entry ``n % P``, so a sequence holds
    ``P`` pages however long it grows, and only positions a later step
    can still see are written (a prefill keeps its last ``window``).
    A decode or chunked call over a ring may span at most
    ``(P - 1) * page_size - window + 2`` positions: a longer one could
    overwrite what its own first token still attends.

    kind="prefill": K/V of the window are right here, so attention is
    ordinary causal attention over the window (bit-identical to the
    uncached path); the pool write only *persists* them for later
    decode steps. Prompts are left-aligned, so a row's garbage pad
    positions cannot leak into its real positions' outputs (causality).

    kind="decode": S == 1; attention reads the whole context back
    through the block table (write-then-gather, so self is included).

    kind="chunked": arbitrary S over a NON-zero starting position —
    suffix prefill after a shared-prefix cache hit, and the
    speculative-decoding verify window. Same write-then-gather as
    decode, with the mask generalized to per-(row, position) causality
    (``t <= positions[b, s]``): window tokens attend to the cached
    prefix AND causally within the window. With positions starting at
    0 this computes the same math as prefill, via the gather path.

    ``use_pallas`` names who attends for decode/chunked: True the
    fused Pallas kernels that read K/V through the block table
    (ops/pallas_paged_attention.py), False the pure body below, which
    gathers every slot a table can address and is the reference the
    kernels are tested against. None (the default) is decided by what
    the code can observe (``kernel_by_default``): the kernels on a TPU
    wherever they can serve the call, the pure body elsewhere — off
    the chip a kernel runs in Pallas's interpreter, a test's tool and
    not a path. The pure body never answers for a kernel that was
    asked for by name: such a call the kernel cannot serve raises.
    Prefill reads no pool and is ``attention_bshd`` either way.

    ``mesh`` is the serving replica's tensor-parallel mesh
    (serving/mesh.py) with weights and pools heads-sharded over 'mp'.
    It only changes HOW the Pallas kernels dispatch: GSPMD cannot
    partition a pallas_call, so under a live 'mp' axis the fused
    kernels run per-shard through shard_map (each rank on its H/mp
    heads-block of q and the pools). The pure-JAX path ignores the mesh
    entirely — write/gather/attend are all heads-pointwise, and GSPMD
    partitions them from the operands' committed shardings; that path
    is the oracle the shard_map dispatch is tested against.
    (``ServingMesh.validate_heads`` refuses heads that mp does not
    divide before any of this is traced.)

    Returns (attn_out [B, S, H, D], k_pool', v_pool').
    """
    if kind not in KINDS:
        raise ValueError(f"kind must be one of {KINDS}, got {kind!r}")
    # the scopes are metadata (every operation's op_name begins
    # "paged_attention/kv_write|kv_gather|attend"): they name this
    # layer's device time in a profile and change no operation
    with jax.named_scope("paged_attention"):
        return _paged_attention_update(
            q, k, v, k_pool, v_pool, block_tables, ctx_len, valid,
            positions, page_size=page_size, kind=kind,
            use_flash=use_flash, use_pallas=use_pallas, mesh=mesh,
            window=None if window is None else int(window))


def paged_latent_attention_update(q, latent, pool, block_tables, ctx_len,
                                  valid, positions, *, page_size: int,
                                  kind: str, scale: float, value_dim: int,
                                  expanded=None, use_flash: bool = True,
                                  use_pallas=None):
    """One latent-attention layer's cache-aware attention (multi-head
    latent attention, DeepSeek-V2): write this call's latent rows into
    the layer's one pool, then attend.

    latent: [B, S, W], a token's row for every head: the normed latent
    (``value_dim`` wide) and then the rotated key part all heads share;
    pool: [num_pages, page_size, L] (``latent_pool_shape``: the row in
    whole lane tiles, zeros past W; no V pool); block_tables, ctx_len,
    valid, positions as ``paged_attention_update`` takes them.

    kind="prefill": ``expanded`` is ``(q, k, v)`` [B, S, H, D] with
    every head's keys and values made from the latent: ordinary causal
    attention over the window (``attention_bshd``); returns [B, S, H,
    Dv]. kind="decode": ``q`` [B, 1, H, W] holds the absorbed queries
    (a head's query through its key up-projection, beside its rotary
    part), so a score is ``q . row`` and the value of a position is its
    row's first ``value_dim`` lanes, the latent, which the caller takes
    through each head's value up-projection; returns [B, 1, H,
    value_dim]. The page-copying kernel attends where ``use_pallas``
    (None: ``kernel_by_default``), the pure body below elsewhere.
    A chunked window is not built for a latent pool."""
    if kind not in ("prefill", "decode"):
        raise ValueError(f"a latent pool serves prefill and decode, not "
                         f"kind={kind!r}")
    b, s, w = latent.shape
    lanes = pool.shape[-1]
    with jax.named_scope("paged_attention"):
        with jax.named_scope("kv_write"):
            slots = flat_slots(block_tables, positions, valid, page_size)
            rows = jnp.pad(latent.reshape(b * s, 1, w),
                           ((0, 0), (0, 0), (0, lanes - w)))
            pool = write_pool(pool, slots.reshape(b * s), rows)
        with jax.named_scope("attend"):
            if kind == "prefill":
                from .flash_attention import attention_bshd
                return attention_bshd(*expanded, causal=True, scale=scale,
                                      use_flash=use_flash), pool
            if use_pallas is None:
                use_pallas = kernel_by_default(page_size)
            if use_pallas:
                from . import pallas_paged_attention as ppa
                out = ppa.paged_latent_decode(
                    q[:, 0], pool, block_tables, ctx_len,
                    page_size=page_size, scale=scale, value_dim=value_dim)
            else:
                rows = gather_pool(pool, block_tables, 1)[:, :, 0, :w]
                out = _latent_decode_attention(q[:, 0], rows, ctx_len,
                                               scale, value_dim)
    return out[:, None].astype(q.dtype), pool


def _latent_decode_attention(q, rows, ctx_len, scale, value_dim):
    """The pure body's decode over gathered latent rows: q [B, H, W],
    rows [B, T, W], ctx_len [B]. Scores and softmax in f32, the values
    the rows' first ``value_dim`` lanes. Returns [B, H, value_dim] f32."""
    logits = jnp.einsum("bhw,btw->bht", q, rows,
                        preferred_element_type=jnp.float32) \
        * jnp.float32(scale)
    seen = jnp.arange(rows.shape[1])[None, None, :] < ctx_len[:, None, None]
    probs = jax.nn.softmax(jnp.where(seen, logits, jnp.float32(-1e30)),
                           axis=-1)
    return jnp.einsum("bht,btc->bhc", probs.astype(rows.dtype),
                      rows[..., :value_dim],
                      preferred_element_type=jnp.float32)


def kernel_by_default(page_size: int) -> bool:
    """Whether a call that names no path takes the fused kernels: on a
    TPU, for pages the kernels can tile (``pallas_paged_attention.
    supported``'s structural half; the ranks are the model's)."""
    from ..framework import place
    return place.on_tpu() and page_size >= 2


def _paged_attention_update(q, k, v, k_pool, v_pool, block_tables,
                            ctx_len, valid, positions, *, page_size,
                            kind, use_flash, use_pallas, mesh, window):
    from . import pallas_paged_attention as ppa
    if use_pallas is None:
        use_pallas = kernel_by_default(page_size) and ppa.supported(
            q, k_pool, block_tables, page_size, kind)
    mp = _mesh_mp(mesh)
    heads = q.shape[2]
    kv_heads = k.shape[2]
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} K/V heads")
    sharded = use_pallas and mp > 0 and kv_heads % mp == 0
    ring = window is not None
    b, s = q.shape[0], q.shape[1]
    pages = block_tables.shape[1]
    if ring:
        if pages < ring_pages(window, page_size):
            raise ValueError(
                f"a window of {window} positions needs a ring of "
                f"{ring_pages(window, page_size)} pages of {page_size} "
                f"slots, the table has {pages}")
        # the newest token's page may not be the one the oldest token
        # of the same call still reads, wherever in a page they fall
        if kind != "prefill" and \
                s > pages * page_size - window - page_size + 2:
            raise ValueError(
                f"{s} positions a call over a ring of {pages} x "
                f"{page_size} slots would overwrite what the first of "
                f"them attends (window {window}): at most "
                f"{pages * page_size - window - page_size + 2}")
    scale = 1.0 / math.sqrt(q.shape[-1])
    with jax.named_scope("kv_write"):
        keep = valid
        if ring:
            # only what a later step can still see is kept
            keep = valid & (positions >= ctx_len[:, None] - window)
        slots = flat_slots(block_tables, positions, keep, page_size,
                           ring=ring)
        slots_flat = slots.reshape(b * s)
        # the pool scatter stays OUTSIDE shard_map: heads are the
        # major factor of the flat [P*page, H*D] rows' lanes, so GSPMD
        # partitions the write from the pool's committed sharding
        k_pool = write_pool(k_pool, slots_flat,
                            k.reshape(b * s, *k.shape[2:]))
        v_pool = write_pool(v_pool, slots_flat,
                            v.reshape(b * s, *v.shape[2:]))
    if kind == "prefill":
        with jax.named_scope("attend"):
            from .flash_attention import attention_bshd
            out = attention_bshd(q, k, v, causal=True, scale=scale,
                                 use_flash=use_flash, window=window)
        return out, k_pool, v_pool
    if use_pallas:
        if not ppa.supported(q, k_pool, block_tables, page_size, kind):
            raise ValueError(
                f"the fused paged-attention kernel was asked for "
                f"(use_pallas=True) and cannot serve "
                f"kind={kind!r} q{tuple(q.shape)} page_size={page_size}"
                f" tables{tuple(block_tables.shape)}; see "
                f"pallas_paged_attention.supported")
        # the fused kernels read K/V through the table themselves:
        # their gather is inside "attend"
        with jax.named_scope("attend"):
            if sharded:
                out = _sharded_paged_attention(
                    mesh, q, k_pool, v_pool, block_tables, ctx_len,
                    valid, positions, page_size=page_size, kind=kind,
                    scale=scale, window=window)
            else:
                out = ppa.paged_attention(
                    q, k_pool, v_pool, block_tables, ctx_len, valid,
                    positions, page_size=page_size, kind=kind,
                    scale=scale, window=window)
        return out, k_pool, v_pool
    with jax.named_scope("kv_gather"):
        ks = gather_pool(k_pool, block_tables, kv_heads, out_dtype=q.dtype)
        vs = gather_pool(v_pool, block_tables, kv_heads, out_dtype=q.dtype)
    with jax.named_scope("attend"):
        if ring or kv_heads != heads:
            # where each gathered slot lies in the sequence: table
            # order, or the ring's
            at = ring_positions(ctx_len, pages, page_size) if ring else \
                jnp.broadcast_to(jnp.arange(ks.shape[1], dtype=jnp.int32),
                                 (b, ks.shape[1]))
            if kind == "decode":
                seen = (at < ctx_len[:, None])[:, None, :]
                newest = ctx_len[:, None, None] - 1
            else:
                seen = (at[:, None, :] <= positions[:, :, None]) \
                    & valid[:, :, None]
                newest = positions[:, :, None]
            seen = seen & (at >= 0)[:, None, :]
            if ring:
                seen = seen & (newest - at[:, None, :] < window)
            out = _masked_attention(q, ks, vs, seen, scale)
        elif kind == "decode":
            out = _decode_attention(q, ks, vs, ctx_len, scale)
        else:
            out = _chunked_attention(q, ks, vs, positions, valid, scale)
    return out, k_pool, v_pool
