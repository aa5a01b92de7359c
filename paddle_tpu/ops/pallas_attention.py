"""Pallas flash-attention (TPU) — forward AND backward kernels.

Replaces the reference's CUDA flash_attn binding
(/root/reference/paddle/phi/api/yaml/ops.yaml:546, backward :558;
dynload at /root/reference/paddle/phi/backends/dynload/flashattn.cc).

Design:
- forward: grid (batch*heads, q_blocks); each program streams K/V blocks
  through VMEM with a fori_loop keeping running max/denominator (classic
  online softmax). Also emits the per-row logsumexp residual.
- backward: two kernels, both recomputing the attention probabilities from
  (q, k, lse) inside the kernel — O(S) memory, no S×S materialization:
    * dq:   grid (bh, q_blocks, k_blocks), f32 VMEM scratch accumulator
    * dk/dv: grid (bh, k_blocks, q_blocks), two f32 scratch accumulators
  Causal runs skip whole blocks above the diagonal via pl.when.
- row statistics (lse, delta=rowsum(o*do)) ride as [bh, S, 128] f32 arrays
  (TPU tiling wants a 128-lane last dim; values replicated across lanes).
- bf16 inputs, f32 accumulation on the MXU throughout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import place as _place

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
LANES = 128
NEG_INF = -1e30
# Below this sequence length the S×S XLA recompute backward is faster than
# the blocked kernels (grid overhead dominates; the S×S scores still fit in
# VMEM-friendly fusions). Measured on v5e: s=512 XLA bwd ~5× faster; the
# kernel path wins as S grows and is mandatory once S×S won't fit.
BWD_PALLAS_MIN_SEQ = 1024


def _i0():
    """Index-map zero as i32: under jax_enable_x64 a bare python 0 traces as
    i64 and Mosaic refuses the mixed-width index tuple."""
    return jnp.int32(0)


# ---------------------------------------------------------------- forward

def _mha_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                    block_k, kv_len, window=None):
    # q_ref: [block_q, d]; k_ref/v_ref: [kv_len, d]; o_ref: [block_q, d]
    # lse_ref: [block_q, LANES] (row logsumexp replicated across lanes)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    # MXU fast path: keep q/k/v in their native (bf16) dtype and let
    # ``preferred_element_type=f32`` give bf16×bf16→f32 accumulation; an
    # upfront .astype(f32) would force 3-pass f32 matmuls (~4× slower on
    # v5e — measured as the round-2 kernel's whole-step loss vs XLA).
    # The softmax statistics still run in f32.
    q = q_ref[:]
    q_idx = pl.program_id(1)

    m_init = jnp.full((block_q,), NEG_INF, jnp.float32)
    l_init = jnp.zeros((block_q,), jnp.float32)
    acc_init = jnp.zeros((block_q, d), jnp.float32)

    num_kb = kv_len // block_k

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # scalars must be explicit f32: under jax_enable_x64 a python float
        # is a weak f64 and Mosaic cannot legalize the resulting truncf
        s = s * jnp.float32(sm_scale)
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            seen = q_pos >= k_pos
            if window is not None:
                seen = seen & (q_pos - k_pos < jnp.int32(window))
            s = jnp.where(seen, s, jnp.float32(NEG_INF))
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    if causal:
        # only loop over blocks at/below the diagonal (int32 literals: under
        # jax_enable_x64 a bare python int would promote the divisor to i64)
        last_kb = jax.lax.div(
            (q_idx + 1) * block_q + block_k - 1, jnp.int32(block_k))
        last_kb = jnp.minimum(last_kb, jnp.int32(num_kb))
    else:
        last_kb = jnp.int32(num_kb)

    first_kb = jnp.int32(0)
    if window is not None:
        # blocks wholly before the first row's window are skipped
        first_kb = jax.lax.div(
            jnp.maximum(q_idx * block_q - jnp.int32(window - 1), 0),
            jnp.int32(block_k))
    m, l, acc = jax.lax.fori_loop(first_kb, last_kb, body,
                                  (m_init, l_init, acc_init))
    l = jnp.maximum(l, jnp.float32(1e-30))
    o_ref[:] = (acc / l[:, None]).astype(o_ref.dtype)
    lse = m + jnp.log(l)
    lse_ref[:] = jax.lax.broadcast_in_dim(lse, (block_q, LANES), (0,))


def _mha_fwd(q, k, v, causal, sm_scale, block_q, block_k, window=None):
    """Returns (out [b,h,sq,d], lse [b*h, sq, LANES] f32). ``k``/``v``
    may hold fewer heads than ``q`` (a multiple): K/V head ``g`` then
    serves query heads ``g*G .. g*G+G-1``, and is copied once for all of
    them (consecutive grid steps name the same block). ``window``
    (causal only): a row sees its last ``window`` positions, itself
    among them."""
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * hk, sk, d)
    vr = v.reshape(b * hk, sk, d)
    if hk == h:
        def kv_map(bh, i):
            return (bh, _i0(), _i0())
    else:
        def kv_map(bh, i):
            return (jax.lax.div(bh, jnp.int32(h // hk)), _i0(), _i0())

    kernel = functools.partial(
        _mha_fwd_kernel, sm_scale=sm_scale, causal=causal, block_k=block_k,
        kv_len=sk, **({} if window is None else {"window": int(window)}))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i: (bh, i, _i0())),
            pl.BlockSpec((None, sk, d), kv_map),
            pl.BlockSpec((None, sk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i: (bh, i, _i0())),
            pl.BlockSpec((None, block_q, LANES),
                         lambda bh, i: (bh, i, _i0())),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=not _place.on_tpu(),
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d), lse


# ---------------------------------------------------------------- backward

def _mha_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                       acc_ref, *, sm_scale, causal, block_k):
    # q/do/dq: [block_q, d]; k/v: [block_k, d]; lse/di: [block_q, LANES]
    block_q, d = q_ref.shape
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: skip K blocks strictly above the diagonal
    needed = True
    if causal:
        needed = k_idx * block_k <= (q_idx + 1) * block_q - 1

    @pl.when(needed)
    def _acc():
        # native-dtype (bf16) matmul inputs, f32 accumulation — see fwd
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        do = do_ref[:]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * jnp.float32(sm_scale)
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        # lse/di replicated over LANES; tile to block_k width
        reps = block_k // LANES
        lse = jnp.tile(lse_ref[:], (1, reps))
        di = jnp.tile(di_ref[:], (1, reps))
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di) * jnp.float32(sm_scale)
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k_idx == nk - 1)
    def _out():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _mha_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                        block_q):
    # k/v/dk/dv: [block_k, d]; q/do: [block_q, d]; lse/di: [block_q, LANES]
    block_k, d = k_ref.shape
    k_idx = pl.program_id(1)
    q_idx = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # causal: Q block participates iff its last row sees this K block
    needed = True
    if causal:
        needed = (q_idx + 1) * block_q - 1 >= k_idx * block_k

    @pl.when(needed)
    def _acc():
        # native-dtype (bf16) matmul inputs, f32 accumulation — see fwd
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        do = do_ref[:]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * jnp.float32(sm_scale)
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        reps = block_k // LANES
        lse = jnp.tile(lse_ref[:], (1, reps))
        di = jnp.tile(di_ref[:], (1, reps))
        p = jnp.exp(s - lse)                              # [block_q, block_k]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # p^T @ do
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di) * jnp.float32(sm_scale)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # ds^T @ q

    @pl.when(q_idx == nq - 1)
    def _out():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _mha_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
             lse_ct=None):
    """dq/dk/dv via the blocked kernels. ``lse_ct`` (optional [b,h,sq])
    is a cotangent on the logsumexp output: since ds = p*(dp - di), a
    cotangent g_lse on lse contributes ds += p*g_lse, which folds in
    exactly as di -= g_lse (used by the ring-attention chunk combine)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    dor = g.reshape(b * h, sq, d)
    # delta_i = rowsum(dO * O): cheap elementwise reduce, leave it to XLA,
    # replicate across the 128-lane stat layout
    di = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)
    if lse_ct is not None:
        di = di - lse_ct.astype(jnp.float32).reshape(b * h, sq)
    di = jnp.broadcast_to(di.reshape(b * h, sq, 1), (b * h, sq, LANES))

    dq_kernel = functools.partial(_mha_bwd_dq_kernel, sm_scale=sm_scale,
                                  causal=causal, block_k=block_k)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, _i0())),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh, j, _i0())),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh, j, _i0())),
            pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, i, _i0())),
            pl.BlockSpec((None, block_q, LANES),
                         lambda bh, i, j: (bh, i, _i0())),
            pl.BlockSpec((None, block_q, LANES),
                         lambda bh, i, j: (bh, i, _i0())),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda bh, i, j: (bh, i, _i0())),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=not _place.on_tpu(),
    )(qr, kr, vr, dor, lse, di)

    dkv_kernel = functools.partial(_mha_bwd_dkv_kernel, sm_scale=sm_scale,
                                   causal=causal, block_q=block_q)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * h, sk // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, j, _i0())),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh, i, _i0())),
            pl.BlockSpec((None, block_k, d), lambda bh, i, j: (bh, i, _i0())),
            pl.BlockSpec((None, block_q, d), lambda bh, i, j: (bh, j, _i0())),
            pl.BlockSpec((None, block_q, LANES),
                         lambda bh, i, j: (bh, j, _i0())),
            pl.BlockSpec((None, block_q, LANES),
                         lambda bh, i, j: (bh, j, _i0())),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d),
                         lambda bh, i, j: (bh, i, _i0())),
            pl.BlockSpec((None, block_k, d),
                         lambda bh, i, j: (bh, i, _i0())),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=not _place.on_tpu(),
    )(qr, kr, vr, dor, lse, di)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# ---------------------------------------------------------------- public op

def _mha_reference(q, k, v, causal, sm_scale):
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _check_mha_args(q, k, causal, block_q, block_k):
    if block_q < LANES or block_k < LANES or block_q % LANES or \
            block_k % LANES:
        raise ValueError(
            f"block_q/block_k must be multiples of {LANES} (got "
            f"{block_q}/{block_k}); the backward row-stat tiles are "
            f"{LANES}-lane replicated")
    sq, sk = q.shape[2], k.shape[2]
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"sequence lengths must be multiples of the block sizes (got "
            f"sq={sq} % block_q={block_q}, sk={sk} % block_k={block_k}); "
            f"the grid covers whole blocks only — pad the sequence or use "
            f"the XLA attention path (ops.flash_attention.supported gates "
            f"this automatically)")
    if causal and q.shape[2] != k.shape[2]:
        raise ValueError(
            f"causal mha requires sq == sk (got {q.shape[2]} vs "
            f"{k.shape[2]}); the kernel masks top-left aligned")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def mha(q, k, v, causal=False, sm_scale=None, block_q=DEFAULT_BLOCK_Q,
        block_k=DEFAULT_BLOCK_K):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    _check_mha_args(q, k, causal, block_q, block_k)
    out, _ = _mha_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return out


def _mha_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    _check_mha_args(q, k, causal, block_q, block_k)
    out, lse = _mha_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _mha_vjp_bwd(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    # a custom_vjp's backward is traced on its own, outside the scope
    # its forward was called in (ops/flash_attention.py)
    with jax.named_scope("flash_attention"):
        if q.shape[2] < BWD_PALLAS_MIN_SEQ:
            _, vjp_fn = jax.vjp(
                lambda qq, kk, vv: _mha_reference(qq, kk, vv, causal,
                                                  sm_scale),
                q, k, v)
            return vjp_fn(g)
        return _mha_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q,
                        block_k)


mha.defvjp(_mha_vjp_fwd, _mha_vjp_bwd)


def mha_forward(q, k, v, *, sm_scale, block_q, block_k, window=None):
    """Causal forward alone, for serving: grouped K/V heads (``k``/``v``
    [B, Hkv, S, D] under ``q`` [B, Hq, S, D]) and a sliding ``window``,
    neither of which the backward kernels know."""
    _check_mha_args(q, k, True, block_q, block_k)
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads over {k.shape[1]} "
                         f"K/V heads")

    def run(q, k, v):
        return _mha_fwd(q, k, v, True, sm_scale, block_q, block_k,
                        window=window)[0]

    call = jax.custom_vjp(run)
    call.defvjp(lambda *a: (run(*a), None), _forward_only_bwd)
    return call(q, k, v)


def _forward_only_bwd(_res, _g):
    raise NotImplementedError(
        "mha_forward (grouped K/V heads, sliding window) is the serving "
        "prefill's kernel and has no backward")
