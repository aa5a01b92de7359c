"""The state-space mixer of a Mamba-2 layer (Dao & Gu, ICML '24), the
part between its two projections: a depthwise causal convolution, the
selective recurrence and the gated group norm. Pure jax functions.

A layer of ``H`` heads of width ``P`` over ``G`` groups of state size
``N`` (head ``h`` reads group ``h // (H // G)``) takes, a position,
``xBC_t`` (``H*P + 2*G*N`` channels) and ``dt_t`` (``H``):

    xBC_t = silu(b_c + sum_j w_c[:, j] * xBC_{t-K+1+j})     zeros before t=0
    [xs | B | C] = xBC_t            xs [H, P]   B, C [G, N]
    d_t[h] = softplus(dt_t[h] + dt_bias[h])
    a_t[h] = exp(-exp(A_log[h]) * d_t[h])
    S_t[h] = a_t[h] * S_{t-1}[h] + d_t[h] * outer(xs_t[h], B_t[g])
    y_t[h] = S_t[h] C_t[g] + D[h] * xs_t[h]

What a sequence keeps between calls, however long it grows, is the
convolution's tail (its last ``K - 1`` inputs, ``[K-1, H*P + 2*G*N]``)
and ``S`` (``[H, P, N]``, float32): the ``state`` kind of
``serving/generation/kv_cache.py``, one slot of two pools a sequence.

``ssm_prefill`` computes a padded ``[rows, L]`` window in the chunked
(SSD) form: inside a chunk of ``chunk`` positions the recurrence is a
masked product of ``C B^T`` with the decays (matmuls), between chunks
it is carried as a state (a scan over ``L / chunk`` chunks). A position
that is not ``valid`` has ``d_t = 0``: its ``a_t`` is 1 and it adds
nothing, so the state a row leaves is the state *at its last real
position*, and its tail is gathered at ``lens - K + 1 .. lens - 1``
(zeros before 0), not at the window's end. ``ssm_decode_step`` is the
recurrence itself, one position a lane; ``ssm_decode_pools`` runs it
over the slots of the two pools, and ``write_slots`` is how a prefill
leaves its rows' tails and states in theirs.

Precision: ``d``, ``a``, the decays and every state are float32; the
products run in the inputs' type with float32 accumulation (float32
inputs: all float32).

Scopes (metadata): ``ssm/conv``, ``ssm/scan`` (prefill) and
``ssm/state_update`` (decode), ``ssm/gate_norm``; the projections
around them are the model's (``ssm/in_proj``, ``ssm/out_proj``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssm_prefill", "ssm_decode_step", "ssm_decode_pools",
           "gated_group_norm", "slot_shapes", "write_slots"]

_F32 = jnp.float32


def slot_shapes(*, heads: int, head_dim: int, groups: int, state: int,
                conv_kernel: int) -> tuple:
    """Shapes of one slot of a layer's two pools: the convolution tail
    and the SSM state."""
    channels = heads * head_dim + 2 * groups * state
    return ((conv_kernel - 1, channels), (heads, head_dim, state))


def _split(xbc, heads, head_dim, groups, state):
    """``xs [..., H, P]``, ``B`` and ``C [..., G, N]`` of ``xbc``."""
    lead = xbc.shape[:-1]
    inner = heads * head_dim
    xs = xbc[..., :inner].reshape(*lead, heads, head_dim)
    b = xbc[..., inner:inner + groups * state].reshape(*lead, groups, state)
    c = xbc[..., inner + groups * state:].reshape(*lead, groups, state)
    return xs, b, c


def _steps(dt, dt_bias, a_log):
    """``(d, log a)`` float32: the step of each head and the log of its
    decay, ``-exp(A_log) * d``."""
    d = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    return d, -jnp.exp(a_log.astype(_F32)) * d


def gated_group_norm(y, z, weight, *, groups: int, eps: float):
    """``y * silu(z)``, then RMSNorm within each of ``groups`` equal
    groups of the channels, times ``weight``; float32 inside."""
    with jax.named_scope("ssm"), jax.named_scope("gate_norm"):
        lead, width = y.shape[:-1], y.shape[-1]
        g = (y.astype(_F32) * jax.nn.silu(z.astype(_F32))).reshape(
            *lead, groups, width // groups)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1,
                                       keepdims=True) + _F32(eps))
        return (g.reshape(*lead, width) * weight.astype(_F32)).astype(
            y.dtype)


def ssm_prefill(xbc, dt, valid, lens, conv_w, conv_b, dt_bias, a_log,
                d_skip, *, heads: int, head_dim: int, groups: int,
                state: int, chunk: int):
    """The mixer's core over a padded window.

    xbc: [R, L, H*P + 2*G*N] (the projection's output, before the
    convolution); dt: [R, L, H]; valid: [R, L] bool; lens: [R] int32
    (real positions a row, 0 for a dead row); conv_w: [channels, K];
    conv_b: [channels]; dt_bias, a_log, d_skip: [H]. Every row starts
    from a zero state. Returns ``(y [R, L, H*P] in xbc's type,
    conv_tail [R, K-1, channels], S_last [R, H, P, N] float32)``: ``y``
    at an invalid position means nothing.
    """
    r, length, channels = xbc.shape
    k = conv_w.shape[1]
    dtype = xbc.dtype
    per_group = heads // groups
    with jax.named_scope("ssm"), jax.named_scope("conv"):
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        # the tail a row leaves: its last K-1 real inputs
        at = lens.astype(jnp.int32)[:, None] + jnp.arange(k - 1)[None, :]
        tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
        acc = conv_b.astype(_F32)
        for j in range(k):
            acc = acc + padded[:, j:j + length].astype(_F32) \
                * conv_w[:, j].astype(_F32)
        conv = jax.nn.silu(acc).astype(dtype)
    with jax.named_scope("ssm"), jax.named_scope("scan"):
        pad = -length % chunk
        if pad:
            conv = jnp.pad(conv, ((0, 0), (0, pad), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            valid = jnp.pad(valid, ((0, 0), (0, pad)))
        n_chunks = (length + pad) // chunk
        xs, bm, cm = _split(
            conv.reshape(r, n_chunks, chunk, channels), heads, head_dim,
            groups, state)
        d, log_a = _steps(dt, dt_bias, a_log)
        live = valid[:, :, None]
        d = jnp.where(live, d, 0.0).reshape(r, n_chunks, chunk, heads)
        log_a = jnp.where(live, log_a, 0.0).reshape(
            r, n_chunks, chunk, heads)
        cs = jnp.cumsum(log_a, axis=2)          # log of a_1 .. a_l's product
        # ---- inside a chunk: y_l += sum_{s <= l} (C_l . B_s) *
        # a_{s+1} .. a_l * d_s * xs_s
        cb = jnp.einsum("rclgn,rcsgn->rcgls", cm, bm,
                        preferred_element_type=_F32)
        cs_h = jnp.moveaxis(cs, 3, 2)                        # [R, c, H, Q]
        gap = cs_h[:, :, :, :, None] - cs_h[:, :, :, None, :]
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.exp(jnp.where(causal, gap, -jnp.inf))    # [R,c,H,l,s]
        m = decay * jnp.moveaxis(d, 3, 2)[:, :, :, None, :]
        m = m.reshape(r, n_chunks, groups, per_group, chunk, chunk) \
            * cb[:, :, :, None]
        xs_g = xs.reshape(r, n_chunks, chunk, groups, per_group, head_dim)
        y = jnp.einsum("rcgkls,rcsgkp->rclgkp", m.astype(dtype), xs_g,
                       preferred_element_type=_F32)
        # ---- what a chunk adds to the state it is handed: sum_s
        # a_{s+1} .. a_Q * d_s * outer(xs_s, B_s)
        to_end = jnp.exp(cs[:, :, -1:, :] - cs) * d          # [R, c, Q, H]
        xw = (xs_g.astype(_F32) * to_end.reshape(
            r, n_chunks, chunk, groups, per_group, 1)).astype(dtype)
        added = jnp.einsum("rcsgkp,rcsgn->rcgkpn", xw, bm,
                           preferred_element_type=_F32)
        through = jnp.exp(cs[:, :, -1, :]).reshape(
            r, n_chunks, groups, per_group, 1, 1)

        def carry(s, chunk_of):
            a, add = chunk_of
            return a * s + add, s               # a chunk is handed s

        s_last, s_in = jax.lax.scan(
            carry, jnp.zeros((r, groups, per_group, head_dim, state), _F32),
            (jnp.moveaxis(through, 1, 0), jnp.moveaxis(added, 1, 0)))
        # ---- and what the state it is handed adds to its outputs
        from_start = jnp.exp(cs).reshape(
            r, n_chunks, chunk, groups, per_group, 1)
        y = y + from_start * jnp.einsum(
            "rclgn,crgkpn->rclgkp", cm.astype(_F32), s_in,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=_F32)
        y = y + d_skip.astype(_F32).reshape(groups, per_group, 1) \
            * xs_g.astype(_F32)
        y = y.reshape(r, n_chunks * chunk, heads * head_dim)[:, :length]
    return (y.astype(dtype), tail,
            s_last.reshape(r, heads, head_dim, state))


def _lane_inputs(xbc, dt, tail, conv_w, conv_b, dt_bias, a_log, d_skip,
                 heads, head_dim, groups, state):
    """What one position a lane brings to the recurrence, by group
    (float32): ``(window [B, K, channels], xs [B, G, H/G, P], a [B, G,
    H/G], d * xs, B [B, G, N], C, D * xs)``."""
    b = xbc.shape[0]
    per_group = heads // groups
    with jax.named_scope("ssm"), jax.named_scope("conv"):
        window = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)],
                                 axis=1)                     # [B, K, ch]
        conv = jax.nn.silu(
            conv_b.astype(_F32) + jnp.einsum(
                "bkc,ck->bc", window.astype(_F32), conv_w.astype(_F32))
        ).astype(xbc.dtype)
        xs, bm, cm = _split(conv, heads, head_dim, groups, state)
        d, log_a = _steps(dt, dt_bias, a_log)
        xs_g = xs.astype(_F32).reshape(b, groups, per_group, head_dim)
        skip = d_skip.astype(_F32).reshape(groups, per_group, 1) * xs_g
    return (window, jnp.exp(log_a).reshape(b, groups, per_group),
            xs_g * d.reshape(b, groups, per_group, 1), bm.astype(_F32),
            cm.astype(_F32), skip)


def _advance(s, a, dx, bm, cm):
    """``(S', S' C)``: ``S' = a * S + outer(d * xs, B)`` for ``s [...,
    G, H/G, P, N]``."""
    s = a[..., None, None] * s.astype(_F32) \
        + dx[..., None] * bm[..., None, None, :]
    return s, jnp.sum(s * cm[..., None, None, :], axis=-1)


def ssm_decode_step(xbc, dt, tail, s, conv_w, conv_b, dt_bias, a_log,
                    d_skip, *, heads: int, head_dim: int, groups: int,
                    state: int):
    """One position a lane. xbc: [B, channels]; dt: [B, H]; tail: [B,
    K-1, channels] (the lane's last inputs); s: [B, H, P, N] float32.
    Returns ``(y [B, H*P] in xbc's type, tail', s')``."""
    b = xbc.shape[0]
    window, a, dx, bm, cm, skip = _lane_inputs(
        xbc, dt, tail, conv_w, conv_b, dt_bias, a_log, d_skip, heads,
        head_dim, groups, state)
    with jax.named_scope("ssm"), jax.named_scope("state_update"):
        s_new, y = _advance(s.reshape(b, groups, heads // groups, head_dim,
                                      state), a, dx, bm, cm)
    return ((y + skip).reshape(b, heads * head_dim).astype(xbc.dtype),
            window[:, 1:], s_new.reshape(b, heads, head_dim, state))


def write_slots(pool, slots, rows):
    """``pool`` with ``rows[i]`` written at slot ``slots[i]``, a row at
    a time where it lies (a scan whose carry is the pool), as the
    decode step writes its lanes. One scatter of the rows is the same
    mathematics, and hung the chip: a prefill of 4 rows over five
    state-space layers, whose five scatters of ``[4, 128, 64, 128]``
    float32 XLA sinks to the program's end, never returned (my chip
    runs, PR 35; 2 rows, or 4 layers, did)."""
    def one(pool, at):
        slot, row = at
        return jax.lax.dynamic_update_index_in_dim(
            pool, row.astype(pool.dtype), slot, 0), None

    return jax.lax.scan(one, pool, (slots.astype(jnp.int32), rows))[0]


def ssm_decode_pools(xbc, dt, slots, live, tail_pool, s_pool, conv_w,
                     conv_b, dt_bias, a_log, d_skip, *, heads: int,
                     head_dim: int, groups: int, state: int):
    """``ssm_decode_step`` over the pools: lane ``i`` reads and writes
    slot ``slots[i]`` of both; a lane that is not ``live`` reads zeros
    and writes the trash slot 0, so no live slot moves. The states are
    advanced a lane at a time (a scan whose carry is the pool): with
    the pools donated each live slot is read once and written once
    where it lies, and nothing of a pool's size is kept beside it.
    Returns ``(y, tail_pool', s_pool')``."""
    b = xbc.shape[0]
    update = jax.named_scope("ssm/state_update")
    with update:
        slots = jnp.where(live, slots.astype(jnp.int32), 0)
        tail = jnp.where(live[:, None, None], tail_pool[slots], 0)
    window, a, dx, bm, cm, skip = _lane_inputs(
        xbc, dt, tail, conv_w, conv_b, dt_bias, a_log, d_skip, heads,
        head_dim, groups, state)
    by_group = (groups, heads // groups, head_dim, state)

    def lane(pool, at):
        slot, a, dx, bm, cm = at
        s, y = _advance(jax.lax.dynamic_index_in_dim(
            pool, slot, keepdims=False).reshape(by_group), a, dx, bm, cm)
        return jax.lax.dynamic_update_index_in_dim(
            pool, s.reshape(pool.shape[1:]).astype(pool.dtype), slot, 0), y

    with update:
        tail_pool = tail_pool.at[slots].set(window[:, 1:])
        # a dead lane's decay is 0: what the trash slot holds is dropped
        s_pool, y = jax.lax.scan(
            lane, s_pool, (slots, jnp.where(live[:, None, None], a, 0.0),
                           dx, bm, cm))
    return ((y + skip).reshape(b, heads * head_dim).astype(xbc.dtype),
            tail_pool, s_pool)
