"""Power retention of degree 2 (Gelada, Buckman, Zhang & Bach, *Scaling
Context Requires Rethinking Attention*, arXiv:2507.04239): attention whose
softmax is replaced by the square of the scaled dot product, with a forget
gate a K/V head. Pure jax functions.

Query head ``i`` of a layer reads K/V head ``h = i // G`` (``G`` query
heads a K/V head). With ``q' = q / sqrt(d)`` and ``G_t[h] = sum_{s <= t}
log gamma_s[h]`` (the gate of position ``s``):

    a_ts   = exp(G_t[h] - G_s[h]) * (q'_t[i] . k_s[h])^2      s <= t
    y_t[i] = sum_s a_ts v_s[h] / (sum_s a_ts + eps)

``phi``, the symmetric degree-2 embedding of ``R^d`` in ``R^D`` (``D =
d(d+1)/2``, 8,256 for ``d`` = 128), has ``phi(q) . phi(k) = (q . k)^2``,
so the same layer is a recurrence over a state a K/V head::

    S_t = gamma_t S_{t-1} + phi(k_t) v_t^T        [D, d]
    z_t = gamma_t z_{t-1} + phi(k_t)              [D]
    y_t = phi(q'_t)^T S_t / (phi(q'_t) . z_t + eps)

What a sequence keeps between calls is ``S`` and ``z`` of every K/V head,
however long it grows: the ``state`` kind of
``serving/generation/kv_cache.py``, one slot of two pools a sequence
(``slot_shapes``; ``z`` as ``phi``'s rows of ``d`` lanes, the last one
padded: whole lane tiles where ``D`` alone is not).

``phi``'s entries are the products ``x_m x_n`` of each unordered pair,
``sqrt(2)`` times where ``m != n``, laid out as the symmetric product's
cyclic diagonals: entry ``o * d + m`` is ``x_m x_{(m + o) mod d}`` for
``o`` in ``0 .. d/2 - 1``, and the last ``d/2`` entries are ``x_m x_{m +
d/2}``. So ``D`` is ``d/2 + 1`` rows of ``d`` lanes, the last one half
full, and each row is the vector times a rotation of itself.

``retention_prefill`` computes a padded ``[rows, L]`` window a row at a
time in the chunked form: inside a chunk of ``chunk`` positions the
quadratic form above (matmuls, no ``phi``), between chunks ``S`` and
``z`` carried by a scan whose body also adds the carried state's share to
that chunk's outputs, so no state of every chunk exists at once. A
position that is not ``valid`` has gate 1 and adds nothing, so the state
a row leaves is its state *at its last real position*.
``retention_decode_pools`` is the recurrence itself, one position a lane,
over the slots of the pools: ``y = gamma phi(q')^T S_old + (q' . k)^2 v``
from the old state, which the same step overwrites with the new. On a TPU
it is one Pallas kernel (``ops/pallas_retention.py``) that reads each
live slot once and writes it once; the pure body here (a scan over lanes)
reads a slot for the output and again for the update.

Precision: the gates, decays, ``phi`` and every state are float32; the
products run in float32 (on a TPU at the default matmul precision).

Scopes (metadata): ``retention/chunk_scan`` (prefill),
``retention/state_update`` (decode), ``retention/phi``; the projections
around them are the model's (``retention/qkv``, ``retention/out``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["state_dim", "phi", "slot_shapes", "retention_prefill",
           "retention_decode_pools"]

_F32 = jnp.float32


def state_dim(head_dim: int) -> int:
    """``D``: the width of ``phi`` of a head of ``head_dim``."""
    return head_dim * (head_dim + 1) // 2


def slot_shapes(kv_heads: int, head_dim: int) -> tuple:
    """Shapes of one slot of a layer's two pools: ``S [Hk, D, d]`` (``D``
    on the sublanes, ``d`` on the lanes) and ``z [Hk, d/2 + 1, d]``
    (``phi``'s rows; the last row's second half is never written)."""
    return ((kv_heads, state_dim(head_dim), head_dim),
            (kv_heads, head_dim // 2 + 1, head_dim))


def _z_rows(z, d: int):
    """``z [..., D]`` as the pool keeps it, ``[..., d/2 + 1, d]``."""
    rows = d // 2 + 1
    pad = [(0, 0)] * (z.ndim - 1) + [(0, rows * d - z.shape[-1])]
    return jnp.pad(z, pad).reshape(*z.shape[:-1], rows, d)


def phi(x):
    """The symmetric degree-2 embedding of ``x [..., d]`` (``d`` even):
    ``[..., d(d+1)/2]`` float32, ``phi(q) . phi(k) = (q . k)^2``."""
    x = x.astype(_F32)
    d = x.shape[-1]
    half = d // 2
    root2 = _F32(math.sqrt(2.0))
    rows = [x * x] + [root2 * x * jnp.roll(x, -o, axis=-1)
                      for o in range(1, half)]
    rows.append(root2 * x[..., :half] * x[..., half:])
    return jnp.concatenate(rows, axis=-1)


def _row(q, k, v, log_gate, valid, *, chunk: int, eps: float):
    """One row of ``retention_prefill``: q [L, Hk, G, d], k, v [L, Hk,
    d], log_gate [L, Hk], valid [L]; L a multiple of ``chunk``."""
    length, kv_heads, group, d = q.shape
    n = length // chunk
    big = state_dim(d)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    out = v.dtype
    q = q.astype(_F32) * _F32(d ** -0.5)
    k, v = k.astype(_F32), v.astype(_F32)

    def body(carry, at):
        s, z = carry                          # [Hk, D, d], [Hk, D]
        qc, kc, vc, gc, ok = at
        b = jnp.cumsum(gc, axis=0)            # [C, Hk] log decay from start
        # ---- inside the chunk: the quadratic form with the decays
        gap = jnp.where(causal[None] & ok[None, None, :],
                        b.T[:, :, None] - b.T[:, None, :], -jnp.inf)
        dots = jnp.einsum("thgd,shd->hgts", qc, kc,
                          preferred_element_type=_F32)
        a = jnp.square(dots) * jnp.exp(gap)[:, None]    # [Hk, G, C, C]
        num = jnp.einsum("hgts,shd->thgd", a, vc, preferred_element_type=_F32)
        den = jnp.moveaxis(jnp.sum(a, axis=-1), 2, 0)   # [C, Hk, G]
        # ---- the carried state's share of the chunk's outputs
        with jax.named_scope("phi"):
            pq = phi(qc)                      # [C, Hk, G, D]
            pk = phi(kc)                      # [C, Hk, D]
        into = jnp.exp(b)                     # [C, Hk]
        num = num + into[:, :, None, None] * jnp.einsum(
            "thgx,hxd->thgd", pq, s, preferred_element_type=_F32)
        den = den + into[:, :, None] * jnp.einsum(
            "thgx,hx->thg", pq, z, preferred_element_type=_F32)
        # ---- and what the chunk adds to the state it hands on
        left = jnp.where(ok[:, None], jnp.exp(b[-1][None] - b), 0.0)
        pk = pk * left[:, :, None]
        through = jnp.exp(b[-1])              # [Hk]
        s = through[:, None, None] * s + jnp.einsum(
            "shx,shd->hxd", pk, vc, preferred_element_type=_F32)
        z = through[:, None] * z + jnp.sum(pk, axis=0)
        return (s, z), num / (den[..., None] + _F32(eps))

    def chunks(a):
        return a.reshape(n, chunk, *a.shape[1:])

    (s, z), y = jax.lax.scan(
        body, (jnp.zeros((kv_heads, big, d), _F32),
               jnp.zeros((kv_heads, big), _F32)),
        tuple(chunks(a) for a in (q, k, v, log_gate, valid)))
    return y.reshape(length, kv_heads, group, d).astype(out), s, z


def retention_prefill(q, k, v, log_gate, valid, *, chunk: int = 128,
                      eps: float = 1e-6):
    """The layer's core over a padded window.

    q: [R, L, Hk, G, d] (after the q norm and the positions); k, v: [R, L,
    Hk, d]; log_gate: [R, L, Hk] (``log gamma``; float32); valid: [R, L]
    bool. Every row starts from a zero state. Returns ``(y [R, L, Hk, G,
    d] in v's type, S [R, Hk, D, d], z [R, Hk, d/2 + 1, d])``, the states
    float32 and laid out as a slot keeps them: ``y`` at an invalid
    position means nothing, ``S`` and ``z`` are each row's state at its
    last valid position. The rows are computed one after another, so what
    a chunk keeps is one row's."""
    length = valid.shape[1]
    pad = -length % chunk
    with jax.named_scope("retention"), jax.named_scope("chunk_scan"):
        log_gate = jnp.where(valid[..., None], log_gate.astype(_F32), 0.0)
        feeds = (q, k, v, log_gate, valid)
        if pad:
            feeds = tuple(jnp.pad(a, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (a.ndim - 2)) for a in feeds)
        y, s, z = jax.lax.map(
            lambda row: _row(*row, chunk=chunk, eps=eps), feeds)
    return y[:, :length], s, _z_rows(z, q.shape[-1])


def retention_decode_pools(q, k, v, log_gate, slots, live, s_pool, z_pool,
                           *, eps: float = 1e-6, use_pallas: bool = False):
    """One position a lane over the pools. q: [B, Hk, G, d]; k, v: [B,
    Hk, d]; log_gate: [B, Hk]; slots: [B] int; live: [B] bool; s_pool:
    [slots, Hk, D, d]; z_pool: [slots, Hk, d/2 + 1, d]. Lane ``b`` reads
    and writes slot ``slots[b]`` of both; a lane that is not ``live`` has
    gate 0 and writes the trash slot 0, so no live slot moves. With the
    pools donated each live slot is written where it lies and nothing of
    a pool's size is kept beside it: with ``use_pallas`` (float32 pools)
    by one kernel that reads each slot once, else a lane at a time (a
    scan whose carry is the pools). Returns ``(y [B, Hk, G, d] in v's
    type, s_pool', z_pool')``."""
    b, kv_heads, group, d = q.shape
    big = state_dim(d)
    scale = _F32(d ** -0.5)
    qs = q.astype(_F32) * scale
    kf, vf = k.astype(_F32), v.astype(_F32)
    slots = jnp.where(live, slots.astype(jnp.int32), 0)
    gamma = jnp.where(live[:, None], jnp.exp(log_gate.astype(_F32)), 0.0)
    if use_pallas and s_pool.dtype == z_pool.dtype == _F32:
        from .pallas_retention import retention_decode_kernel
        with jax.named_scope("retention"), \
                jax.named_scope("state_update"):
            y, s_pool, z_pool = retention_decode_kernel(
                qs, kf, vf, gamma, slots, s_pool, z_pool, eps=eps)
        return y.astype(v.dtype), s_pool, z_pool
    with jax.named_scope("retention"), jax.named_scope("phi"):
        pq, pk = phi(qs), phi(kf)             # [B, Hk, G, D], [B, Hk, D]
        here = jnp.square(jnp.einsum("bhgd,bhd->bhg", qs, kf))
    with jax.named_scope("retention"), jax.named_scope("state_update"):

        def lane(pools, at):
            s_pool, z_pool = pools
            slot, g, pq, pk, v, here = at
            s = jax.lax.dynamic_index_in_dim(
                s_pool, slot, keepdims=False).astype(_F32)
            z = jax.lax.dynamic_index_in_dim(
                z_pool, slot, keepdims=False).astype(_F32).reshape(
                    kv_heads, -1)[:, :big]
            num = g[:, None, None] * jnp.einsum(
                "hgx,hxd->hgd", pq, s, preferred_element_type=_F32) \
                + here[:, :, None] * v[:, None, :]
            den = g[:, None] * jnp.einsum(
                "hgx,hx->hg", pq, z, preferred_element_type=_F32) + here
            s = g[:, None, None] * s + pk[:, :, None] * v[:, None, :]
            z = g[:, None] * z + pk
            s_pool = jax.lax.dynamic_update_index_in_dim(
                s_pool, s.astype(s_pool.dtype), slot, 0)
            z_pool = jax.lax.dynamic_update_index_in_dim(
                z_pool, _z_rows(z, d).astype(z_pool.dtype), slot, 0)
            return (s_pool, z_pool), num / (den[..., None] + _F32(eps))

        (s_pool, z_pool), y = jax.lax.scan(
            lane, (s_pool, z_pool), (slots, gamma, pq, pk, vf, here))
    return y.astype(v.dtype), s_pool, z_pool
