"""The decode step of power retention (``ops/retention.py``) as one Pallas
TPU kernel over the state pools: each live lane's ``S`` and ``z`` of each
K/V head are read from HBM once, the token's output is read off the old
state and the new state written back where it lies, in the same pass.

A grid program is a ``(lane, K/V head)``: its blocks are the slot's ``S
[D, d]`` (``D`` on the sublanes, ``d`` on the lanes) and ``z [d/2 + 1,
d]``, found through the slots (scalar prefetch), with both pools aliased
in to out. ``phi``'s layout (``ops/retention.py``: row ``o`` of ``D`` is
``x * roll(x, -o)``, ``sqrt(2)`` times for ``o > 0``, the last row half)
makes each row of ``d`` state rows a lane rotation of the query: the
program walks the ``d/2 + 1`` rows of ``S``, each a ``[d, d]`` tile, and
for each

- adds ``phi(q)``'s row times the tile to the output (the MXU, the
  query's heads of the group as rows) and ``phi(q)``'s row times ``z``'s
  row to the normaliser;
- writes ``gamma * tile + phi(k)[row] v^T`` (``phi(k)``'s row as a
  column, from the transposed feature the caller hands in) and ``gamma
  * z_row + phi(k)``'s row.

Then ``y = (gamma num + (q . k)^2 v) / (gamma den + (q . k)^2 + eps)``.
The tile products run in bfloat16 with float32 accumulation (the default
precision of a float32 product on the TPU); the state stays float32.
A dead lane has gamma 0 and the trash slot 0. Inference only.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import place as _place
from .pallas_attention import _i0
from .pallas_paged_attention import _inference_only
from .retention import phi

_F32 = jnp.float32
VMEM_LIMIT = 48 * 1024 * 1024    # two S blocks in, two out: 17 MiB at d 128


def _kernel(slots_ref, gamma_ref, q_ref, k_ref, v_ref, kt_ref, s_ref, z_ref,
            y_ref, s_out, z_out, *, eps: float):
    del slots_ref                       # the blocks' index maps read it
    gamma = gamma_ref[pl.program_id(0), pl.program_id(1)]
    q, k, v = q_ref[...], k_ref[...], v_ref[...]    # [Gp, d], [1, d]
    d = q.shape[-1]
    half = d // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    num = jnp.zeros(q.shape, _F32)
    den = jnp.zeros((q.shape[0], 1), _F32)
    for o in range(half + 1):
        scale = 1.0 if o == 0 else math.sqrt(2.0)
        rows = d if o < half else half
        lo = o * d
        # roll by d - o: lane m holds x[(m + o) mod d]
        shift = jnp.int32(d - o)
        q_o = scale * q * (q if o == 0 else pltpu.roll(q, shift, 1))
        k_o = scale * k * (k if o == 0 else pltpu.roll(k, shift, 1))
        if o == half:
            q_o = jnp.where(lane < half, q_o, 0.0)
            k_o = jnp.where(lane < half, k_o, 0.0)
        tile = s_ref[lo:lo + rows, :]
        z_o = z_ref[o:o + 1, :]
        num = num + jnp.dot(q_o[:, :rows].astype(jnp.bfloat16),
                            tile.astype(jnp.bfloat16),
                            preferred_element_type=_F32)
        den = den + jnp.sum(q_o * z_o, axis=1, keepdims=True)
        s_out[lo:lo + rows, :] = gamma * tile + kt_ref[:rows, o:o + 1] * v
        z_out[o:o + 1, :] = gamma * z_o + k_o
    here = jnp.square(jnp.sum(q * k, axis=1, keepdims=True))
    y_ref[...] = (gamma * num + here * v) / (gamma * den + here + eps)


def retention_decode_kernel(q, k, v, gamma, slots, s_pool, z_pool, *,
                            eps: float = 1e-6):
    """q: [B, Hk, G, d] (scaled by ``d ** -0.5``); k, v: [B, Hk, d];
    gamma: [B, Hk] (0 for a dead lane); slots: [B] int32 (0 for a dead
    lane); s_pool: [slots, Hk, D, d]; z_pool: [slots, Hk, d/2 + 1, d],
    all float32. Returns ``(y [B, Hk, G, d] float32, s_pool', z_pool')``,
    the pools written where they lie."""
    b, kv_heads, group, d = q.shape
    rows = d // 2 + 1
    padded = -(-group // 8) * 8
    q = jnp.pad(q.astype(_F32), ((0, 0), (0, 0), (0, padded - group),
                                 (0, 0)))
    k, v = k.astype(_F32), v.astype(_F32)
    # phi(k) a row of d lanes at a time, transposed: row o's column
    kt = jnp.pad(phi(k), ((0, 0), (0, 0), (0, rows * d - s_pool.shape[2])))
    kt = jnp.swapaxes(kt.reshape(b, kv_heads, rows, d), 2, 3)

    def lane_head(i, j, slots, gamma):
        return (i, j, _i0(), _i0())

    def slot_head(i, j, slots, gamma):
        return (slots[i], j, _i0(), _i0())

    def spec(shape, index):
        return pl.BlockSpec((None, None) + shape, index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, kv_heads),
        in_specs=[spec((padded, d), lane_head), spec((1, d), lane_head),
                  spec((1, d), lane_head), spec((d, rows), lane_head),
                  spec(s_pool.shape[2:], slot_head),
                  spec(z_pool.shape[2:], slot_head)],
        out_specs=[spec((padded, d), lane_head),
                   spec(s_pool.shape[2:], slot_head),
                   spec(z_pool.shape[2:], slot_head)])

    def run(slots, gamma, q, k, v, kt, s_pool, z_pool):
        return pl.pallas_call(
            functools.partial(_kernel, eps=float(eps)), grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(q.shape, _F32),
                       jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype),
                       jax.ShapeDtypeStruct(z_pool.shape, z_pool.dtype)],
            input_output_aliases={6: 1, 7: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=not _place.on_tpu(),
        )(slots, gamma, q, k, v, kt, s_pool, z_pool)

    y, s_pool, z_pool = _inference_only(run)(
        slots.astype(jnp.int32), gamma.astype(_F32), q, k[:, :, None],
        v[:, :, None], kt, s_pool, z_pool)
    return y[:, :, :group], s_pool, z_pool
