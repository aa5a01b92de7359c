"""Operations and bytes the paged attention of one decode step needs,
from the traffic alone. The yardstick of ``paged_attn_roofline``.

The work is the traffic's, whatever implements it: a decode step adds
one token to each live lane and attends from it to that lane's cached
context. Per layer, with H heads of width D and ``context_tokens`` =
the sum of the live lanes' context lengths (the token just written
among them):

- read: every cached position's K and V once,
  2 * context_tokens * H * D elements of the pool's type;
- write: the new token's K and V of each lane, 2 * lanes * H * D;
- operations: the scores Q K^T and the weighted sum P V,
  2 * 2 * context_tokens * H * D.

Q, the output and the softmax are a token's worth a lane and are left
out: under 1% of the reads at a mean context of tens of positions.
What an implementation moves beyond this (the gather path reads every
slot of every lane's table, full or not, and writes the gathered copy
before it reads it again; a pool relayout) is not needed by the
traffic and not counted: it lowers the share, as it should.

At one query token a lane the arithmetic intensity is 4 operations
over 2 * elem_bytes bytes, 0.5 FLOP/byte for a float32 pool against
the v5e's 240 FLOP/byte: memory-bound by a wide margin at every
shape the cells run.
"""
from __future__ import annotations


def paged_decode_step_cost(*, context_tokens: float, lanes: float,
                           heads: int, head_dim: int, layers: int,
                           elem_bytes: float) -> dict:
    """FLOPs and HBM bytes of the paged attention of one decode step
    whose live lanes hold ``context_tokens`` cached positions."""
    per_token = 2 * heads * head_dim * layers        # K and V elements
    return {
        "flops": 2.0 * per_token * context_tokens,
        "bytes": per_token * elem_bytes * (context_tokens + lanes),
    }


def pool_elem_bytes(*, pool_bytes: int, pages: int, page_size: int,
                    heads: int, head_dim: int, layers: int) -> float:
    """Bytes the pool holds per cached element, scale planes of a
    quantized pool included: what a reader of one element has to
    move."""
    return pool_bytes / (2.0 * pages * page_size * heads * head_dim
                         * layers)
