"""Operations and bytes the paged attention of one decode step needs
where the layers are of two kinds and K/V heads are shared by groups of
query heads, from the traffic alone. The yardstick of
``paged_attn_roofline.longctx``.

A decode step adds one token to each live lane and attends from it to
what that lane's layer keeps: a full layer the whole cached context
(``context_tokens``, summed over lanes, the token just written among
them), a window layer the last ``window`` positions of it
(``window_context_tokens`` = the sum of ``min(ctx, window)``). Per
layer, with ``kv_heads`` K/V heads of width ``head_dim`` under
``heads`` query heads:

- read: every position the layer attends, K and V once,
  ``2 * positions * kv_heads * head_dim`` elements of the pool's type
  (a K/V head is read once for its whole group of query heads);
- write: the new token's K and V of each lane;
- operations: scores and weighted sum for every query head,
  ``2 * 2 * positions * heads * head_dim``.

Q, the output and the softmax are a token's worth a lane and are left
out. With 7 query heads a K/V head the intensity is 7 operations a
byte of a bfloat16 pool against the v5e's 240: memory-bound.
"""
from __future__ import annotations


def paged_decode_step_cost(*, context_tokens: float,
                           window_context_tokens: float, lanes: float,
                           full_layers: int, window_layers: int,
                           heads: int, kv_heads: int, head_dim: int,
                           elem_bytes: float) -> dict:
    positions = full_layers * context_tokens \
        + window_layers * window_context_tokens
    written = (full_layers + window_layers) * lanes
    return {
        "flops": 4.0 * heads * head_dim * positions,
        "bytes": 2.0 * kv_heads * head_dim * elem_bytes
        * (positions + written),
    }


def layers_by_kind(model_cfg) -> tuple:
    """``(full, window)`` layer counts of a config object that says
    which layers attend a window (``layer_window``)."""
    windows = [model_cfg.layer_window(i)
               for i in range(model_cfg.num_layers)]
    return sum(1 for w in windows if not w), sum(1 for w in windows if w)
