"""The ``state`` kind of a model's serving cache, for every model whose
layers keep a recurrent state a sequence (a state-space mixer's
convolution tail and state, a retention layer's ``S`` and ``z``): one slot
of the layer's two pools a sequence, however long it grows
(``serving/generation/kv_cache.py``).

A model states the shapes and types of one slot of a layer's two pools
(``slot_shapes``: ``((shape, dtype), (shape, dtype))``); from them come
the zeroed pools (``state_pools``: slot 0 the trash slot), what
``kv_cache_spec()["kinds"]["state"]`` says (``state_kind``: the layers and
the bytes a sequence holds of them all, which the cache manager compares
with the arrays it is handed) and where a program finds a row's slot
(``split_state_column``: the last column of its block-table row, after
the paged layers' columns).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

__all__ = ["state_pools", "state_kind", "split_state_column"]


def state_pools(slot_shapes, state_slots) -> tuple:
    """Zeroed pools of one state layer, ``state_slots`` slots each."""
    if not state_slots:
        raise ValueError(
            "a model with layers that keep a recurrent state sizes their "
            "pools from the lanes: init_kv_pools needs state_slots")
    return tuple(jnp.zeros((int(state_slots), *shape), dtype)
                 for shape, dtype in slot_shapes)


def state_kind(layers, slot_shapes) -> dict:
    """``kv_cache_spec()["kinds"]["state"]``: the state layers and the
    bytes one sequence holds of them all."""
    per_layer = sum(math.prod(shape) * jnp.dtype(dtype).itemsize
                    for shape, dtype in slot_shapes)
    return {"layers": list(layers),
            "bytes_per_slot": int(len(layers) * per_layer)}


def split_state_column(tables, paged: bool):
    """``(the paged layers' columns, the state slot)`` of block-table rows
    ``tables [B, W]``; a model with paged layers needs a column beside
    the slot."""
    if paged and tables.shape[1] < 2:
        raise ValueError("block tables of one column leave none beside "
                         "the state slot")
    return tables[:, :tables.shape[1] - 1], tables[:, -1]
