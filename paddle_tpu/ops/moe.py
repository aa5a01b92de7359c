"""Dropless top-k expert layer (sparse ReGLU experts), one chip.

Every token goes to its ``top_k`` experts and every assignment is
computed: no capacity, no token dropped. The work is laid out the way
MegaBlocks (Gale et al., MLSys '23) lays it out: the ``T * top_k``
assignments are sorted by expert, so that each expert's rows are
contiguous, and each of the three projections is ONE grouped product
over the sorted rows (``jax.lax.ragged_dot``: on a TPU XLA lowers it
to a Mosaic grouped matmul that walks row tiles and reads the weights
of the experts that own rows in them, so an expert that got no token
is not read). Then the rows are put back in token order and summed
with the router's weights in float32.

Precision: the router's logits and softmax and the weighted sum are
float32; the three products run in the weights' type with float32
accumulation.

The scopes are metadata: ``moe/route``, ``moe/dispatch``,
``moe/experts``, ``moe/combine`` name this layer's device time in a
profile (the grouped matmuls themselves reach the profile under XLA's
own name, ``ragged-dot*``: the pass that makes them drops ``op_name``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["route_top_k", "dropless_moe"]


def route_top_k(router_in, w_router, top_k: int):
    """``(experts [T, k] int32, weights [T, k] f32)``: the ``top_k``
    largest router logits a token, and a softmax over those alone (the
    same numbers as a softmax over all experts renormalised over the
    chosen). Logits and softmax in float32."""
    logits = jnp.dot(router_in.astype(jnp.float32),
                     w_router.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    top, experts = jax.lax.top_k(logits, top_k)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def dropless_moe(x, router_in, w_router, w_gate, w_up, w_down, *,
                 top_k: int, valid=None):
    """``sum_{e in top_k(router_in @ w_router)} w_e * E_e(x)`` a token,
    ``E_e(x) = (relu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]``.

    x, router_in: [T, H] (the router may read another tensor than the
    experts: here the attention's normed input); w_router: [H, E];
    w_gate, w_up: [E, H, I]; w_down: [E, I, H]; ``valid``: [T] bool or
    None: a row that is padding or a dead lane goes to no expert,
    touches none and gets zeros.

    Returns ``(out [T, H] in x's type, stats)`` with ``stats`` whole
    numbers of this call: ``assignments`` (valid rows x top_k),
    ``experts_touched`` (experts that got a row) and
    ``max_expert_load`` (rows of the fullest).
    """
    t, hidden = x.shape
    n_experts = w_gate.shape[0]
    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            experts, weights = route_top_k(router_in, w_router, top_k)
            if valid is not None:
                # past every expert: sorted last, counted in no group
                experts = jnp.where(valid[:, None], experts, n_experts)
        with jax.named_scope("dispatch"):
            flat = experts.reshape(t * top_k)
            order = jnp.argsort(flat, stable=True)
            rows = x[order // top_k]                       # [T*k, H]
            group_sizes = jnp.bincount(
                flat, length=n_experts + 1)[:n_experts].astype(jnp.int32)
        with jax.named_scope("experts"):
            gate = jax.lax.ragged_dot(rows, w_gate, group_sizes)
            up = jax.lax.ragged_dot(rows, w_up, group_sizes)
            act = (jax.nn.relu(gate) * up).astype(x.dtype)
            down = jax.lax.ragged_dot(act, w_down, group_sizes)
        with jax.named_scope("combine"):
            # back to token order: row i of the sorted list is
            # assignment order[i]
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(t * top_k, dtype=order.dtype))
            per = down[back].astype(jnp.float32).reshape(t, top_k, hidden)
            w = weights
            if valid is not None:
                # rows past the last group are not computed
                w = jnp.where(valid[:, None], w, 0.0)
                per = jnp.where(valid[:, None, None], per, 0.0)
            out = jnp.sum(per * w[:, :, None], axis=1).astype(x.dtype)
        stats = {"assignments": jnp.sum(group_sizes),
                 "experts_touched": jnp.sum(group_sizes > 0),
                 "max_expert_load": jnp.max(group_sizes)}
    return out, stats
