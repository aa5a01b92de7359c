"""Dropless top-k expert layer (sparse gated experts), one chip.

Every token goes to its ``top_k`` experts and every assignment to an
expert held here is computed: no capacity, no token dropped. The work
is laid out the way MegaBlocks (Gale et al., MLSys '23) lays it out:
the ``T * top_k`` assignments are sorted by expert, so that each
expert's rows are contiguous, and each of the three projections is ONE
grouped product over the sorted rows (``jax.lax.ragged_dot``: on a TPU
XLA lowers it to a Mosaic grouped matmul that walks row tiles and reads
the weights of the experts that own rows in them, so an expert that got
no token is not read). Then the rows are put back in token order and
summed with the router's weights in float32.

One chip's share of an expert-parallel layer: the router is as wide as
the model has experts (``w_router.shape[1]``) and the weights hold the
``w_gate.shape[0]`` of them that live here, experts ``offset ..
offset + held - 1``. Every token is routed over all of them; an
assignment to an expert that lives elsewhere sorts past every group (as
a padding row does), is computed by no product and adds nothing, and
the weights of the chosen are normalised over all the chosen, held or
not. What the absent experts would add is left out: the sum over the
shares of a layer is the layer. There is no exchange here: on one chip
the layer runs without it.

XLA's grouped matmul pays for every row it is handed, in a group or
not (PERF.md section 6, PR 33), so a share hands its products only the
first ``cap`` sorted rows, twice what even routing gives its experts
(``2 * T * top_k * held / E_all`` rounded up to whole tiles of 128, at
most ``T * top_k``), where its experts got no more rows than that;
else all ``T * top_k``, so the layer stays dropless (``jax.lax.cond``,
opened outside the scopes so that each branch's operations carry
``moe/...`` as before). A layer that holds every expert has no
capacity and no branch.

Precision: the router's logits, its scores and the weighted sum are
float32; the products run in the weights' type with float32
accumulation.

The scopes are metadata: ``moe/route``, ``moe/dispatch``,
``moe/experts``, ``moe/combine`` and ``moe/shared`` name this layer's
device time in a profile (the grouped matmuls themselves reach the
profile under XLA's own name, ``ragged-dot*``: the pass that makes them
drops ``op_name``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["route_top_k", "route_sigmoid_norm", "dropless_moe"]

_ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu,
                "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def _router_logits(router_in, w_router):
    return jnp.dot(router_in.astype(jnp.float32),
                   w_router.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def route_top_k(router_in, w_router, top_k: int):
    """``(experts [T, k] int32, weights [T, k] f32)``: the ``top_k``
    largest router logits a token, and a softmax over those alone (the
    same numbers as a softmax over all experts renormalised over the
    chosen). Logits and softmax in float32."""
    top, experts = jax.lax.top_k(_router_logits(router_in, w_router), top_k)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def route_sigmoid_norm(router_in, w_router, top_k: int, scale: float,
                       bias=None):
    """``(experts [T, k] int32, weights [T, k] f32)``: the ``top_k``
    largest of ``s = sigmoid(logits)`` a token, each weighted ``scale *
    s_e / sum of the chosen s`` (DeepSeek-V3's ``scoring_func`` sigmoid
    with ``norm_topk_prob`` and ``routed_scaling_factor``, one group).
    With ``bias`` ([E_all], DeepSeek-V3's ``noaux_tc`` selection bias)
    the experts are the ``top_k`` largest of ``s + bias`` and are still
    weighed by ``s``. All in float32."""
    scores = jax.nn.sigmoid(_router_logits(router_in, w_router))
    if bias is None:
        top, experts = jax.lax.top_k(scores, top_k)
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    weights = jnp.float32(scale) * top / jnp.sum(top, axis=-1,
                                                 keepdims=True)
    return experts.astype(jnp.int32), weights


def _gated(x, w_gate, w_up, w_down, act):
    """``(act(x Wg) * (x Wu)) Wd`` of one expert, plain matmuls."""
    h = (act(x @ w_gate) * (x @ w_up)).astype(x.dtype)
    return jnp.dot(h, w_down, preferred_element_type=jnp.float32)


def dropless_moe(x, router_in, w_router, w_gate, w_up, w_down, *,
                 top_k: int, valid=None, scoring: str = "softmax_top_k",
                 scale: float = 1.0, activation: str = "relu",
                 offset: int = 0, shared=None, token_block: int = 0,
                 bias=None):
    """``sum_{e in C, e held} w_e * E_e(x)`` a token, ``C`` the
    ``top_k`` experts the router chooses among all of them, ``E_e(x) =
    (act(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]``, or with
    ``w_gate=None`` the ungated ``act(x @ w_up[e]) @ w_down[e]``; with
    ``shared`` (the three weights of one more gated expert every token
    goes through) ``+ E_shared(x)``.

    x: [T, H]; router_in: [T, R] (the router may read another tensor,
    of another width, than the experts); w_router: [R, E_all]; w_gate,
    w_up: [E, H, I]; w_down: [E, I, H], the experts ``offset .. offset
    + E - 1`` of ``E_all`` (``E <= E_all``); ``scoring``:
    ``"softmax_top_k"`` (``route_top_k``) or ``"sigmoid_norm"``
    (``route_sigmoid_norm`` with ``scale`` and, where given, the
    selection ``bias`` [E_all]); ``activation``:
    ``"relu"``, ``"silu"`` or ``"relu2"`` (``relu(x)^2``);
    ``valid``: [T] bool or None: a row that is padding or a dead lane
    goes to no expert, touches none and gets zeros. With
    ``token_block`` a call of more rows than that is computed a block
    of rows at a time (``jax.lax.map``), so that what it keeps beside
    its result is a block's: the sorted copy of the rows and the
    experts' activations are ``top_k`` times the rows wide.

    Returns ``(out [T, H] in x's type, stats)`` with ``stats`` whole
    numbers of this call: ``assignments`` (valid rows x top_k, wherever
    their experts live), ``local_assignments`` (those of them the
    experts held here computed), ``experts_touched`` (held experts that
    got a row) and ``max_expert_load`` (rows of the fullest of them);
    a share's also ``narrow_calls`` (1 where the products were handed
    ``cap`` rows; with ``token_block``, in every block) and
    ``wide_calls`` (1 where they were handed all of them).
    """
    t, hidden = x.shape
    n_held, n_all = w_up.shape[0], w_router.shape[1]
    whole = n_held == n_all and not offset
    if not whole and not 0 <= offset <= n_all - n_held:
        raise ValueError(f"experts {offset}..{offset + n_held - 1} are not "
                         f"among the router's {n_all}")
    if scoring not in ("softmax_top_k", "sigmoid_norm"):
        raise ValueError(f"unknown scoring {scoring!r}")
    if bias is not None and scoring != "sigmoid_norm":
        raise ValueError("a selection bias chooses among sigmoid scores")
    if activation not in _ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    act = _ACTIVATIONS[activation]

    def block(x, router_in, valid):
        """``(out [t, H], group_sizes [E], narrow)`` of ``t`` rows:
        ``narrow`` (a share's) is 1 where the grouped products were
        handed the first ``cap`` sorted rows alone, else 0."""
        t = x.shape[0]
        n = t * top_k
        # a share's capacity: twice the rows even routing gives its
        # experts, in whole tiles of 128 rows
        cap = n if whole else min(
            n, -(-2 * n * n_held // (n_all * 128)) * 128)
        with jax.named_scope("moe"):
            with jax.named_scope("route"):
                if scoring == "softmax_top_k":
                    experts, weights = route_top_k(router_in, w_router,
                                                   top_k)
                else:
                    experts, weights = route_sigmoid_norm(
                        router_in, w_router, top_k, scale, bias)
                if not whole:
                    # an expert that lives elsewhere: past every group
                    experts = jnp.where(
                        (experts >= offset) & (experts < offset + n_held),
                        experts - offset, n_held)
                if valid is not None:
                    # past every expert: sorted last, counted in no group
                    experts = jnp.where(valid[:, None], experts, n_held)
            with jax.named_scope("dispatch"):
                flat = experts.reshape(n)
                order = jnp.argsort(flat, stable=True)
                if cap == n:
                    rows = x[order // top_k]                   # [T*k, H]
                group_sizes = jnp.bincount(
                    flat, length=n_held + 1)[:n_held].astype(jnp.int32)

        def weighted_sum(rows):
            """The sorted ``rows`` (the first of them) through their
            experts, put back in token order, weighted and summed."""
            given = rows.shape[0]
            with jax.named_scope("moe"):
                with jax.named_scope("experts"):
                    if w_gate is None:
                        act_rows = act(jax.lax.ragged_dot(
                            rows, w_up, group_sizes)).astype(x.dtype)
                    else:
                        gate = jax.lax.ragged_dot(rows, w_gate, group_sizes)
                        up = jax.lax.ragged_dot(rows, w_up, group_sizes)
                        act_rows = (act(gate) * up).astype(x.dtype)
                    down = jax.lax.ragged_dot(act_rows, w_down, group_sizes)
                with jax.named_scope("combine"):
                    # back to token order: row i of the sorted list is
                    # assignment order[i]
                    back = jnp.zeros_like(order).at[order].set(
                        jnp.arange(n, dtype=order.dtype))
                    if given < n:
                        # a row not handed in lies past the last group
                        back = jnp.minimum(back, given - 1)
                    per = down[back].astype(jnp.float32).reshape(
                        t, top_k, hidden)
                    w = weights
                    if not whole:
                        # rows past the last group are not computed:
                        # those of absent experts, and every invalid
                        # row's
                        here = experts < n_held
                        w = jnp.where(here, w, 0.0)
                        per = jnp.where(here[:, :, None], per, 0.0)
                    elif valid is not None:
                        w = jnp.where(valid[:, None], w, 0.0)
                        per = jnp.where(valid[:, None, None], per, 0.0)
                    out = jnp.sum(per * w[:, :, None], axis=1)
                    if shared is None:
                        out = out.astype(x.dtype)
            return out

        def handed(m):
            """The first ``m`` sorted rows through ``weighted_sum``."""
            def run():
                with jax.named_scope("moe"), jax.named_scope("dispatch"):
                    rows = jnp.asarray(x)[order[:m] // top_k]
                return weighted_sum(rows)
            return run

        if cap == n:
            out = weighted_sum(rows)
            narrow = None if whole else jnp.int32(0)
        else:
            # the sort puts every row a held expert computes first, so
            # where they are no more than ``cap`` the products need see
            # no other; else all of them, and the layer stays dropless
            fits = jnp.sum(group_sizes) <= cap
            out = jax.lax.cond(fits, handed(cap), handed(n))
            narrow = fits.astype(jnp.int32)
        if shared is not None:
            with jax.named_scope("moe"), jax.named_scope("shared"):
                # whole on every chip of the deployment: counted once
                # beside the routed sum of all the shares. The sum and
                # its rounding are in the scope too: a fusion is named
                # for its root
                every = _gated(x, *shared, act)
                if valid is not None:
                    every = jnp.where(valid[:, None], every, 0.0)
                out = (out + every).astype(x.dtype)
        return out, group_sizes, narrow

    if token_block and t > token_block:
        n = -(-t // token_block)
        live = jnp.ones((t,), bool) if valid is None else valid

        def blocks(a):
            a = jnp.pad(a, [(0, n * token_block - t)] + [(0, 0)] * (a.ndim - 1))
            return a.reshape(n, token_block, *a.shape[1:])

        out, sizes, narrow = jax.lax.map(
            lambda a: block(*a), (blocks(x), blocks(router_in), blocks(live)))
        out = out.reshape(n * token_block, hidden)[:t]
        group_sizes = jnp.sum(sizes, axis=0)
        if narrow is not None:
            narrow = jnp.min(narrow)
    else:
        out, group_sizes, narrow = block(x, router_in, valid)
    with jax.named_scope("moe"):
        local = jnp.sum(group_sizes)
        stats = {"assignments": local if whole else top_k * (
                     t if valid is None else jnp.sum(valid, dtype=jnp.int32)),
                 "local_assignments": local,
                 "experts_touched": jnp.sum(group_sizes > 0),
                 "max_expert_load": jnp.max(group_sizes)}
        if not whole:
            stats["narrow_calls"] = narrow
            stats["wide_calls"] = 1 - narrow
    return out, stats
