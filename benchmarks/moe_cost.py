"""Operations and bytes a dropless top-k expert layer needs for one
decode step, and what the whole decode step of a model with such
layers needs, from the traffic alone. The yardsticks of
``moe_experts_roofline.*`` and ``decode_step_mfu_pct.*``.

The work is the traffic's, whatever implements it. In one step a live
lane's token goes to ``top_k`` experts in each layer (an *assignment*),
and an expert that got at least one is *touched*:

- read: each touched expert's three projections once,
  ``3 * hidden * inter`` elements of the weights' type (a step of 32
  lanes touches most of 64 experts a layer, so this is most of a step's
  bytes); the assignments' activations are a few KiB beside it and are
  left out;
- operations: ``2 * 3 * hidden * inter`` an assignment (gate, up and
  down products).

The whole step adds what every token needs whatever its routing: every
other weight read once (the attention projections, the routers and the
norms of every layer, the final norm and the untied head; of the
embedding only a row a lane) with ``2`` operations a weight element a
lane, and the cache (``window_paged_cost.py``). What an implementation
moves beyond this (an expert read that got no token, a second pass over
the rows) is not needed by the traffic and not counted: it lowers the
share, as it should.
"""
from __future__ import annotations


def expert_elems(cfg) -> int:
    """Weight elements of one expert: gate, up and down projections."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def experts_step_cost(cfg, *, experts_touched: float, lanes: float,
                      elem_bytes: float) -> dict:
    """FLOPs and HBM bytes of the expert products of one decode step in
    which ``experts_touched`` experts (summed over layers) got a token
    and ``lanes`` lanes are live."""
    assignments = lanes * cfg.moe_top_k * cfg.num_layers
    return {"flops": 2.0 * expert_elems(cfg) * assignments,
            "bytes": float(expert_elems(cfg)) * elem_bytes
            * experts_touched}


def dense_elems(cfg) -> int:
    """Weight elements every decode step reads whatever its routing:
    q/k/v/out projections, router and two norms a layer, the final norm
    and the head."""
    h = cfg.hidden_size
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    layer = h * (qd + 2 * kvd) + qd * h + h * cfg.moe_num_experts + 2 * h
    return cfg.num_layers * layer + h + h * cfg.vocab_size


def decode_step_cost(cfg, *, experts_touched: float, lanes: float,
                     elem_bytes: float, attention: dict) -> dict:
    """FLOPs and HBM bytes of one whole decode step: the experts, every
    other weight once (and an embedding row a lane), and the cache as
    ``attention`` (``window_paged_cost.paged_decode_step_cost``) counts
    it."""
    experts = experts_step_cost(cfg, experts_touched=experts_touched,
                                lanes=lanes, elem_bytes=elem_bytes)
    dense = dense_elems(cfg)
    return {
        "flops": experts["flops"] + 2.0 * dense * lanes
        + attention["flops"],
        "bytes": experts["bytes"] + elem_bytes * (
            dense + lanes * cfg.hidden_size) + attention["bytes"],
    }
