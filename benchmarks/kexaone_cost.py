"""Operations and bytes one decode step needs of a decoder whose layers
are of several kinds and whose expert layers hold a *share* of their
experts (``benchmarks/configs/k-exaone-236b-a23b.json``), from the
traffic alone. The yardsticks of ``moe_experts_roofline.reasoning`` and
``decode_step_mfu_pct.reasoning``. ``moe_cost.py`` counts a model with
experts in every layer, none shared and all held; this file counts by
layer kind and by what is held.

In one step a live lane's token is routed to ``top_k`` of the router's
experts in each expert layer (an *assignment*); those to experts held
here are *local*, and a held expert that got at least one is *touched*:

- read: each touched expert's three projections once, ``3 * hidden *
  inter`` elements of the weights' type; the assignments' activations
  are a few KiB beside it and are left out;
- operations: ``2 * 3 * hidden * inter`` a local assignment (gate, up
  and down products). An assignment to an expert that lives on another
  chip needs nothing here.

The whole step adds what every token needs whatever its routing: every
other weight read once (attention's four projections and its q and k
norms, two norms a layer, the router at its full width and the shared
expert of each expert layer, the gated MLP of each dense layer, the
final norm and the head over the vocabulary rows held; of the embedding
only a row a lane) with 2 operations a weight element a lane, and the
cache (``window_paged_cost.py``). What an implementation moves beyond
this (an expert read that got no token, rows of absent experts carried
through the sort) is not needed by the traffic and not counted: it
lowers the share, as it should.
"""
from __future__ import annotations


def expert_elems(cfg) -> int:
    """Weight elements of one routed expert: gate, up and down."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def expert_layers(cfg) -> int:
    return sum(1 for i in range(cfg.num_layers) if cfg.layer_experts(i))


def experts_step_cost(cfg, *, experts_touched: float,
                      local_assignments: float, elem_bytes: float) -> dict:
    """FLOPs and HBM bytes of the held experts' products of one decode
    step in which ``experts_touched`` held experts (summed over layers)
    got a token and ``local_assignments`` assignments were theirs."""
    return {"flops": 2.0 * expert_elems(cfg) * local_assignments,
            "bytes": float(expert_elems(cfg)) * elem_bytes
            * experts_touched}


def dense_elems(cfg) -> int:
    """Weight elements every decode step reads whatever its routing."""
    h = cfg.hidden_size
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    attention = h * (qd + 2 * kvd) + qd * h + 2 * h \
        + (2 * cfg.head_dim if cfg.qk_norm else 0)
    sparse = h * cfg.moe_router_experts \
        + 3 * h * cfg.moe_shared_intermediate_size
    dense = 3 * h * cfg.intermediate_size
    n_sparse = expert_layers(cfg)
    return cfg.num_layers * attention + n_sparse * sparse \
        + (cfg.num_layers - n_sparse) * dense + h + h * cfg.vocab_size


def decode_step_cost(cfg, *, experts_touched: float,
                     local_assignments: float, lanes: float,
                     elem_bytes: float, attention: dict) -> dict:
    """FLOPs and HBM bytes of one whole decode step: the held experts,
    every other weight once (and an embedding row a lane), and the
    cache as ``attention`` (``window_paged_cost.paged_decode_step_cost``)
    counts it."""
    experts = experts_step_cost(
        cfg, experts_touched=experts_touched,
        local_assignments=local_assignments, elem_bytes=elem_bytes)
    dense = dense_elems(cfg)
    return {
        "flops": experts["flops"] + 2.0 * dense * lanes
        + attention["flops"],
        "bytes": experts["bytes"] + elem_bytes * (
            dense + lanes * cfg.hidden_size) + attention["bytes"],
    }


# ------------------------------------- a run's numbers for the above
def local_share(run: dict):
    """``local_assignments / assignments`` of the window's programs
    (``engine.moe``), or None where the program counts no such thing."""
    from . import program_spans
    moe = (program_spans.engine_window(run) or {}).get("moe") or {}
    if not moe.get("assignments") or "local_assignments" not in moe:
        return None
    return moe["local_assignments"] / moe["assignments"]


def traced_step(run: dict):
    """What the mean traced decode step had, for the cost functions:
    lanes, held experts touched (the steps' ``engine::decode_call``
    spans) and local assignments (the lanes' assignments times the
    window's local share: a step's span does not carry them). None
    where the trace or the counters have nothing to read."""
    from . import decode_scopes
    s, share = decode_scopes.of(run), local_share(run)
    cfg = run["model_cfg"]
    if not s or share is None or not hasattr(cfg, "layer_experts"):
        return None
    lanes = s["active"] / s["steps"]
    return {"lanes": lanes,
            "experts_touched": s["experts_touched"] / s["steps"],
            "local_assignments": share * lanes * cfg.moe_top_k
            * expert_layers(cfg),
            "elem_bytes": decode_scopes._elem_bytes(run)}
