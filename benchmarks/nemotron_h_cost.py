"""Operations and bytes one decode step needs of a decoder whose every
layer is one mixer, of three kinds, one of which keeps a recurrent state
a sequence (``benchmarks/configs/nemotron-3-super-120b-a12b.json``), from
the traffic alone. The yardsticks of ``ssm_state_roofline.hybrid``,
``moe_experts_roofline.hybrid`` and ``decode_step_mfu_pct.hybrid``.
``kexaone_cost.py`` counts gated experts as wide as the model beside
attention in every layer; this file counts ungated experts in a latent,
layers by letter of the pattern, and the state.

In one step a live lane's token

- in each state-space layer reads its slot's state and convolution tail
  and writes both back (``state_bytes_per_slot`` twice: the state in
  float32, the tail in the weights' type), beside the token's own
  inputs and output (``xBC_t``, ``dt_t``, ``y_t``); 5 operations a state
  element (decay, outer product, sum, and the product and sum with
  ``C``). Whatever implements the update, this is what it has to move;
- in each expert layer is routed to ``top_k`` of the router's experts
  (an *assignment*); those to experts held here are *local*, and a held
  expert that got at least one is *touched*: each touched expert's two
  projections are read once, ``2 * latent * inter`` elements, against
  ``2 * 2 * latent * inter`` operations a local assignment;
- in the attention layer reads the lane's cached context
  (``window_paged_cost.py``, one full layer, no window layers).

The whole step adds what every token needs whatever its routing: every
other weight read once (the state-space projections, convolutions, and
norms, attention's four projections, the routers at their full width,
the latent projections and the shared experts, the final norm and the
head over the vocabulary rows held; of the embedding only a row a lane)
with 2 operations a weight element a lane.
"""
from __future__ import annotations

STATE_OPS_PER_ELEMENT = 5.0


def layers_of(cfg, kind: str) -> int:
    return len(cfg.layers_of(kind))


def expert_elems(cfg) -> int:
    """Weight elements of one routed expert: up and down, in the
    latent."""
    return 2 * cfg.moe_latent_size * cfg.moe_intermediate_size


def experts_step_cost(cfg, *, experts_touched: float,
                      local_assignments: float, elem_bytes: float) -> dict:
    """FLOPs and HBM bytes of the held experts' products of one decode
    step in which ``experts_touched`` held experts (summed over layers)
    got a token and ``local_assignments`` assignments were theirs."""
    return {"flops": 2.0 * expert_elems(cfg) * local_assignments,
            "bytes": float(expert_elems(cfg)) * elem_bytes
            * experts_touched}


def state_step_cost(cfg, *, state_slots_live: float,
                    elem_bytes: float) -> dict:
    """FLOPs and HBM bytes of the state updates of one decode step over
    ``state_slots_live`` live lanes: every state-space layer's slot read
    and written once, and the token's inputs and output."""
    state_elems = cfg.mamba_inner * cfg.ssm_state_size
    token = (cfg.conv_channels + cfg.mamba_num_heads
             + cfg.mamba_inner) * elem_bytes
    lane_layers = state_slots_live * layers_of(cfg, "M")
    return {
        "flops": STATE_OPS_PER_ELEMENT * state_elems * lane_layers,
        "bytes": lane_layers * (
            2.0 * cfg.state_bytes_per_slot(int(elem_bytes)) + token)}


def dense_elems(cfg) -> int:
    """Weight elements every decode step reads whatever its routing:
    all but the held experts and the embedding."""
    held = layers_of(cfg, "E") * cfg.moe_num_experts * expert_elems(cfg)
    return cfg.num_params() - held - cfg.vocab_size * cfg.hidden_size


def attention_cost(cfg, *, context_tokens: float, lanes: float,
                   elem_bytes: float) -> dict:
    from . import window_paged_cost
    return window_paged_cost.paged_decode_step_cost(
        context_tokens=context_tokens, window_context_tokens=0.0,
        lanes=lanes, full_layers=layers_of(cfg, "*"), window_layers=0,
        heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, elem_bytes=elem_bytes)


def decode_step_cost(cfg, *, experts_touched: float,
                     local_assignments: float, lanes: float,
                     state_slots_live: float, context_tokens: float,
                     elem_bytes: float) -> dict:
    """FLOPs and HBM bytes of one whole decode step: the held experts,
    the states, every other weight once (and an embedding row a lane)
    and the attention layer's cache."""
    parts = [
        experts_step_cost(cfg, experts_touched=experts_touched,
                          local_assignments=local_assignments,
                          elem_bytes=elem_bytes),
        state_step_cost(cfg, state_slots_live=state_slots_live,
                        elem_bytes=elem_bytes),
        attention_cost(cfg, context_tokens=context_tokens, lanes=lanes,
                       elem_bytes=elem_bytes)]
    dense = dense_elems(cfg)
    return {
        "flops": sum(p["flops"] for p in parts) + 2.0 * dense * lanes,
        "bytes": sum(p["bytes"] for p in parts) + elem_bytes * (
            dense + lanes * cfg.hidden_size)}


# ------------------------------------- a run's numbers for the above
def traced_step(run: dict):
    """What the mean traced decode step had, for the cost functions:
    lanes, live state slots, cached positions and held experts touched
    (the steps' ``engine::decode_call`` spans) and local assignments
    (the lanes' assignments times the window's local share: a step's
    span does not carry them). None where the trace or the counters
    have nothing to read."""
    from . import decode_scopes, hybrid_scopes, kexaone_cost
    s, share = hybrid_scopes.of(run), kexaone_cost.local_share(run)
    cfg = run["model_cfg"]
    if not s or share is None or not hasattr(cfg, "layers_of"):
        return None
    lanes = s["active"] / s["steps"]
    return {"lanes": lanes,
            "state_slots_live": s["state_slots_live"] / s["steps"],
            "context_tokens": s["context_tokens"] / s["steps"],
            "experts_touched": s["experts_touched"] / s["steps"],
            "local_assignments": share * lanes * cfg.moe_top_k
            * layers_of(cfg, "E"),
            "elem_bytes": decode_scopes._elem_bytes(run)}
