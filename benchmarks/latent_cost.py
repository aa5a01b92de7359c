"""Operations and bytes one decode step needs of a decoder whose layers
attend through a latent cache and whose expert layers hold a *share* of
their experts (``benchmarks/configs/glm-4.7-flash.json``), from the
traffic alone. The yardsticks of ``latent_attn_roofline.latent`` and
``decode_step_mfu_pct.latent``. ``kexaone_cost.py`` counts K and V a
head in the cache; this file counts the latent row in their place.

In one step a live lane's token, in each layer:

- reads every cached position's row once, ``W = kv_lora_rank +
  qk_rope_head_dim`` elements of the pool's type, for all its heads,
  and writes its own row; every head scores its absorbed query (``W``
  wide) against a row and sums the rows' latents (``kv_lora_rank``
  wide): ``2 * heads * (W + kv_lora_rank)`` operations a position. At
  20 heads that is 37.8 operations a byte of a bfloat16 pool against
  the v5e's 240: memory-bound. What an implementation reads beyond the
  row (a row laid out in whole lane tiles: 640 lanes for 576 values)
  is not needed by the traffic and not counted;
- is routed to ``top_k`` of the router's experts in each expert layer,
  the touched held experts read once and the local assignments computed
  (``kexaone_cost.experts_step_cost``).

The whole step adds what every token needs whatever its routing: every
other weight read once (the latent attention's projections and norms,
two norms a layer, the router and its selection bias at their full
width, the shared experts, the dense layer's MLP, the final norm and
the head over the vocabulary rows held; of the embedding only a row a
lane) with 2 operations a weight element a lane. The absorbed form
reads each layer's ``W_kvb`` twice over (the key part to absorb the
queries, the value part to lift the sums): once in all, as counted.
"""
from __future__ import annotations


def attention_step_cost(cfg, *, context_tokens: float, lanes: float,
                        elem_bytes: float) -> dict:
    """FLOPs and HBM bytes of the latent attention over the cache in one
    decode step: ``context_tokens`` cached positions summed over the
    live lanes (the token just written among them), ``lanes`` rows
    written, every layer."""
    width, latent = cfg.latent_width, cfg.kv_lora_rank
    layers = cfg.num_layers
    return {
        "flops": 2.0 * cfg.num_heads * (width + latent) * context_tokens
        * layers,
        "bytes": float(width) * elem_bytes * (context_tokens + lanes)
        * layers,
    }


def dense_elems(cfg) -> int:
    """Weight elements every decode step reads whatever its routing:
    all but the held experts and the embedding."""
    from . import kexaone_cost
    held = kexaone_cost.expert_layers(cfg) * cfg.moe_num_experts \
        * kexaone_cost.expert_elems(cfg)
    return cfg.num_params() - held - cfg.vocab_size * cfg.hidden_size


def decode_step_cost(cfg, *, experts_touched: float,
                     local_assignments: float, lanes: float,
                     context_tokens: float, elem_bytes: float) -> dict:
    """FLOPs and HBM bytes of one whole decode step: the held experts,
    the latent cache, every other weight once (and an embedding row a
    lane)."""
    from . import kexaone_cost
    parts = [
        kexaone_cost.experts_step_cost(
            cfg, experts_touched=experts_touched,
            local_assignments=local_assignments, elem_bytes=elem_bytes),
        attention_step_cost(cfg, context_tokens=context_tokens, lanes=lanes,
                            elem_bytes=elem_bytes)]
    dense = dense_elems(cfg)
    return {
        "flops": sum(p["flops"] for p in parts) + 2.0 * dense * lanes,
        "bytes": sum(p["bytes"] for p in parts) + elem_bytes * (
            dense + lanes * cfg.hidden_size)}


# ------------------------------------- a run's numbers for the above
def traced_step(run: dict):
    """What the mean traced decode step had, for the cost functions:
    lanes, cached positions and held experts touched (the steps'
    ``engine::decode_call`` spans) and local assignments (the lanes'
    assignments times the window's local share: a step's span does not
    carry them). None where the trace or the counters have nothing to
    read, or the configuration has no latent attention."""
    from . import decode_scopes, kexaone_cost, latent_scopes
    s, share = latent_scopes.of(run), kexaone_cost.local_share(run)
    cfg = run["model_cfg"]
    if not s or share is None or not getattr(cfg, "kv_lora_rank", 0):
        return None
    lanes = s["active"] / s["steps"]
    return {"lanes": lanes,
            "context_tokens": s["context_tokens"] / s["steps"],
            "experts_touched": s["experts_touched"] / s["steps"],
            "local_assignments": share * lanes * cfg.moe_top_k
            * kexaone_cost.expert_layers(cfg),
            "elem_bytes": decode_scopes._elem_bytes(run)}
