"""Operations and bytes one decode step needs of a decoder whose every
layer is power retention of degree 2 (``benchmarks/configs/
brumby-14b-base.json``), from the traffic alone; and the scopes and span
arguments its readers hand ``scoped_steps.summarize``. The yardsticks of
``retention_state_roofline.retention`` and
``decode_step_mfu_pct.retention``.

In one step a live lane's token, in each layer, reads its slot's state
(``S [Hk, D, d]`` and ``z [Hk, D]``) and writes both back: the bytes a
slot holds twice, whatever implements the update. Against them, a K/V
head of ``G`` query heads computes ``G`` reads of ``S`` and ``z`` (``2 G``
operations an element) and the update (the decay, the outer product and
the sum: 3 an element).

The whole step's operations are every weight's product a lane (2 a
weight element: all but the embedding, whose row is read; the head over
the vocabulary rows held among them) and the retention's above.
"""
from __future__ import annotations

SCOPES = ("retention", "retention/state_update")
SPAN_ARGS = ("active", "context_tokens", "state_slots_live")
UPDATE_OPS_PER_ELEMENT = 3.0


def state_bytes_per_slot(run: dict) -> float:
    """Device bytes of every layer's state a sequence holds, from the
    arrays (``engine.kv.pool_bytes["state"]`` over the slots, the trash
    slot among them): the pools' precision is read, not assumed."""
    kv = run["snap1"].get("engine", {}).get("kv") or {}
    held = kv.get("pool_bytes", {}).get("state")
    slots = kv.get("capacity", {}).get("state")
    return float(held) / (slots + 1) if held and slots else 0.0


def retention_flops(cfg, *, state_slots_live: float) -> float:
    """Operations of the state reads and updates of one step."""
    group = cfg.num_heads // cfg.num_kv_heads
    elems = cfg.num_kv_heads * cfg.retention_state_dim * (cfg.head_dim + 1)
    return (2.0 * group + UPDATE_OPS_PER_ELEMENT) * elems \
        * cfg.num_layers * state_slots_live


def state_step_cost(cfg, *, state_slots_live: float,
                    bytes_per_slot: float) -> dict:
    """FLOPs and HBM bytes of the state traffic of one decode step over
    ``state_slots_live`` live lanes: each slot read and written once."""
    return {"flops": retention_flops(cfg, state_slots_live=state_slots_live),
            "bytes": 2.0 * bytes_per_slot * state_slots_live}


def step_flops(cfg, *, lanes: float, state_slots_live: float) -> float:
    """Operations of one whole decode step: every weight but the
    embedding twice a lane, and the retention's."""
    weights = cfg.num_params() - cfg.vocab_size * cfg.hidden_size
    return 2.0 * weights * lanes + retention_flops(
        cfg, state_slots_live=state_slots_live)


def summary(run: dict):
    """The run's decode steps by ``SCOPES`` (``scoped_steps.of``), with
    the mean step's state traffic and whole-step operations as
    ``cost``."""
    from . import scoped_steps
    cfg = run["model_cfg"]
    if not getattr(cfg, "retention_degree", 0):
        return None
    per_slot = state_bytes_per_slot(run)

    def cost(step):
        return {"state": state_step_cost(
                    cfg, state_slots_live=step["state_slots_live"],
                    bytes_per_slot=per_slot),
                "step_flops": step_flops(
                    cfg, lanes=step["active"],
                    state_slots_live=step["state_slots_live"])}

    return scoped_steps.of(run, "_retention_steps", SCOPES, SPAN_ARGS, cost)
