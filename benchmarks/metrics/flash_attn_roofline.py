"""The least time the chip could take for the step's flash calls (the
larger of operations over the bf16 peak and bytes over the HBM peak,
``flash_cost.py``) over the time they took. At b=2 s=2048 d=128 the
calls are compute-bound: operations over peak is the bound."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'ops (ops/pallas_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def read(run):
    if "trace" not in run:
        return None
    from benchmarks import flash_cost
    cfg, traffic = run["model_cfg"], run["traffic"]
    cost = flash_cost.flash_step_cost(
        batch=int(traffic["batch"]), heads=cfg.num_heads,
        seq=int(traffic["seq"]), head_dim=cfg.hidden_size // cfg.num_heads,
        layers=cfg.num_layers,
        forward_calls=2 if cfg.recompute == "full" else 1)
    least = flash_cost.roofline(cost, common.chip_peaks(run["device"]["kind"]))
    per_step = readers.mosaic_seconds(run) / run["trace_steps"]
    return 100.0 * least["min_seconds"] / per_step if per_step else None
