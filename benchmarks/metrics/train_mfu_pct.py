"""Model FLOP/s utilization: (6N + 12*L*H*S) operations a token times
tokens/s of the window (less the time the traced run's profiler took
to start and stop), over the chip's bf16 peak. Recomputed
operations earn nothing."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'step (jit/train_step.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'host_clock'
MOVES = 'train_tokens_per_s'


def read(run):
    if run["kind"] != "train-steps" or not run["on_chip"]:
        return None
    from benchmarks import flash_cost
    cfg = run["model_cfg"]
    per_token = flash_cost.train_flops_per_token(
        n_params=run["n_params"], layers=cfg.num_layers,
        hidden=cfg.hidden_size, seq=int(run["traffic"]["seq"]))
    # a traced window loses a second or two to starting and stopping
    # the profiler, which is the benchmark's work and not the step's
    rate = len(run["step_s"]) * run["tokens_per_step"] \
        / (run["seconds"] - run["profiler_s"])
    peak = common.chip_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * rate / (peak * run["device"]["count"])
