"""The decode step's operations at the bf16 peak: what the mean traced step
computes (``retention_cost.step_flops``: every weight but the embedding
twice a lane, the head over the vocabulary rows held among them, and the
retention's state reads and updates) over the mean device time of the decode
programs that ran whole in the traced window, as a share of the chip's bf16
operations a second. A decode step is bound by bytes (the weights and the
states), so this reads low: ``retention_state_roofline.retention`` says how
near the state traffic runs to the HBM's rate."""
from benchmarks import common, retention_cost

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    s = retention_cost.summary(run)
    if not s or not s["device_s"]:
        return None
    peak = common.chip_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * s["cost"]["step_flops"] / peak \
        / (s["device_s"] / s["steps"])
