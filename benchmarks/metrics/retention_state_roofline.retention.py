"""The least time the chip's HBM could take to move the state traffic of one
decode step (``retention_cost.state_step_cost``: every live lane's slot of
every layer, ``S`` and ``z``, read once and written once, ``state_slots_live``
of the steps' ``engine::decode_call`` spans, the bytes a slot counted from
the pools' arrays) over the time under ``retention/state_update`` in the same
steps, in percent. Bytes bind: about 1.6 operations a byte. The work is
counted the same whatever implements the update."""
from benchmarks import common, retention_cost, scoped_steps

LAYER = 'ops (ops/retention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    s = retention_cost.summary(run)
    ms = scoped_steps.ms_per_step(s, "retention/state_update")
    if not ms or not s["cost"]["state"]["bytes"]:
        return None
    peak = common.chip_peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * s["cost"]["state"]["bytes"] / peak / (ms / 1e3)
