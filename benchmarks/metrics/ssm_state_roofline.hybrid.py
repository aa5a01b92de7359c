"""The least time the chip could take for the state updates of one decode
step (``nemotron_h_cost.state_step_cost``: every live lane's slot of every
state-space layer, the float32 state and the convolution tail, read once and
written once, and the token's inputs and output, ``state_slots_live`` of the
steps' ``engine::decode_call`` spans; the larger of bytes over the HBM peak
and operations over the bf16 peak) over the time under ``ssm/state_update``
(the pools' reads and writes and the recurrence) in the same steps. The work
is counted the same whatever implements the update."""
from benchmarks import decode_scopes, hybrid_scopes, nemotron_h_cost

LAYER = 'ops (ops/ssm.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    ms = hybrid_scopes.scope_ms_per_step(run, "ssm/state_update")
    step = nemotron_h_cost.traced_step(run)
    if not ms or step is None:
        return None
    cost = nemotron_h_cost.state_step_cost(
        run["model_cfg"], state_slots_live=step["state_slots_live"],
        elem_bytes=step["elem_bytes"])
    return decode_scopes._share(run, cost, ms / 1e3)
