"""The least time the chip could take for the held experts' products of one
decode step (``kexaone_cost.experts_step_cost``: each touched held expert's
three projections read once, ``experts_touched`` of the steps'
``engine::decode_call`` spans, against 2 * 3 * hidden * inter operations a
*local* assignment; the larger of bytes over the HBM peak and operations
over the bf16 peak) over the time under ``moe/experts`` (the ``ragged-dot*``
calls counted there, ``decode_scopes.py``) in the same steps."""
from benchmarks import decode_scopes, kexaone_cost

LAYER = 'ops (ops/moe.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    ms = decode_scopes.scope_ms_per_step(run, "moe/experts")
    step = kexaone_cost.traced_step(run)
    if not ms or step is None:
        return None
    cost = kexaone_cost.experts_step_cost(
        run["model_cfg"], experts_touched=step["experts_touched"],
        local_assignments=step["local_assignments"],
        elem_bytes=step["elem_bytes"])
    return decode_scopes._share(run, cost, ms / 1e3)
