"""The least time the chip could take for the held experts' products of one
decode step where the experts are ungated and live in a latent
(``nemotron_h_cost.experts_step_cost``: each touched held expert's two
projections read once, 2 * latent * inter elements, ``experts_touched`` of the
steps' ``engine::decode_call`` spans, against 2 * 2 * latent * inter
operations a *local* assignment; the larger of bytes over the HBM peak and
operations over the bf16 peak) over the time under ``moe/experts`` in the
same steps (``hybrid_scopes.py``; a ``ragged-dot*`` call would be counted
there, but this model's decode step at the cell's lanes computes every
held expert for every lane, ``models/nemotron_h.py:_every_held_expert``, so
it reads all the held experts and the share says how much of that the
traffic needed and how fast it went)."""
from benchmarks import decode_scopes, hybrid_scopes, nemotron_h_cost

LAYER = 'ops (ops/moe.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    ms = hybrid_scopes.scope_ms_per_step(run, "moe/experts")
    step = nemotron_h_cost.traced_step(run)
    if not ms or step is None:
        return None
    cost = nemotron_h_cost.experts_step_cost(
        run["model_cfg"], experts_touched=step["experts_touched"],
        local_assignments=step["local_assignments"],
        elem_bytes=step["elem_bytes"])
    return decode_scopes._share(run, cost, ms / 1e3)
