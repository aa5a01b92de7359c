"""The least time the chip could take for the latent attention over the
cache of one decode step (``latent_cost.attention_step_cost``: every
cached position's 576-value row read once a layer and a token's row
written a lane a layer, against 2 * 20 * (576 + 512) operations a
position a layer; the larger of bytes over the HBM peak and operations
over the bf16 peak: bytes) over the time under ``paged_attention`` (the
write and the kernel) in the same steps. The work is counted the same
whatever implements it."""
from benchmarks import decode_scopes, latent_cost, latent_scopes

LAYER = 'ops (ops/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    ms = latent_scopes.scope_ms_per_step(run, "paged_attention")
    step = latent_cost.traced_step(run)
    if not ms or step is None:
        return None
    cost = latent_cost.attention_step_cost(
        run["model_cfg"], context_tokens=step["context_tokens"],
        lanes=step["lanes"], elem_bytes=step["elem_bytes"])
    return decode_scopes._share(run, cost, ms / 1e3)
