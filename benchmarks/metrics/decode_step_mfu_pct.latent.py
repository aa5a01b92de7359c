"""The whole decode program's share of its roofline where the layers
attend through a latent cache and the expert layers hold a share of
their experts: what one step needs (``latent_cost.decode_step_cost``: the
touched held experts once, every other weight once, every cached
position's latent row read once a layer and a token's row written, an
embedding row a lane, against its operations; the larger of bytes over
the HBM peak and operations over the bf16 peak) over the mean device time
of the decode programs that ran whole in the traced window
(``latent_scopes.py``). The name carries ``mfu`` because it is the cell's
share of the whole step; a decode step is bound by bytes."""
from benchmarks import decode_scopes, latent_cost, latent_scopes

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    s = latent_scopes.of(run)
    step = latent_cost.traced_step(run)
    if not s or step is None or not s["device_s"]:
        return None
    cost = latent_cost.decode_step_cost(run["model_cfg"], **step)
    return decode_scopes._share(run, cost, s["device_s"] / s["steps"])
