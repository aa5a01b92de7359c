"""The whole decode program's share of its roofline where every layer is one
mixer and some keep a recurrent state: what one step needs
(``nemotron_h_cost.decode_step_cost``: the touched held experts once, every
other weight once, each live lane's state and convolution tail read and
written once, the attention layer's cache as ``window_paged_cost.py`` counts a
full layer, an embedding row a lane, against its operations; the larger of
bytes over the HBM peak and operations over the bf16 peak) over the mean
device time of the decode programs that ran whole in the traced window
(``hybrid_scopes.py``). The name carries ``mfu`` because it is the cell's
share of the whole step; a decode step is bound by bytes."""
from benchmarks import decode_scopes, hybrid_scopes, nemotron_h_cost

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    s = hybrid_scopes.of(run)
    step = nemotron_h_cost.traced_step(run)
    if not s or step is None or not s["device_s"]:
        return None
    cost = nemotron_h_cost.decode_step_cost(run["model_cfg"], **step)
    return decode_scopes._share(run, cost, s["device_s"] / s["steps"])
