"""The whole decode program's share of its roofline where the expert layers
hold a share of their experts: what one step needs
(``kexaone_cost.decode_step_cost``: the touched held experts once, every
other weight once (attention, q and k norms, router, shared expert, the
dense layer's MLP, final norm, head, an embedding row a lane), the cache as
``window_paged_cost.py`` counts it, against its operations; the larger of
bytes over the HBM peak and operations over the bf16 peak) over the mean
device time of the decode programs that ran whole in the traced window. The
name carries ``mfu`` because it is the cell's share of the whole step; a
decode step is bound by bytes."""
from benchmarks import decode_scopes, kexaone_cost

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    s = decode_scopes.of(run)
    attention = decode_scopes.attention_cost(run)
    step = kexaone_cost.traced_step(run)
    if not s or attention is None or step is None or not s["device_s"]:
        return None
    cost = kexaone_cost.decode_step_cost(
        run["model_cfg"], attention=attention, **step)
    return decode_scopes._share(run, cost, s["device_s"] / s["steps"])
