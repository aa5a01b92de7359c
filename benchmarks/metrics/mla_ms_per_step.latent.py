"""Summed duration of the ``XLA Ops`` of the latent attention's own
products (scope ``mla``: the query bottleneck and its up-projection, the
latent's projection and norm, the queries' absorption into the latent,
the lift of the attended latents through each head's value projection,
the output projection) inside the decode programs that ran whole in the
traced window under an ``engine::decode_call`` span, over their count
(``latent_scopes.py``). The cache's write and the attention over it are
``paged_attention``'s, not counted here."""
from benchmarks import latent_scopes

LAYER = 'model (models/gpt.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return latent_scopes.scope_ms_per_step(run, "mla")
