"""ProgramRunner: the one place a decoder program meets its KV pools.

``GenerationServer`` runs every program of its target decoder, and of
its draft decoder under speculation, through an instance of this
class: what a run hands back, when the pools are replaced and when the
output is fetched are decided here and nowhere else.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

__all__ = ["ProgramRunner", "SITES"]

# a kind of program (the name of its ``CachedDecoder`` entry point) to
# the site it compiles under, which is also its warmup-manifest site
SITES = {"prefill": "generate_prefill",
         "prefill_chunked": "generate_chunked",
         "decode": "generate_decode",
         "verify": "generate_verify"}


class ProgramRunner:
    """A ``CachedDecoder`` and the K/V pools its programs read and
    write. ``pools`` is whatever holds them as ``.k`` and ``.v``: the
    engine's ``PagedKVCache`` for the target (callers read
    ``srv.kv.k``), a bare namespace for a draft model's."""

    def __init__(self, decoder, pools):
        self.decoder = decoder
        self.pools = pools

    def run(self, kind: str, feeds: Sequence[np.ndarray]
            ) -> Tuple[np.ndarray, bool, List[Tuple[tuple, str]]]:
        """Run ``kind``'s program (a key of ``SITES``) over its numpy
        ``feeds``. Returns ``(out, fresh, signature)``: the program's
        first output on the host, whether the decoder saw this
        signature for the first time, and the feeds' ``(shape,
        dtype)`` list as a warmup manifest records it.

        The entry point is looked up at call time (a test may have
        replaced it). The pools are replaced as soon as the call
        returns: they were donated, so the old ones are gone whether
        or not the fetch below succeeds."""
        pools = self.pools
        out, k, v, fresh = getattr(self.decoder, kind)(
            *feeds, pools.k, pools.v)
        pools.k, pools.v = k, v
        return (np.asarray(out), bool(fresh),
                [(a.shape, str(a.dtype)) for a in feeds])
