"""ProgramRunner: the one place a decoder program meets its KV pools.

``GenerationServer`` runs every program of its target decoder, and of
its draft decoder under speculation, through an instance of this
class: what a run hands back, when the pools are replaced and what is
fetched are decided here and nowhere else.

A run hands back the tokens the program chose, on the host, and the
program's logits where they are, on the device. One ``jax.device_get``
brings the tokens and the expert counters (``CachedDecoder.last_aux``):
``rows * 4`` bytes and a few scalars. The logits come with them only
for a caller that says it needs them there (``host_logits=True``: a
verify step, which chooses nothing, and a draft step with a sampled
lane; no traffic of a server without a draft does).
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

__all__ = ["ProgramRunner", "Run", "SITES"]

# a kind of program (the name of its ``CachedDecoder`` entry point) to
# the site it compiles under, which is also its warmup-manifest site
SITES = {"prefill": "generate_prefill",
         "prefill_chunked": "generate_chunked",
         "decode": "generate_decode",
         "verify": "generate_verify"}


class Run(NamedTuple):
    """What ``ProgramRunner.run`` hands back: ``tokens`` the program's
    choice ``[rows]`` on the host (None from a verify step), ``logits``
    its logits (a device array; numpy where the caller asked for them
    on the host), ``aux`` the expert counters as host numbers,
    ``fresh`` whether the decoder saw this signature for the first
    time, ``signature`` the feeds' ``(shape, dtype)`` list as a warmup
    manifest records it, ``fetched_bytes`` what came to the host."""
    tokens: Optional[np.ndarray]
    logits: object
    aux: dict
    fresh: bool
    signature: List[Tuple[tuple, str]]
    fetched_bytes: int


class ProgramRunner:
    """A ``CachedDecoder`` and the K/V pools its programs read and
    write. ``pools`` is whatever holds them as ``.k`` and ``.v``: the
    engine's ``PagedKVCache`` for the target (callers read
    ``srv.kv.k``), a bare namespace for a draft model's."""

    def __init__(self, decoder, pools):
        self.decoder = decoder
        self.pools = pools

    def run(self, kind: str, feeds: Sequence[np.ndarray],
            host_logits: bool = False) -> Run:
        """Run ``kind``'s program (a key of ``SITES``) over its numpy
        ``feeds`` and fetch what it chose.

        The entry point is looked up at call time (a test may have
        replaced it). The pools are replaced as soon as the call
        returns: they were donated, so the old ones are gone whether
        or not the fetch below succeeds."""
        import jax
        pools = self.pools
        *chosen, logits, k, v, fresh = getattr(self.decoder, kind)(
            *feeds, pools.k, pools.v)
        pools.k, pools.v = k, v
        want = {"tokens": chosen[0] if chosen else None,
                "aux": self.decoder.last_aux}
        if host_logits:
            want["logits"] = logits
        got = jax.device_get(want)
        return Run(got["tokens"], got.get("logits", logits), got["aux"],
                   bool(fresh), [(a.shape, str(a.dtype)) for a in feeds],
                   sum(a.nbytes for a in jax.tree_util.tree_leaves(got)))
