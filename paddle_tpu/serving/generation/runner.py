"""ProgramRunner: the one place a decoder program meets its KV pools.

``GenerationServer`` runs every program of its target decoder, and of
its draft decoder under speculation, through an instance of this
class: what a run hands back, when the pools are replaced and what is
fetched are decided here and nowhere else.

A run is two halves. ``enqueue`` calls the entry point and swaps the
donated pools, and reads nothing from the device: what it returns (an
``Enqueued``) are the program's outputs where they lie, maybe not yet
computed. ``harvest`` is the one ``jax.device_get``: the tokens the
program chose and the expert counters (``CachedDecoder.last_aux``,
taken at the call, so they are this program's whatever was enqueued
since), ``rows * 4`` bytes and a few scalars; the logits come with
them only for a caller that says it needs them on the host
(``host_logits=True``: a verify step, which chooses nothing, and a
draft step with a sampled lane; no traffic of a server without a draft
does). Between the two the caller may enqueue the next program: the
device runs them in the order they were enqueued, each on the pools
its predecessor returned, and an ``Enqueued``'s ``tokens`` are a valid
``decode`` operand before anybody has fetched them. ``run`` is
harvest-of-enqueue, for the callers that need the result before they
can form the next program (a prefill, a verify step, a draft's steps,
a warm-up).

Each half is a ``profiler.RecordEvent`` on the calling thread, so on the
trace's clock: ``runner::enqueue`` round the call and the swap (inside
it the decoder's ``decoder::launch`` round the executable's own call)
and ``runner::harvest`` round the ``device_get``. Their lengths ride on
what each half returns (``enqueue_s``, ``launch_s``, ``harvest_s``) for
the engine to count; the runner keeps no counter.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ...profiler import RecordEvent

__all__ = ["Enqueued", "ProgramRunner", "Run", "SITES"]

# a kind of program (the name of its ``CachedDecoder`` entry point) to
# the site it compiles under, which is also its warmup-manifest site
SITES = {"prefill": "generate_prefill",
         "prefill_chunked": "generate_chunked",
         "decode": "generate_decode",
         "verify": "generate_verify"}


class Enqueued(NamedTuple):
    """What ``ProgramRunner.enqueue`` hands back, all of it still on
    the device and none of it waited for: ``tokens`` the program's
    choice ``[rows]`` int32 (None from a verify step), ``logits``,
    ``aux`` the expert counters as device scalars; and two host facts,
    ``fresh`` (the decoder saw this signature for the first time) and
    ``signature``, the feeds' ``(shape, dtype)`` list as a warmup
    manifest records it; ``enqueue_s`` the host's time in ``enqueue``,
    ``launch_s`` the part of it in the executable's call (the runtime's
    argument handling, transfers and launch)."""
    tokens: object
    logits: object
    aux: dict
    fresh: bool
    signature: List[Tuple[tuple, str]]
    enqueue_s: float
    launch_s: float


class Run(NamedTuple):
    """What ``ProgramRunner.harvest`` hands back: ``tokens`` the
    program's choice ``[rows]`` on the host (None from a verify step),
    ``logits`` its logits (a device array; numpy where the caller asked
    for them on the host), ``aux`` the expert counters as host numbers,
    ``fresh`` and ``signature`` as ``Enqueued`` has them,
    ``fetched_bytes`` what came to the host, ``harvest_s`` the host's
    wait for it."""
    tokens: Optional[np.ndarray]
    logits: object
    aux: dict
    fresh: bool
    signature: List[Tuple[tuple, str]]
    fetched_bytes: int
    harvest_s: float


class ProgramRunner:
    """A ``CachedDecoder`` and the K/V pools its programs read and
    write. ``pools`` is whatever holds them as ``.k`` and ``.v``: the
    engine's ``PagedKVCache`` for the target (callers read
    ``srv.kv.k``), a bare namespace for a draft model's. The pools are
    donated from program to program, so however many programs are
    enqueued and unharvested there is one set of pools alive."""

    def __init__(self, decoder, pools):
        self.decoder = decoder
        self.pools = pools

    def enqueue(self, kind: str, feeds: Sequence) -> Enqueued:
        """Call ``kind``'s program (a key of ``SITES``) over ``feeds``
        (numpy arrays; a decode step's tokens may be a device array)
        and return without reading anything from the device.

        The entry point is looked up at call time (a test may have
        replaced it). The pools are replaced as soon as the call
        returns: they were donated, so the old ones are gone whether
        or not the program, or its harvest, succeeds."""
        pools, span = self.pools, RecordEvent("runner::enqueue")
        with span:
            *chosen, logits, k, v, fresh = getattr(self.decoder, kind)(
                *feeds, pools.k, pools.v)
            pools.k, pools.v = k, v
            signature = [(tuple(a.shape), str(a.dtype)) for a in feeds]
        return Enqueued(chosen[0] if chosen else None, logits,
                        self.decoder.last_aux, bool(fresh), signature,
                        span.elapsed_s, self.decoder.last_launch_s)

    def harvest(self, enqueued: Enqueued,
                host_logits: bool = False) -> Run:
        """Fetch what an enqueued program chose: the one read of the
        device, which waits for the program (and for every program
        enqueued before it)."""
        import jax
        want = {"tokens": enqueued.tokens, "aux": enqueued.aux}
        if host_logits:
            want["logits"] = enqueued.logits
        span = RecordEvent("runner::harvest")
        with span:
            got = jax.device_get(want)
        return Run(got["tokens"], got.get("logits", enqueued.logits),
                   got["aux"], enqueued.fresh, enqueued.signature,
                   sum(a.nbytes for a in jax.tree_util.tree_leaves(got)),
                   span.elapsed_s)

    def run(self, kind: str, feeds: Sequence,
            host_logits: bool = False) -> Run:
        """Enqueue ``kind``'s program and harvest it at once."""
        return self.harvest(self.enqueue(kind, feeds), host_logits)
