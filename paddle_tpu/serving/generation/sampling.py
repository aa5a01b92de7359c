"""Host-side token selection over a batch of next-token logits.

One vectorized numpy pass replaces the per-row ``rng.choice`` loop the
old full-window ``generate()`` ran (O(batch) Python iterations and a
vocab-sized probability normalization per row, per token): softmax and
inverse-CDF selection run across the whole batch at once, and rows can
mix greedy (temperature 0) with sampled selection in the same call.

Who calls it: ``HybridParallelInferenceHelper.generate`` (one
``RandomState`` for its whole batch) and the tests, which hold the
programs of ``model_fns.CachedDecoder`` to this rule. The serving
engine does not: its programs choose in place (``model_fns._select``,
the same rule in float32) and its speculative path judges proposals
with ``spec_decode.accept_tokens``.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import numpy as np

__all__ = ["sample_next_tokens"]


def sample_next_tokens(logits: np.ndarray,
                       temperature: Union[float, Sequence[float]],
                       rng: Optional[np.random.RandomState] = None,
                       uniforms: Optional[np.ndarray] = None) -> np.ndarray:
    """Select one token id per row of ``logits`` ``[B, vocab]``.

    ``temperature`` is a scalar or per-row vector; rows at 0 take the
    argmax, rows above 0 sample from ``softmax(logits / t)`` by inverse
    CDF. Randomness comes from ``uniforms`` ``[B]`` in [0, 1) when
    given (one a row, as the engine feeds its programs from each
    request's own RandomState), else from ``rng``. Returns ``[B]``
    int64.
    """
    logits = np.asarray(logits)
    b = logits.shape[0]
    temps = np.broadcast_to(np.asarray(temperature, np.float64),
                            (b,)).copy()
    sampled = temps > 0.0
    if not sampled.any():
        # all greedy: the argmax of the logits as they came (widening a
        # [lanes, vocab] array to float64 first changes no comparison
        # and costs more than the selection)
        return logits.argmax(-1).astype(np.int64)
    logits = logits.astype(np.float64)
    out = logits.argmax(-1).astype(np.int64)
    if uniforms is None:
        if rng is None:
            rng = np.random.RandomState(0)
        uniforms = rng.random_sample(b)
    z = logits[sampled] / temps[sampled, None]
    z -= z.max(-1, keepdims=True)
    p = np.exp(z)
    cdf = np.cumsum(p, -1)
    u = np.asarray(uniforms, np.float64)[sampled] * cdf[:, -1]
    # first index whose cumulative mass exceeds u (strict: u==0 picks
    # the first token with nonzero mass)
    out[sampled] = np.minimum((cdf > u[:, None]).argmax(-1),
                              logits.shape[-1] - 1).astype(np.int64)
    return out
