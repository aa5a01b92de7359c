"""paddle_tpu.serving.generation — autoregressive decode serving.

The generation analog of the batch-predict ``InferenceServer``:
prefill/decode split over a paged KV cache (PagedAttention layout) with
Orca-style continuous batching — sequences join and leave the in-flight
decode batch every iteration, the decode step compiles ONCE at
``[max_batch, 1]`` with dead lanes slot-masked, and tokens stream back
through ``StreamingFuture``.

Pieces:

- ``GenerationServer`` (engine.py): ``submit_generate(prompt,
  max_new_tokens, temperature) -> StreamingFuture`` with the serving
  layer's backpressure/deadline semantics, continuous batcher worker,
  ``paddle_decode_*`` metrics on the observability registry, warmup +
  warmup-manifest replay over the decode lattice.
- ``CachedDecoder`` (model_fns.py): the jitted device entry points
  over a cache-capable model (bucketed prefill, chunked suffix
  prefill, fixed-shape decode: each chooses its rows' next tokens
  itself, greedy or sampled, and returns ``(tokens, logits, k', v',
  new_signature)``; the speculative verify step returns every window
  position's logits and no tokens), KV pools donated where the backend
  supports it, persistent-compile-cache AOT tier first.
- ``ProgramRunner`` (runner.py): a decoder and its pools; ``enqueue``
  calls a program and reads nothing, ``harvest`` fetches the chosen
  tokens (``[rows]`` int32) and the expert counters in one transfer
  and leaves the logits on the device unless the caller asks for them;
  the engine's loop enqueues decode step i+1 before it harvests step i.
- ``PagedKVCache`` (kv_cache.py): preallocated per-layer
  ``[num_pages, page_size, heads * head_dim]`` pools + the host page
  allocator (page 0 reserved as the trash page for masked writes),
  REFCOUNTED so full pages can be shared across sequences.
- ``PrefixCache`` (prefix_cache.py): radix index over immutable full
  KV pages keyed by token content — shared-prefix reuse with
  copy-on-write at the divergence page and LRU eviction under pool
  pressure.
- ``accept_tokens`` (spec_decode.py): host-side accept-and-resample
  for speculative decoding (draft proposes k, the target verifies all
  k in one fixed-shape step; output distribution unchanged).
- ``sample_next_tokens`` (sampling.py): vectorized host-side
  greedy/temperature selection, the rule the programs implement; the
  engine's loop no longer calls it, ``HybridParallelInferenceHelper.
  generate`` does.

Model contract: ``forward(ids, cache=...)`` returning ``(logits,
(k', v'))`` plus ``init_kv_pools``/``kv_cache_spec`` — implemented by
``models.GPTForCausalLM`` (module and stacked decoders); see
``models.gpt.GPTKVCache`` for the threaded pytree.

Knobs: ``FLAGS_decode_*`` in framework/flags.py.
"""
from __future__ import annotations

from .engine import (DecodeMetrics, GenerationServer, StreamingFuture,
                     engines_statusz)
from .kv_cache import PagedKVCache
from .model_fns import CachedDecoder, supports_cached_decode
from .prefix_cache import PrefixCache
from .sampling import sample_next_tokens
from .spec_decode import accept_tokens

__all__ = [
    "GenerationServer", "StreamingFuture", "DecodeMetrics",
    "PagedKVCache", "PrefixCache", "CachedDecoder",
    "supports_cached_decode", "sample_next_tokens", "accept_tokens",
    "engines_statusz",
]
