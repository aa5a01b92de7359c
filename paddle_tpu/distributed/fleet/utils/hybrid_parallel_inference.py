"""Hybrid-parallel autoregressive inference helper.

Reference: python/paddle/distributed/fleet/utils/hybrid_parallel_inference.py
(HybridParallelInferenceHelper:27) rewrites a static Program so an
autoregressive decode loop runs pipeline-parallel. TPU-native collapse:
the model forward is already one SPMD program under the global mesh, so
the helper only has to run the decode loop.

Decode path: models that support the paged KV cache (``forward(ids,
cache=...)`` — see serving.generation.model_fns) run prefill once and
then one ``[batch, 1]`` cached decode step per emitted token, so the
per-token cost is O(T·L) instead of the old full-window O(T²·L)
recompute. Models without cache support fall back to the original
fixed-padded-window forward (``_full_window_generate`` — also the
measured baseline in tools/bench_decode.py). Token selection is one
vectorized host pass either way (serving.generation.sampling).
"""
from __future__ import annotations

import numpy as np

__all__ = ["HybridParallelInferenceHelper"]


class HybridParallelInferenceHelper:
    """Greedy/sampling decode driver over a causal-LM ``Layer``.

    ``model(ids)`` must return logits ``[batch, seq, vocab]`` (optionally
    wrapped in a tuple/list, first element used). Works on a single chip
    and unchanged under a fleet mesh — sharding comes from the params'
    dist_spec annotations, not from this class. (The KV-cached fast path
    is single-shard; a live pp/mp/sep mesh routes to the full-window
    fallback.)
    """

    def __init__(self, model, max_length: int = 128, eos_token_id=None,
                 pad_token_id: int = 0):
        self.model = model
        self.max_length = int(max_length)
        self.eos_token_id = eos_token_id
        self.pad_token_id = int(pad_token_id)
        self._decoders = {}     # batch -> CachedDecoder (+ page geometry)

    def _logits(self, ids_tensor):
        out = self.model(ids_tensor)
        if isinstance(out, (tuple, list)):
            out = out[0]
        return out

    # ------------------------------------------------------ entry point
    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0):
        """Decode ``max_new_tokens`` tokens. temperature 0 = greedy;
        otherwise softmax sampling (vectorized inverse-CDF over the
        batch, numpy RNG seeded with ``seed``)."""
        ids = np.asarray(input_ids.numpy() if hasattr(input_ids, "numpy")
                         else input_ids).astype("int64")
        if ids.ndim == 1:
            ids = ids[None, :]
        prompt_len = ids.shape[1]
        if prompt_len >= self.max_length:
            raise ValueError(
                f"prompt length {prompt_len} leaves no room to generate "
                f"within max_length={self.max_length}")
        total = min(self.max_length, prompt_len + int(max_new_tokens))
        was_training = getattr(self.model, "training", False)
        if hasattr(self.model, "eval"):
            self.model.eval()
        try:
            if self._cached_decode_ok():
                return self._generate_cached(ids, total, temperature,
                                             seed)
            return self._full_window_generate(ids, total, temperature,
                                              seed)
        finally:
            if was_training:
                self.model.train()

    def _cached_decode_ok(self) -> bool:
        from ....serving.generation.model_fns import supports_cached_decode
        if not supports_cached_decode(self.model):
            return False
        from ...mesh_utils import get_global_mesh
        mesh = get_global_mesh()
        return mesh is None or not any(
            mesh.shape.get(a, 1) > 1 for a in ("pp", "mp", "sep"))

    # ------------------------------------------------------ cached path
    def _decoder_for(self, batch: int):
        entry = self._decoders.get(batch)
        if entry is None:
            from ....serving.generation.model_fns import CachedDecoder
            page_size = 16 if self.max_length >= 16 else self.max_length
            pages_per_seq = -(-self.max_length // page_size)
            dec = CachedDecoder(self.model, max_batch=batch,
                                page_size=page_size,
                                pages_per_seq=pages_per_seq)
            entry = self._decoders[batch] = (dec, page_size,
                                             pages_per_seq)
        return entry

    def _generate_cached(self, ids: np.ndarray, total: int,
                         temperature: float, seed: int):
        from ....serving.generation.sampling import sample_next_tokens

        b, prompt_len = ids.shape
        dec, page_size, pages_per_seq = self._decoder_for(b)
        dec.refresh_params()    # pick up weight updates between calls
        # contiguous per-row page ranges (page 0 is the trash page)
        tables = (1 + np.arange(b * pages_per_seq, dtype=np.int32)
                  .reshape(b, pages_per_seq))
        k, v = self.model.init_kv_pools(1 + b * pages_per_seq, page_size)
        lens = np.full(b, prompt_len, np.int32)
        # this loop selects on the host, one RandomState for the batch
        _, last, k, v, _ = dec.prefill(ids, lens, tables, None, None, k, v)
        rng = np.random.RandomState(seed)
        done = np.zeros(b, bool)
        buf = np.full((b, total), self.pad_token_id, "int64")
        buf[:, :prompt_len] = ids
        step_logits = np.asarray(last)
        for pos in range(prompt_len, total):
            nxt = sample_next_tokens(step_logits, temperature, rng=rng)
            buf[:, pos] = np.where(done, self.pad_token_id, nxt)
            if self.eos_token_id is not None:
                done |= (nxt == self.eos_token_id)
                if done.all():
                    return buf[:, :pos + 1]
            if pos + 1 >= total:
                break
            # cache the chosen token at `pos`, get logits for pos+1;
            # finished rows keep decoding masked-off via `active`
            active = ~done
            _, logits, k, v, _ = dec.decode(
                buf[:, pos], np.full(b, pos, np.int32), active,
                np.where(active, pos + 1, pos).astype(np.int32),
                tables, None, None, k, v)
            step_logits = np.asarray(logits)
        return buf[:, :total]

    # ------------------------------------------------------ fallback
    def _full_window_generate(self, ids: np.ndarray, total: int,
                              temperature: float, seed: int):
        """The pre-KV-cache path: one full padded-window forward per
        emitted token (ONE compiled shape for all steps). Kept for
        models without cache support and as the decode-bench baseline."""
        import paddle_tpu as paddle

        from ....serving.generation.sampling import sample_next_tokens

        b, prompt_len = ids.shape
        buf = np.full((b, total), self.pad_token_id, "int64")
        buf[:, :prompt_len] = ids
        rng = np.random.RandomState(seed)
        done = np.zeros(b, bool)
        for pos in range(prompt_len, total):
            logits = self._logits(paddle.to_tensor(buf))
            # slice the one needed row ON DEVICE before the host
            # transfer — the full [b, total, vocab] tensor is ~200MB
            # at realistic vocab sizes
            step_logits = np.asarray(logits[:, pos - 1, :].numpy())
            nxt = sample_next_tokens(step_logits, temperature, rng=rng)
            buf[:, pos] = np.where(done, self.pad_token_id, nxt)
            if self.eos_token_id is not None:
                done |= (nxt == self.eos_token_id)
                if done.all():
                    return buf[:, :pos + 1]
        return buf[:, :total]
