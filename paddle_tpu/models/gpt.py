"""GPT decoder-only transformer, TPU-native hybrid-parallel flagship.

Capability target: the GPT models the reference trains through Fleet hybrid
parallelism (SURVEY §3.3 north-star config; reference TP layers at
/root/reference/python/paddle/distributed/fleet/layers/mpu/mp_layers.py,
fused attention ops at /root/reference/paddle/fluid/operators/fused/).

TPU-native design:
- TP: q/kv/mlp projections are Column/RowParallelLinear — logically-full
  params carrying `dist_spec` PartitionSpecs; GSPMD shards the matmuls and
  inserts the Megatron identity/allreduce collectives.
- SP (sequence parallel / long context): activations carry a sequence-axis
  sharding constraint over the "sep" mesh axis when present — capability the
  reference snapshot lacks (SURVEY §5.7).
- Attention: Pallas flash attention on TPU (paddle_tpu.ops), XLA softmax
  path elsewhere; always causal, static shapes.
- PP: the layer stack is an explicit list so PipelineLayer/LayerDesc can
  segment it (paddle_tpu.distributed.fleet.meta_parallel.pp_layers).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import numpy as np

from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.initializer_utils import create_parameter_with_attr
from ..nn.layer.common import Dropout, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import LayerNorm

__all__ = [
    "gpt2_large",
    "GPTConfig", "GPTModel", "GPTForCausalLM", "GPTPretrainingCriterion",
    "GPTKVCache",
    "gpt_tiny", "gpt2_small", "gpt2_medium", "gpt3_1p3b",
]


@dataclass
class GPTConfig:
    vocab_size: int = 50304          # multiple of 128 for clean TP splits
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    intermediate_size: int = 0       # 0 → 4*hidden
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    tie_word_embeddings: bool = True
    # stacked=True swaps the per-layer module stack for one scan/pipeline
    # decoder with layer-stacked params (leading dim = num_layers, sharded
    # over 'pp') — the manual-SPMD hybrid-parallel path (TP psums, ring SP,
    # GPipe PP in a single shard_map). Layer dropout is not applied in this
    # mode (pretraining configs use 0).
    stacked: bool = False
    # activation recompute inside the scanned decoder (reference:
    # DistributedStrategy.recompute):
    #   "full"  — jax.checkpoint every layer (min memory, +~33% FLOPs)
    #   "dots"  — save matmul outputs, recompute elementwise (near-zero
    #             extra matmul FLOPs, bounded memory)
    #   "none"  — save everything XLA wants (max memory, max speed)
    recompute: str = "full"

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size
        assert self.hidden_size % self.num_heads == 0
        if self.recompute not in ("full", "dots", "attn", "none"):
            raise ValueError(
                f"recompute must be 'full', 'dots', 'attn' or 'none', "
                f"got {self.recompute!r}")


def gpt_tiny(**kw) -> GPTConfig:
    d = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
             max_seq_len=128)
    d.update(kw)
    return GPTConfig(**d)


def gpt2_small(**kw) -> GPTConfig:
    d = dict(vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
             max_seq_len=1024)
    d.update(kw)
    return GPTConfig(**d)


def gpt2_medium(**kw) -> GPTConfig:
    d = dict(vocab_size=50304, hidden_size=1024, num_layers=24,
             num_heads=16, max_seq_len=1024)
    d.update(kw)
    return GPTConfig(**d)


def gpt2_large(**kw) -> GPTConfig:
    d = dict(vocab_size=50304, hidden_size=1280, num_layers=36,
             num_heads=20, max_seq_len=1024)
    d.update(kw)
    return GPTConfig(**d)


def gpt3_1p3b(**kw) -> GPTConfig:
    d = dict(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
             max_seq_len=2048)
    d.update(kw)
    return GPTConfig(**d)


def _seq_constraint(x):
    """Sequence-parallel activation sharding over the 'sep' mesh axis
    ([B, S, H] → S sharded) — the unified surface's
    ``distributed.shard.constrain_seq``. No-op without a mesh or sep
    axis."""
    from ..distributed.shard import constrain_seq
    return constrain_seq(x)


class GPTKVCache:
    """Paged KV-cache view threaded through ``GPTModel.forward``.

    All array fields are framework Tensors (eager) or tracer-backed
    Tensors (under jit via ``jit.functional.functional_call``):

    - ``k``/``v``: per-layer pools — a list of ``[num_pages, page_size,
      heads, head_dim]`` Tensors for the module stack, or ONE stacked
      ``[num_layers, num_pages, page_size, heads, head_dim]`` Tensor
      for ``GPTStackedTransformer``. Page 0 is the trash page
      (ops/paged_attention.py).
    - ``block_tables``: [B, P] int32 logical-page → pool-page map.
    - ``ctx_len``: [B] int32 visible context length INCLUDING the
      positions written by this forward.
    - ``valid``: [B, S] bool — which fed positions are real (prefill
      padding and dead decode lanes are False; their K/V writes go to
      the trash page).
    - ``positions``: [B, S] int32 absolute positions being fed.
    - ``kind``: "prefill" (S = prompt window, ordinary causal attention
      plus pool write), "decode" (S = 1, attention reads the context
      back through the block table), or "chunked" (arbitrary S at a
      non-zero starting position — shared-prefix suffix prefill and the
      speculative-decoding verify window; per-position causal mask over
      the gathered paged context).

    ``forward(ids, cache=...)`` returns ``(logits, (k', v'))`` — the
    updated pool pytree mirrors the input structure, so jitted callers
    can donate the pools and carry them across steps.

    Quantized pools (FLAGS_decode_kv_dtype=int8) make each per-layer
    pool a 2-tuple ``(int8 values, f32 scales)`` instead of one array
    (ops/paged_attention.py docstring); everything here is
    structure-agnostic — pools are opaque pytrees whose leaves get
    wrapped/unwrapped at the boundaries.

    ``use_pallas`` names who attends (the fused kernels of
    ops/pallas_paged_attention.py or the pure-JAX body) for every
    layer of this forward; None leaves it to
    ``ops.paged_attention.kernel_by_default`` at trace time. The
    serving decoder always pins it (model_fns.CachedDecoder), so its
    fingerprint says what its executables hold.
    """

    __slots__ = ("kind", "page_size", "k", "v", "block_tables",
                 "ctx_len", "valid", "positions", "use_pallas", "mesh")

    def __init__(self, kind, page_size, k, v, block_tables, ctx_len,
                 valid, positions, use_pallas=None, mesh=None):
        if kind not in ("prefill", "decode", "chunked"):
            raise ValueError(f"kind must be 'prefill', 'decode' or "
                             f"'chunked', got {kind!r}")
        self.kind = kind
        self.page_size = int(page_size)
        self.k = k
        self.v = v
        self.block_tables = block_tables
        self.ctx_len = ctx_len
        self.valid = valid
        self.positions = positions
        self.use_pallas = use_pallas
        # serving replica's tensor-parallel mesh (serving/mesh.py) —
        # threaded EXPLICITLY because the engine dispatches from worker
        # threads that never see the thread-local global mesh. Only the
        # Pallas shard_map dispatch consumes it; the pure-JAX path
        # relies on GSPMD propagating the operands' heads sharding.
        self.mesh = mesh


class GPTEmbeddings(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size)
        init = I.Normal(std=config.initializer_range)
        self.position_embeddings = create_parameter_with_attr(
            [config.max_seq_len, config.hidden_size], self._dtype, None,
            False, default_initializer=init)
        self.dropout = Dropout(config.dropout)

    def forward(self, input_ids, positions=None):
        seq_len = input_ids.shape[-1]
        h = self.word_embeddings(input_ids)
        if positions is not None:
            # decode path: each row sits at its own absolute position
            import jax.numpy as jnp
            h = h + apply_op("position_embedding",
                             lambda w, p: jnp.take(w, p, axis=0),
                             self.position_embeddings, positions)
        else:
            h = h + self.position_embeddings[:seq_len]
        return _seq_constraint(self.dropout(h))


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_heads
        self.head_dim = config.hidden_size // config.num_heads
        self.hidden_size = config.hidden_size
        self.use_flash = config.use_flash_attention
        self.attn_dropout_p = config.dropout
        self.qkv_proj = ColumnParallelLinear(
            config.hidden_size, 3 * config.hidden_size, gather_output=False)
        self.out_proj = RowParallelLinear(
            config.hidden_size, config.hidden_size, input_is_parallel=True)
        self.dropout = Dropout(config.dropout)

    def forward(self, x, kv_cache=None):
        b, s, _ = x.shape
        qkv = self.qkv_proj(x)                       # [B,S,3H]
        # head-major (nh, 3, hd) layout: the mp-sharded 3H dim factors with
        # num_heads major, so GSPMD propagates the 'mp' sharding through the
        # reshape instead of all-gathering, and the layout matches the
        # stacked decoder (_stacked_layer_fwd) for checkpoint portability.
        qkv = qkv.reshape([b, s, self.num_heads, 3, self.head_dim])
        q = qkv[:, :, :, 0]                          # [B,S,nh,hd]
        k = qkv[:, :, :, 1]
        v = qkv[:, :, :, 2]
        if kv_cache is not None:
            # paged-cache path: persist this window's K/V in the pool;
            # decode attends through the block table (see GPTKVCache).
            # Pool leaves ride flattened through apply_op — a quantized
            # pool is a (values, scales) tuple and dispatch only
            # wraps/unwraps top-level Tensor args.
            import jax as _jax

            from ..ops.paged_attention import paged_attention_update
            k_leaves, pool_def = _jax.tree_util.tree_flatten(kv_cache.k)
            v_leaves, _ = _jax.tree_util.tree_flatten(kv_cache.v)
            nk = len(k_leaves)

            def _flat_update(q, k, v, tables, ctx, valid, positions,
                             *pool_leaves, **kw):
                kp = _jax.tree_util.tree_unflatten(
                    pool_def, pool_leaves[:nk])
                vp = _jax.tree_util.tree_unflatten(
                    pool_def, pool_leaves[nk:])
                out, kp2, vp2 = paged_attention_update(
                    q, k, v, kp, vp, tables, ctx, valid, positions, **kw)
                return (out, *_jax.tree_util.tree_leaves(kp2),
                        *_jax.tree_util.tree_leaves(vp2))

            res = apply_op(
                "paged_attention", _flat_update, q, k, v,
                kv_cache.block_tables, kv_cache.ctx_len, kv_cache.valid,
                kv_cache.positions, *k_leaves, *v_leaves,
                page_size=kv_cache.page_size, kind=kv_cache.kind,
                use_flash=self.use_flash, use_pallas=kv_cache.use_pallas,
                mesh=kv_cache.mesh)
            out = res[0]
            k_pool = _jax.tree_util.tree_unflatten(
                pool_def, res[1:1 + nk])
            v_pool = _jax.tree_util.tree_unflatten(
                pool_def, res[1 + nk:])
            out = out.reshape([b, s, self.hidden_size])
            return self.dropout(self.out_proj(out)), k_pool, v_pool
        from ..nn.functional.attention import scaled_dot_product_attention
        out = scaled_dot_product_attention(
            q, k, v, is_causal=True, dropout_p=self.attn_dropout_p,
            training=self.training, use_flash=self.use_flash)  # [B,S,nh,hd]
        out = out.reshape([b, s, self.hidden_size])
        return self.dropout(self.out_proj(out))


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.fc_in = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, gather_output=False)
        self.fc_out = RowParallelLinear(
            config.intermediate_size, config.hidden_size,
            input_is_parallel=True)
        self.dropout = Dropout(config.dropout)

    def forward(self, x):
        # tanh-approximate gelu: GPT-2's canonical "gelu_new", and the
        # same form the stacked decoder uses (keeps the two paths
        # numerically consistent)
        return self.dropout(self.fc_out(F.gelu(self.fc_in(x),
                                               approximate=True)))


class GPTDecoderLayer(Layer):
    """Pre-LN decoder block (the MFU-critical fused pattern the reference
    implements as fused_attention/fused_feedforward CUDA ops —
    /root/reference/paddle/fluid/operators/fused/; here XLA fuses)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.mlp = GPTMLP(config)

    def forward(self, x, kv_cache=None):
        if kv_cache is not None:
            a, k_pool, v_pool = self.attn(self.ln_1(x), kv_cache=kv_cache)
            x = x + a
            x = x + self.mlp(self.ln_2(x))
            return _seq_constraint(x), k_pool, v_pool
        x = x + self.attn(self.ln_1(x))
        x = x + self.mlp(self.ln_2(x))
        return _seq_constraint(x)


def _stacked_layer_fwd(p, x, *, num_heads, head_dim, eps, mp_size, sep_size,
                       use_flash=True, kv=None):
    """ONE decoder layer, manual SPMD (runs inside shard_map).

    x: [mb, s_local, H] (full hidden; seq sep-sharded). Params are the local
    TP shards: qkv/fc1 column-split, out/fc2 row-split over 'mp' — the
    Megatron pattern with the allreduces written out (psum over 'mp'),
    which is what GSPMD would insert for the module path
    (mp_layers.py docstring) but explicit here because shard_map is manual.

    qkv layout is HEAD-MAJOR: the 3H output dim is (num_heads, 3, head_dim),
    so a contiguous 'mp' column split hands each rank nh/mp complete heads
    with their (q,k,v) triples — checkpoints are portable across mp degrees.
    """
    import jax
    import jax.numpy as jnp

    def ln(h, w, b):
        h32 = h.astype(jnp.float32)
        mu = jnp.mean(h32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h32 - mu), axis=-1, keepdims=True)
        out = (h32 - mu) * jax.lax.rsqrt(var + jnp.float32(eps))
        return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)

    mb, s_loc, hidden = x.shape
    nh_loc = num_heads // mp_size

    from ..distributed.fleet.meta_parallel.mp_ops import (mp_allreduce,
                                                          mp_identity)

    h = ln(x, p["ln1_w"], p["ln1_b"])
    if mp_size > 1:
        h = mp_identity(h, "mp")                      # 'f': psum bwd
    qkv = h @ p["qkv_w"] + p["qkv_b"]                 # [mb, s, 3*H/mp]
    qkv = qkv.reshape(mb, s_loc, nh_loc, 3, head_dim)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]  # [mb,s,nh,hd]
    sm_scale = 1.0 / math.sqrt(head_dim)
    k_pool = v_pool = None
    if kv is not None:
        # paged-cache decode/prefill. The scan body always runs
        # single-program (mp_size=1 — GPTStackedTransformer enforces
        # that before routing here); under a serving mesh the operands
        # arrive mp-sharded and GSPMD partitions this whole block,
        # except the Pallas kernels which dispatch per-shard through
        # shard_map inside paged_attention_update (mesh kwarg).
        from ..ops.paged_attention import paged_attention_update
        (kp, vp, tables, ctx, valid, positions, page_size, kind,
         use_pallas, serving_mesh) = kv
        attn, k_pool, v_pool = paged_attention_update(
            q, k, v, kp, vp, tables, ctx, valid, positions,
            page_size=page_size, kind=kind, use_flash=use_flash,
            use_pallas=use_pallas, mesh=serving_mesh)
    elif sep_size > 1:
        from ..ops.ring_attention import _ring_attention_local
        attn = _ring_attention_local(q, k, v, axis_name="sep",
                                     axis_size=sep_size, causal=True,
                                     sm_scale=sm_scale)
    else:
        # shared flash-or-dense selection (ops/flash_attention.py):
        # long-seq Pallas kernel's O(S) memory is what lets 1.3B s=2048
        # fit one chip (dense S^2 materialization OOMs)
        from ..ops.flash_attention import attention_bshd
        attn = attention_bshd(q, k, v, causal=True, scale=sm_scale,
                              use_flash=use_flash)
    # named for the "attn" recompute policy: saving ONLY this tensor
    # (~hidden-sized, bf16) lets the backward skip re-running the
    # attention forward while everything else still rematerializes
    from jax.ad_checkpoint import checkpoint_name
    attn = checkpoint_name(attn, "attn_out")
    attn = attn.reshape(mb, s_loc, nh_loc * head_dim)
    o = attn @ p["out_w"]                             # partial over H/mp
    if mp_size > 1:
        o = mp_allreduce(o, "mp")                     # 'g': identity bwd
    x = x + o + p["out_b"]

    h2 = ln(x, p["ln2_w"], p["ln2_b"])
    if mp_size > 1:
        h2 = mp_identity(h2, "mp")
    u = jax.nn.gelu(h2 @ p["fc1_w"] + p["fc1_b"], approximate=True)
    d = u @ p["fc2_w"]
    if mp_size > 1:
        d = mp_allreduce(d, "mp")
    out = x + d + p["fc2_b"]
    if kv is not None:
        return out, k_pool, v_pool
    return out


class GPTStackedTransformer(Layer):
    """Decoder stack with layer-stacked params: lax.scan on one device, and
    under a fleet mesh ONE shard_map composing PP (GPipe over 'pp'), TP
    (explicit psums over 'mp') and SP (ring attention over 'sep')."""

    # dist_spec per stacked param (dim 0 = layers → 'pp')
    SPECS = {
        "ln1_w": ("pp", None), "ln1_b": ("pp", None),
        "qkv_w": ("pp", None, "mp"), "qkv_b": ("pp", "mp"),
        "out_w": ("pp", "mp", None), "out_b": ("pp", None),
        "ln2_w": ("pp", None), "ln2_b": ("pp", None),
        "fc1_w": ("pp", None, "mp"), "fc1_b": ("pp", "mp"),
        "fc2_w": ("pp", "mp", None), "fc2_b": ("pp", None),
    }

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        L, H, inter = (config.num_layers, config.hidden_size,
                       config.intermediate_size)
        std = config.initializer_range

        def mk(shape, init):
            return create_parameter_with_attr(
                shape, self._dtype, None, False, default_initializer=init)

        normal = I.Normal(std=std)
        ones = I.Constant(1.0)
        zeros = I.Constant(0.0)
        self.ln1_w = mk([L, H], ones)
        self.ln1_b = mk([L, H], zeros)
        self.qkv_w = mk([L, H, 3 * H], normal)
        self.qkv_b = mk([L, 3 * H], zeros)
        self.out_w = mk([L, H, H], normal)
        self.out_b = mk([L, H], zeros)
        self.ln2_w = mk([L, H], ones)
        self.ln2_b = mk([L, H], zeros)
        self.fc1_w = mk([L, H, inter], normal)
        self.fc1_b = mk([L, inter], zeros)
        self.fc2_w = mk([L, inter, H], normal)
        self.fc2_b = mk([L, H], zeros)
        for name, spec in self.SPECS.items():
            getattr(self, name).dist_spec = spec

    def _n_micro(self, pp, batch):
        from ..distributed.fleet.fleet_api import _fleet_state
        strat = _fleet_state.get("strategy")
        n = None
        if strat is not None:
            n = (strat.pipeline_configs or {}).get("accumulate_steps")
        if not n:
            n = 2 * pp if pp > 1 else 1
        while batch % n != 0 and n > 1:
            n -= 1
        return n

    @staticmethod
    def _pp_schedule():
        """(schedule_mode, virtual_pp_degree) from the fleet strategy —
        reference toggle: pipeline_configs.schedule_mode ('1F1B'/'F-then-B',
        distributed_strategy.py:1509) + virtual pp for the interleaved
        schedule (pipeline_parallel.py:461)."""
        from ..distributed.fleet.fleet_api import _fleet_state
        strat = _fleet_state.get("strategy")
        cfg = (strat.pipeline_configs or {}) if strat is not None else {}
        return (cfg.get("schedule_mode", "1F1B"),
                int(cfg.get("virtual_pp_degree", 1) or 1))

    def forward(self, x, cache=None):
        import functools

        cfg = self.config
        names = list(self.SPECS.keys())
        params = [getattr(self, n) for n in names]

        if cache is not None:
            return self._forward_cached(x, params, names, cache)

        def fn(x_arr, *param_arrays):
            from ..distributed.mesh_utils import get_global_mesh
            p = dict(zip(names, param_arrays))
            mesh = get_global_mesh()
            pp = mesh.shape.get("pp", 1) if mesh is not None else 1
            mp = mesh.shape.get("mp", 1) if mesh is not None else 1
            sep = mesh.shape.get("sep", 1) if mesh is not None else 1
            if cfg.num_layers % max(pp, 1) != 0:
                raise ValueError(
                    f"num_layers={cfg.num_layers} must be divisible by "
                    f"pp_degree={pp} for the stacked pipeline decoder")
            if cfg.num_heads % max(mp, 1) != 0:
                raise ValueError(
                    f"num_heads={cfg.num_heads} must be divisible by "
                    f"mp_degree={mp}")
            layer = functools.partial(
                _stacked_layer_fwd, num_heads=cfg.num_heads,
                head_dim=cfg.hidden_size // cfg.num_heads,
                eps=cfg.layer_norm_eps, mp_size=mp, sep_size=sep,
                use_flash=cfg.use_flash_attention)
            if mesh is None or (pp == 1 and mp == 1 and sep == 1):
                if cfg.recompute == "none":
                    wrapped = layer
                elif cfg.recompute == "dots":
                    wrapped = jax.checkpoint(
                        layer,
                        policy=jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable)
                elif cfg.recompute == "attn":
                    # middle ground: save just the attention outputs
                    # (bf16, hidden-sized — ~16 MB/layer at 1.3B) so the
                    # bwd never re-runs the flash forward kernel; all
                    # other activations rematerialize as in "full"
                    wrapped = jax.checkpoint(
                        layer,
                        policy=jax.checkpoint_policies
                        .save_only_these_names("attn_out"))
                else:  # "full"
                    wrapped = jax.checkpoint(layer)

                def step(c, p_slice):
                    return wrapped(p_slice, c), None
                out, _ = jax.lax.scan(step, x_arr, p)
                return out
            from jax.sharding import PartitionSpec as P
            from ..distributed.fleet.meta_parallel.pp_spmd import (
                spmd_pipeline, spmd_pipeline_1f1b, spmd_pipeline_interleaved)
            param_specs = {n: P(*[a if (a in mesh.axis_names
                                        and mesh.shape[a] > 1) else None
                                  for a in self.SPECS[n]]) for n in names}
            dp_ok = ("dp" in mesh.axis_names and mesh.shape["dp"] > 1)
            sep_ok = sep > 1
            n_micro = self._n_micro(pp, x_arr.shape[0])
            x_spec = P("dp" if dp_ok else None, "sep" if sep_ok else None,
                       None)
            schedule, vpp = self._pp_schedule()
            if pp > 1 and vpp > 1:
                return spmd_pipeline_interleaved(
                    layer, p, x_arr, mesh, n_micro, vpp, param_specs,
                    x_spec, axis="pp")
            if pp > 1 and schedule == "1F1B":
                return spmd_pipeline_1f1b(layer, p, x_arr, mesh, n_micro,
                                          param_specs, x_spec, axis="pp")
            return spmd_pipeline(layer, p, x_arr, mesh, n_micro,
                                 param_specs, x_spec, axis="pp")

        return apply_op("gpt_stacked_decoder", fn, x, *params)

    def _forward_cached(self, x, params, names, cache):
        """Paged-cache scan: pools are stacked ``[L, num_pages, ...]``
        arrays carried through ``lax.scan`` alongside the layer-stacked
        params. A live 'mp' axis is fine: the scan body stays
        single-program (mp_size=1) and GSPMD partitions it from the
        operands' committed shardings (mp-sharded weights, heads-sharded
        pools — serving/mesh.py), inserting the out/fc2 reduction
        collectives itself. Only pp and sep genuinely can't thread a
        paged-pool scan (stage-sliced layers / seq-sharded gather) and
        still raise, naming the offending axis."""
        import functools

        cfg = self.config
        page_size, kind = cache.page_size, cache.kind
        use_pallas = cache.use_pallas
        serving_mesh = cache.mesh
        # pool leaves ride flattened through apply_op (quantized pools
        # are (values, scales) tuples; dispatch only unwraps top-level
        # Tensor args) and re-assemble inside the traced fn
        k_leaves, pool_def = jax.tree_util.tree_flatten(cache.k)
        v_leaves, _ = jax.tree_util.tree_flatten(cache.v)
        nk = len(k_leaves)

        def fn(x_arr, tables, ctx, valid, positions, *rest):
            from ..distributed.mesh_utils import get_global_mesh
            mesh = get_global_mesh()
            for axis in ("pp", "sep"):
                if mesh is not None and mesh.shape.get(axis, 1) > 1:
                    raise NotImplementedError(
                        f"KV-cached decode cannot run under a live "
                        f"'{axis}' mesh axis: the paged-pool scan "
                        f"carries whole layers and whole sequences. "
                        f"Drop '{axis}' — dp replicas serve "
                        f"independently and 'mp' tensor-parallelism is "
                        f"supported via serving.mesh.ServingMesh")
            k_pools = jax.tree_util.tree_unflatten(pool_def, rest[:nk])
            v_pools = jax.tree_util.tree_unflatten(
                pool_def, rest[nk:2 * nk])
            p = dict(zip(names, rest[2 * nk:]))
            layer = functools.partial(
                _stacked_layer_fwd, num_heads=cfg.num_heads,
                head_dim=cfg.hidden_size // cfg.num_heads,
                eps=cfg.layer_norm_eps, mp_size=1, sep_size=1,
                use_flash=cfg.use_flash_attention)

            def step(c, xs):
                p_slice, kp, vp = xs
                out, kp2, vp2 = layer(
                    p_slice, c, kv=(kp, vp, tables, ctx, valid,
                                    positions, page_size, kind,
                                    use_pallas, serving_mesh))
                return out, (kp2, vp2)

            # scan slices each pool leaf's leading (layer) dim — tuple
            # pools scan as pytrees, each step sees its layer's leaves
            out, (k2, v2) = jax.lax.scan(step, x_arr,
                                         (p, k_pools, v_pools))
            return (out, *jax.tree_util.tree_leaves(k2),
                    *jax.tree_util.tree_leaves(v2))

        res = apply_op("gpt_stacked_decoder_cached", fn, x,
                       cache.block_tables, cache.ctx_len, cache.valid,
                       cache.positions, *k_leaves, *v_leaves, *params)
        out = res[0]
        k2 = jax.tree_util.tree_unflatten(pool_def, res[1:1 + nk])
        v2 = jax.tree_util.tree_unflatten(pool_def,
                                          res[1 + nk:1 + 2 * nk])
        return out, k2, v2


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        if config.stacked:
            self.decoder = GPTStackedTransformer(config)
            self.layers = LayerList([])
        else:
            self.layers = LayerList([GPTDecoderLayer(config)
                                     for _ in range(config.num_layers)])
        self.ln_f = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)

    def forward(self, input_ids, cache=None):
        if cache is not None:
            return self._forward_cached(input_ids, cache)
        h = self.embeddings(input_ids)
        if self.config.stacked:
            h = self.decoder(h)
        else:
            for layer in self.layers:
                h = layer(h)
        return self.ln_f(h)

    def _forward_cached(self, input_ids, cache: GPTKVCache):
        """Cache-threaded forward: returns ``(h, (k', v'))`` where the
        updated pools mirror ``cache.k``/``cache.v`` structure."""
        h = self.embeddings(input_ids, positions=cache.positions)
        if self.config.stacked:
            h, k_new, v_new = self.decoder(h, cache=cache)
        else:
            k_new, v_new = [], []
            for i, layer in enumerate(self.layers):
                view = GPTKVCache(
                    cache.kind, cache.page_size, cache.k[i], cache.v[i],
                    cache.block_tables, cache.ctx_len, cache.valid,
                    cache.positions, use_pallas=cache.use_pallas,
                    mesh=cache.mesh)
                h, k_i, v_i = layer(h, kv_cache=view)
                k_new.append(k_i)
                v_new.append(v_i)
        return self.ln_f(h), (k_new, v_new)

    # -- pipeline segmentation hook (pp_layers.LayerDesc consumers) --
    def pipeline_stages(self):
        return [self.embeddings] + list(self.layers) + [self.ln_f]


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config
        if not config.tie_word_embeddings:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def forward(self, input_ids, cache=None):
        if cache is not None:
            h, pools = self.gpt(input_ids, cache=cache)
        else:
            h = self.gpt(input_ids)
        if self.config.tie_word_embeddings:
            from ..tensor import linalg
            w = self.gpt.embeddings.word_embeddings.weight
            logits = linalg.matmul(h, w, transpose_y=True)
        else:
            logits = self.lm_head(h)
        if cache is not None:
            return logits, pools
        return logits

    # ---- paged KV-cache plumbing (serving.generation engine) ----
    def init_kv_pools(self, num_pages: int, page_size: int, dtype=None):
        """Zeroed paged K/V pools shaped for this model: a list of
        per-layer ``[num_pages, page_size, heads, head_dim]`` arrays
        (module stack) or one stacked ``[L, ...]`` pair (stacked
        decoder). Page 0 is the trash page and is never allocated.
        ``dtype`` may also be the string ``"int8"``: pools then become
        ``(int8 values, f32 per-slot-per-head scales)`` tuples (see
        ops.paged_attention for the quantized-pool contract). Returns
        raw jax arrays ``(k, v)`` — engine plumbing, not Tensors."""
        import jax.numpy as jnp
        cfg = self.config
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        shape = (int(num_pages), int(page_size), nh, hd)
        if isinstance(dtype, str) and dtype == "int8":
            sshape = shape[:-1]

            def mk(lead=()):
                return (jnp.zeros(lead + shape, jnp.int8),
                        jnp.zeros(lead + sshape, jnp.float32))

            if cfg.stacked:
                return mk((cfg.num_layers,)), mk((cfg.num_layers,))
            return ([mk() for _ in range(cfg.num_layers)],
                    [mk() for _ in range(cfg.num_layers)])
        dt = dtype or self.gpt.embeddings.word_embeddings.weight._data.dtype
        if cfg.stacked:
            k = jnp.zeros((cfg.num_layers,) + shape, dt)
            return k, jnp.zeros((cfg.num_layers,) + shape, dt)
        return ([jnp.zeros(shape, dt) for _ in range(cfg.num_layers)],
                [jnp.zeros(shape, dt) for _ in range(cfg.num_layers)])

    def kv_cache_spec(self, kv_dtype: str = "") -> dict:
        """Geometry the decode engine sizes its cache from.
        ``kv_dtype`` ('' = model dtype) adds per-token byte accounting
        so sizing and shardcheck agree on pool cost."""
        from ..ops.paged_attention import kv_pool_bytes
        cfg = self.config
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        per_token = cfg.num_layers * 2 * kv_pool_bytes(
            1, 1, nh, hd, kv_dtype or None)
        return {"num_layers": cfg.num_layers,
                "num_heads": nh,
                "head_dim": hd,
                "max_seq_len": cfg.max_seq_len,
                "stacked": bool(cfg.stacked),
                "kv_dtype": kv_dtype or "",
                "kv_bytes_per_token": int(per_token)}

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())


class GPTPretrainingCriterion(Layer):
    """Causal-LM loss: shift-by-one CE over the (vocab-parallel) logits —
    reference: ParallelCrossEntropy (mp_layers.py:558)."""

    def __init__(self, ignore_index: int = -100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        # logits [B,S,V], labels [B,S] — next-token prediction
        from ..tensor import manipulation as M
        lg = logits[:, :-1, :]
        lb = labels[:, 1:]
        b, s, v = lg.shape
        return F.cross_entropy(lg.reshape([b * s, v]), lb.reshape([b * s]),
                               ignore_index=self.ignore_index)
