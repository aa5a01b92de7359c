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
    "smallthinker_21ba3b", "k_exaone_236b_a23b", "glm_4p7_flash",
    "brumby_14b_base",
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
    # ---- what other decoder families need of the one block. Every
    # default is the GPT-2/GPT-3 block, whose modules and programs are
    # then exactly what they were; the module stack alone takes the
    # fields below (``stacked`` refuses each by name).
    num_kv_heads: int = 0            # 0 -> num_heads; K/V head g serves
    #                                  query heads g*G .. g*G+G-1
    head_dim: int = 0                # 0 -> hidden_size // num_heads
    norm: str = "layernorm"          # or "rmsnorm" (no bias, f32 inside)
    bias: bool = True                # biases on projections
    position: str = "learned"        # or "rope": rotate-half RoPE on the
    #                                  layers rope_layout marks, nothing
    #                                  added on the others (NoPE)
    rope_theta: float = 10000.0
    rope_layout: tuple = ()          # per layer 0/1; () -> every layer
    sliding_window: int = 0          # positions a token attends, itself
    #                                  among them, on the layers
    sliding_window_layout: tuple = ()  # marks (per layer 0/1; () -> all)
    moe_num_experts: int = 0         # experts held here; 0 -> the dense
    #                                  MLP on every layer
    moe_top_k: int = 0
    moe_intermediate_size: int = 0   # width of one gated expert
    # what the router reads: the MLP's normed input, or the
    # attention's ("router placed before attention")
    moe_router_input: str = "mlp_input"
    dtype: str = ""                  # parameters are created in it
    #                                  ('' -> float32)
    qk_norm: bool = False            # RMSNorm over head_dim on q and k
    #                                  (one weight vector a layer each),
    #                                  before RoPE
    mlp_kind: str = "gelu"           # the dense MLP: fc_in-GELU-fc_out,
    #                                  or "swiglu": (silu(x Wg) * x Wu) Wd,
    #                                  no biases
    moe_layout: tuple = ()           # per layer 0/1: which layers have
    #                                  experts; () -> every layer
    # one chip's share of an expert-parallel layer: the router has
    # moe_router_experts outputs (0 -> moe_num_experts: all held) and
    # the weights hold experts moe_expert_offset .. + moe_num_experts-1
    moe_router_experts: int = 0
    moe_expert_offset: int = 0
    moe_scoring: str = "softmax_top_k"  # or "sigmoid_norm": sigmoid
    #                                  scores, normalised over the chosen,
    moe_routed_scale: float = 1.0    # times this
    moe_activation: str = "relu"     # the experts' gate: or "silu"
    moe_shared_intermediate_size: int = 0  # width of the expert every
    #                                  token goes through; 0 -> none
    moe_token_block: int = 0         # rows an expert layer computes at a
    #                                  time (bounds what a long prefill
    #                                  keeps); 0 -> all at once
    moe_selection_bias: bool = False  # a bias added to the sigmoid scores
    #                                  to choose the experts, not to weigh
    #                                  them (zero at initialisation)
    # latent attention (DeepSeek-V2's multi-head latent attention): q
    # through a rank-q_lora_rank bottleneck; K and V of every head made
    # from one normed latent of kv_lora_rank beside one rotary key part
    # of qk_rope_head_dim that all heads share, which is all the cache
    # keeps of a token; a head's query and key are qk_nope_head_dim +
    # qk_rope_head_dim wide, its value v_head_dim. 0 -> no latent
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # power retention of degree retention_degree in place of softmax
    # attention on every layer (ops/retention.py): the squared scaled dot
    # product over the same q/k/v projections and GQA groups, a
    # recurrent state a K/V head instead of a KV cache. 0 -> attention
    retention_degree: int = 0
    retention_gate: bool = False     # a forget gate a K/V head, sigmoid(
    #                                  u . w_g + b_g) (else the state
    #                                  never decays)
    retention_eps: float = 1e-6      # added to the normaliser
    retention_state_dtype: str = "float32"  # what a slot keeps S, z in

    NEW_FIELDS = ("num_kv_heads", "head_dim", "norm", "bias", "position",
                  "sliding_window", "moe_num_experts", "dtype", "qk_norm",
                  "mlp_kind", "moe_layout", "moe_router_experts",
                  "moe_expert_offset", "moe_scoring", "moe_routed_scale",
                  "moe_activation", "moe_shared_intermediate_size",
                  "moe_token_block", "moe_selection_bias", "q_lora_rank",
                  "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "retention_degree", "retention_gate",
                  "retention_eps", "retention_state_dtype")

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size
        fresh = GPTConfig.__dataclass_fields__
        changed = [f for f in self.NEW_FIELDS
                   if getattr(self, f) != fresh[f].default]
        latent = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                  self.qk_rope_head_dim, self.v_head_dim)
        if any(latent) and not (all(latent) and self.position == "rope"
                                and not (self.bias or self.qk_norm
                                         or self.sliding_window)):
            raise ValueError(
                "latent attention needs q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim, v_head_dim and rope "
                "positions, and takes no bias, qk_norm or sliding_window")
        if any(latent) and self.head_dim == 0:
            self.head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.head_dim == 0:
            assert self.hidden_size % self.num_heads == 0
            self.head_dim = self.hidden_size // self.num_heads
        if self.num_kv_heads == 0:
            self.num_kv_heads = self.num_heads
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} is no multiple of "
                f"num_kv_heads={self.num_kv_heads}")
        if self.stacked and changed:
            raise ValueError(
                f"the stacked scan decoder is the GPT-2/GPT-3 block; it "
                f"does not take {', '.join(changed)} (module stack only)")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', "
                             f"got {self.norm!r}")
        if self.position not in ("learned", "rope"):
            raise ValueError(f"position must be 'learned' or 'rope', "
                             f"got {self.position!r}")
        if self.moe_router_input not in ("mlp_input", "attention_input"):
            raise ValueError(
                f"moe_router_input must be 'mlp_input' or "
                f"'attention_input', got {self.moe_router_input!r}")
        for name in ("rope_layout", "sliding_window_layout", "moe_layout"):
            layout = tuple(int(v) for v in getattr(self, name))
            if layout and len(layout) != self.num_layers:
                raise ValueError(f"{name} has {len(layout)} entries for "
                                 f"{self.num_layers} layers")
            setattr(self, name, layout)
        if self.moe_num_experts and self.moe_router_experts == 0:
            self.moe_router_experts = self.moe_num_experts
        if self.moe_num_experts and not (
                0 < self.moe_top_k <= self.moe_router_experts
                and self.moe_intermediate_size > 0):
            raise ValueError("experts need moe_top_k and "
                             "moe_intermediate_size")
        if self.mlp_kind not in ("gelu", "swiglu"):
            raise ValueError(f"mlp_kind must be 'gelu' or 'swiglu', "
                             f"got {self.mlp_kind!r}")
        if self.moe_scoring not in ("softmax_top_k", "sigmoid_norm"):
            raise ValueError(
                f"moe_scoring must be 'softmax_top_k' or 'sigmoid_norm', "
                f"got {self.moe_scoring!r}")
        if self.moe_selection_bias and self.moe_scoring != "sigmoid_norm":
            raise ValueError("moe_selection_bias chooses among sigmoid "
                             "scores: it needs moe_scoring='sigmoid_norm'")
        if self.moe_activation not in ("relu", "silu"):
            raise ValueError(f"moe_activation must be 'relu' or 'silu', "
                             f"got {self.moe_activation!r}")
        if self.moe_num_experts and not (
                0 <= self.moe_expert_offset
                <= self.moe_router_experts - self.moe_num_experts):
            raise ValueError(
                f"experts {self.moe_expert_offset}.."
                f"{self.moe_expert_offset + self.moe_num_experts - 1} "
                f"are not among the router's {self.moe_router_experts}")
        if self.retention_degree not in (0, 2):
            raise ValueError(f"retention_degree must be 0 (attention) or "
                             f"2, got {self.retention_degree}")
        if self.retention_degree and (
                any(latent) or self.sliding_window or self.bias
                or self.head_dim % 2):
            raise ValueError(
                "power retention takes no latent attention, "
                "sliding_window or bias, and an even head_dim")
        if self.retention_gate and not self.retention_degree:
            raise ValueError("retention_gate gates power retention: it "
                             "needs retention_degree")
        if self.recompute not in ("full", "dots", "attn", "none"):
            raise ValueError(
                f"recompute must be 'full', 'dots', 'attn' or 'none', "
                f"got {self.recompute!r}")

    # ---- what a layer is, read off the fields
    @property
    def classic_attention(self) -> bool:
        """The GPT-2/GPT-3 attention module (fused head-major qkv with
        biases, learned positions, every head its own K/V, the whole
        context)."""
        return (self.num_kv_heads == self.num_heads
                and self.head_dim * self.num_heads == self.hidden_size
                and self.bias and self.position == "learned"
                and not self.sliding_window)

    def layer_rope(self, layer: int) -> bool:
        return self.position == "rope" and bool(
            self.rope_layout[layer] if self.rope_layout else 1)

    def layer_window(self, layer: int):
        """Positions layer ``layer`` attends, or None for all."""
        if not self.sliding_window:
            return None
        marked = self.sliding_window_layout[layer] \
            if self.sliding_window_layout else 1
        return int(self.sliding_window) if marked else None

    def layer_experts(self, layer: int) -> bool:
        """Whether layer ``layer``'s MLP is the expert layer."""
        return bool(self.moe_num_experts) and bool(
            self.moe_layout[layer] if self.moe_layout else 1)

    @property
    def latent_width(self) -> int:
        """Values a token a layer of a latent attention's cache (the
        latent and the shared rotary key part), 0 without one."""
        return self.kv_lora_rank + self.qk_rope_head_dim \
            if self.kv_lora_rank else 0

    @property
    def retention_state_dim(self) -> int:
        """``D``: the width of a retention layer's key feature (its state
        is ``[D, head_dim]`` a K/V head), 0 without retention."""
        from ..ops.retention import state_dim
        return state_dim(self.head_dim) if self.retention_degree else 0

    def num_params(self) -> int:
        """Parameters of the model these fields describe, reckoned
        without building it."""
        h, v = self.hidden_size, self.vocab_size
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        norm = h * (2 if self.norm == "layernorm" else 1)
        attn = h * (qd + 2 * kvd) + qd * h
        if self.kv_lora_rank:
            nh = self.num_heads
            attn = (h * self.q_lora_rank + self.q_lora_rank
                    + self.q_lora_rank * qd
                    + h * self.latent_width + self.kv_lora_rank
                    + self.kv_lora_rank * nh * (self.qk_nope_head_dim
                                                + self.v_head_dim)
                    + nh * self.v_head_dim * h)
        if self.bias:
            attn += qd + 2 * kvd + h
        if self.qk_norm:
            attn += 2 * self.head_dim
        if self.retention_gate:
            attn += (h + 1) * self.num_kv_heads
        sparse = h * self.moe_router_experts + 3 * h * (
            self.moe_num_experts * self.moe_intermediate_size
            + self.moe_shared_intermediate_size)
        if self.moe_selection_bias:
            sparse += self.moe_router_experts
        if self.mlp_kind == "swiglu":
            dense = 3 * h * self.intermediate_size
        else:
            dense = 2 * h * self.intermediate_size
            if self.bias:
                dense += self.intermediate_size + h
        n_sparse = sum(self.layer_experts(i)
                       for i in range(self.num_layers))
        total = v * h + self.num_layers * (2 * norm + attn) \
            + n_sparse * sparse + (self.num_layers - n_sparse) * dense + norm
        if self.position == "learned":
            total += self.max_seq_len * h
        if not self.tie_word_embeddings:
            total += v * h
        return int(total)


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


def smallthinker_21ba3b(**kw) -> GPTConfig:
    """SmallThinker-21BA3B-Instruct (PowerInfer, config.json): 52 layers
    of 28 query heads over 4 K/V heads of 128, RMSNorm, no biases,
    untied head; every fourth layer attends the whole context with no
    positions at all, the others the last 4096 with RoPE (theta 1.5e6);
    64 ReGLU experts of width 768, 6 a token, routed from the
    attention's normed input. ``num_layers`` cuts whole periods off the
    end (the layouts are cut with it); ``dtype`` is the parameters'."""
    layers = int(kw.get("num_layers", 52))
    pattern = tuple(0 if i % 4 == 0 else 1 for i in range(layers))
    d = dict(vocab_size=151936, hidden_size=2560, num_layers=layers,
             num_heads=28, num_kv_heads=4, head_dim=128, max_seq_len=16384,
             norm="rmsnorm", layer_norm_eps=1e-6, bias=False,
             position="rope", rope_theta=1.5e6, rope_layout=pattern,
             sliding_window=4096, sliding_window_layout=pattern,
             moe_num_experts=64, moe_top_k=6, moe_intermediate_size=768,
             moe_router_input="attention_input", tie_word_embeddings=False,
             use_flash_attention=True)
    d.update(kw)
    return GPTConfig(**d)


def k_exaone_236b_a23b(**kw) -> GPTConfig:
    """K-EXAONE-236B-A23B (LG AI Research, config.json, ``exaone_moe``):
    48 layers of 64 query heads over 8 K/V heads of 128 with RMSNorm on
    q and k, RMSNorm (eps 1e-5), no biases, untied head; every fourth
    layer (3, 7, ...) attends the whole context with no positions at
    all, the others the last 128 with RoPE (theta 1e6); layer 0 a SwiGLU
    MLP of width 18,432, every other layer 128 SwiGLU experts of width
    2,048, 8 a token by sigmoid scores normalised over the chosen and
    scaled 2.5, beside one shared expert of the same width. The
    multi-token-prediction module is not built. ``num_layers`` keeps
    the leading layers under their published kinds; ``moe_num_experts``
    (with ``moe_expert_offset``) is the share of the 128 experts held,
    ``vocab_size`` the rows of the vocabulary held; ``dtype`` is the
    parameters'."""
    layers = int(kw.get("num_layers", 48))
    window = tuple(0 if i % 4 == 3 else 1 for i in range(layers))
    d = dict(vocab_size=153600, hidden_size=6144, num_layers=layers,
             num_heads=64, num_kv_heads=8, head_dim=128,
             max_seq_len=262144, intermediate_size=18432, norm="rmsnorm",
             layer_norm_eps=1e-5, bias=False, position="rope",
             rope_theta=1e6, rope_layout=window, sliding_window=128,
             sliding_window_layout=window, qk_norm=True, mlp_kind="swiglu",
             moe_layout=tuple(int(i >= 1) for i in range(layers)),
             moe_num_experts=128, moe_router_experts=128, moe_top_k=8,
             moe_intermediate_size=2048, moe_shared_intermediate_size=2048,
             moe_scoring="sigmoid_norm", moe_routed_scale=2.5,
             moe_activation="silu", moe_token_block=4096,
             tie_word_embeddings=False, use_flash_attention=True)
    d.update(kw)
    return GPTConfig(**d)


def glm_4p7_flash(**kw) -> GPTConfig:
    """GLM-4.7-Flash (zai-org, config.json, ``glm4_moe_lite``): 47
    layers of latent attention, hidden 2,048, 20 heads whose queries
    pass a rank-768 bottleneck and whose keys and values are made from a
    512-wide latent beside a 64-wide rotary key part shared by all heads
    (queries and keys 192 + 64, values 256), RMSNorm (eps 1e-5), RoPE
    (theta 1e6), no biases, untied head, vocabulary 154,880; layer 0 a
    SwiGLU MLP of width 10,240, every other layer 64 SwiGLU experts of
    width 1,536, 4 a token chosen by sigmoid scores plus a selection
    bias and weighted by the scores normalised over the chosen and
    scaled 1.8, beside one shared expert of the same width. The
    multi-token-prediction module is not built. ``num_layers`` keeps
    the leading layers; ``moe_num_experts`` (with ``moe_expert_offset``)
    is the share of the 64 experts held, ``vocab_size`` the rows of the
    vocabulary held; ``dtype`` is the parameters'."""
    layers = int(kw.get("num_layers", 47))
    d = dict(vocab_size=154880, hidden_size=2048, num_layers=layers,
             num_heads=20, max_seq_len=202752, intermediate_size=10240,
             norm="rmsnorm", layer_norm_eps=1e-5, bias=False,
             position="rope", rope_theta=1e6, q_lora_rank=768,
             kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
             v_head_dim=256, mlp_kind="swiglu",
             moe_layout=tuple(int(i >= 1) for i in range(layers)),
             moe_num_experts=64, moe_router_experts=64, moe_top_k=4,
             moe_intermediate_size=1536, moe_shared_intermediate_size=1536,
             moe_scoring="sigmoid_norm", moe_routed_scale=1.8,
             moe_selection_bias=True, moe_activation="silu",
             moe_token_block=4096, tie_word_embeddings=False,
             use_flash_attention=True)
    d.update(kw)
    return GPTConfig(**d)


def brumby_14b_base(**kw) -> GPTConfig:
    """Brumby-14B-Base (manifestai, config.json, ``brumby``): 40 layers of
    hidden 5,120 whose attention is power retention of degree 2 (40 query
    heads over 8 K/V heads of 128, q/k RMSNorm, RoPE theta 1e6, a sigmoid
    forget gate a K/V head), a SwiGLU MLP of width 17,408, RMSNorm (eps
    1e-6), no biases, an untied head over 151,936 rows: 14.77 B
    parameters. ``num_layers`` keeps the leading layers; ``dtype`` is the
    parameters'."""
    d = dict(vocab_size=151936, hidden_size=5120, num_layers=40,
             num_heads=40, num_kv_heads=8, head_dim=128, max_seq_len=32768,
             intermediate_size=17408, norm="rmsnorm", layer_norm_eps=1e-6,
             bias=False, position="rope", rope_theta=1e6, qk_norm=True,
             mlp_kind="swiglu", retention_degree=2, retention_gate=True,
             tie_word_embeddings=False)
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
      heads * head_dim]`` Tensors for the module stack, or ONE stacked
      ``[num_layers, num_pages, page_size, heads * head_dim]`` Tensor
      for ``GPTStackedTransformer`` (the layout is
      ``ops.paged_attention.kv_pool_shape``'s). Page 0 is the trash page
      (ops/paged_attention.py). A latent attention's layer has its one
      latent pool in ``k`` (``latent_pool_shape``) and ``()`` in ``v``.
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

    ``logits_at`` ([B] int32, or None for every position): the one
    position a row whose logits the caller wants; the head is then
    applied to that position's hidden state alone and the logits come
    back ``[B, 1, vocab]`` (a prefill's last real position: no
    ``[B, S, vocab]`` array exists). ``aux`` is a dict the forward
    fills with whole-number arrays beside the logits (an expert
    layer's ``moe`` counters, one entry a layer), or None.

    ``use_pallas`` names who attends (the fused kernels of
    ops/pallas_paged_attention.py or the pure-JAX body) for every
    layer of this forward; None leaves it to
    ``ops.paged_attention.kernel_by_default`` at trace time. The
    serving decoder always pins it (model_fns.CachedDecoder), so its
    fingerprint says what its executables hold.
    """

    __slots__ = ("kind", "page_size", "k", "v", "block_tables",
                 "ctx_len", "valid", "positions", "use_pallas", "mesh",
                 "logits_at", "aux")

    def __init__(self, kind, page_size, k, v, block_tables, ctx_len,
                 valid, positions, use_pallas=None, mesh=None,
                 logits_at=None, aux=None):
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
        self.logits_at = logits_at
        self.aux = aux

    def layer_view(self, k, v, block_tables=None):
        """This cache as one layer sees it: its own pools and, for a
        layer kind with a table of its own, that table."""
        return GPTKVCache(
            self.kind, self.page_size, k, v,
            self.block_tables if block_tables is None else block_tables,
            self.ctx_len, self.valid, self.positions,
            use_pallas=self.use_pallas, mesh=self.mesh, aux=self.aux)


def _prefill_in_row_blocks(backbone, input_ids, cache, per: int):
    """A served prefill of more rows than ``per``, ``per`` rows at a time
    through every layer of ``backbone``: a scan over row blocks whose
    carry is the pools (a row's pages, ring and slot are its own, so the
    blocks' writes never meet). Returns ``(h [R, 1, hidden], (k', v'))``,
    ``h`` at each row's ``cache.logits_at``. The expert counters of the
    blocks are summed into ``cache.aux`` (exact for ``assignments`` and
    ``local_assignments``; ``experts_touched`` and ``max_expert_load``
    then count an expert once a block)."""
    import jax.numpy as jnp

    def wrap(tree):
        return jax.tree_util.tree_map(
            lambda a: Tensor(a, stop_gradient=True), tree)

    def data(tree):
        return jax.tree_util.tree_map(
            lambda t: t._data, tree,
            is_leaf=lambda t: isinstance(t, Tensor))

    rows = input_ids.shape[0]
    n = -(-rows // per)

    def blocks(t):
        a = t._data
        a = jnp.pad(a, [(0, n * per - rows)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape(n, per, *a.shape[1:])

    def block(pools, fed):
        ids, tables, lens, valid, positions, at = fed
        sub = GPTKVCache(
            "prefill", cache.page_size, *wrap(pools), *wrap(
                (tables, lens, valid, positions)),
            use_pallas=cache.use_pallas, mesh=cache.mesh, aux={})
        h, pools = backbone(Tensor(ids, stop_gradient=True), cache=sub)
        h = jnp.take_along_axis(
            h._data, at.astype(jnp.int32)[:, None, None], axis=1)
        counted = sub.aux.get("moe", [])
        return data(pools), (h, jnp.stack(counted) if counted
                             else jnp.zeros((0,), jnp.int32))

    pools, (h, counted) = jax.lax.scan(
        block, data((cache.k, cache.v)),
        tuple(blocks(t) for t in (
            input_ids, cache.block_tables, cache.ctx_len, cache.valid,
            cache.positions, cache.logits_at)))
    if cache.aux is not None and counted.shape[-1]:
        cache.aux.setdefault("moe", []).extend(jnp.sum(counted, axis=0))
    h = h.reshape(n * per, 1, h.shape[-1])[:rows]
    return Tensor(h, stop_gradient=True), wrap(pools)


def cached_hidden(backbone, input_ids, cache, block_tokens: int = 0):
    """``(h, (k', v'))`` of a cached forward through ``backbone``, ``h``
    at each row's ``cache.logits_at`` where the cache names one. With
    ``block_tokens``, a served prefill of more rows than ``block_tokens``
    positions buy runs whole rows at a time through all the layers
    (``_prefill_in_row_blocks``): what it keeps beside the pools is one
    block's, however many rows the dispatch holds."""
    rows, length = input_ids.shape
    per = max(1, block_tokens // length)
    if block_tokens and cache.kind == "prefill" \
            and cache.logits_at is not None and rows > per:
        return _prefill_in_row_blocks(backbone, input_ids, cache, per)
    h, pools = backbone(input_ids, cache=cache)
    if cache.logits_at is not None:
        # the head meets one position a row, not the window
        import jax.numpy as jnp
        h = apply_op(
            "take_positions",
            lambda h, at: jnp.take_along_axis(
                h, at.astype(jnp.int32)[:, None, None], axis=1),
            h, cache.logits_at)
    return h, pools


class GPTEmbeddings(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size)
        init = I.Normal(std=config.initializer_range)
        # rotary layers place positions inside attention (or nowhere)
        self.position_embeddings = create_parameter_with_attr(
            [config.max_seq_len, config.hidden_size], self._dtype, None,
            False, default_initializer=init) \
            if config.position == "learned" else None
        self.dropout = Dropout(config.dropout)

    def forward(self, input_ids, positions=None):
        seq_len = input_ids.shape[-1]
        h = self.word_embeddings(input_ids)
        if self.position_embeddings is None:
            return _seq_constraint(self.dropout(h))
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


def _rms_norm(x, w, eps):
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + jnp.float32(eps))
    return (y * w.astype(jnp.float32)).astype(x.dtype)


class GPTRMSNorm(Layer):
    """``x * rsqrt(mean(x^2) + eps) * w``, computed in float32."""

    def __init__(self, size: int, eps: float, dtype: str):
        super().__init__()
        self.eps = float(eps)
        self.weight = create_parameter_with_attr(
            [size], dtype, None, False, default_initializer=I.Constant(1.0))

    def forward(self, x):
        return apply_op("rms_norm", _rms_norm, x, self.weight, eps=self.eps)


def _norm(config: GPTConfig):
    if config.norm == "rmsnorm":
        return GPTRMSNorm(config.hidden_size, config.layer_norm_eps,
                          config.dtype or "float32")
    return LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)


def _rope(x, positions, theta):
    """Rotate-half RoPE over the whole head: pairs ``(i, i + D/2)``
    turn by ``positions * theta^(-2i/D)``. x: [B, S, H, D]; positions:
    [B, S]. Angles and rotation in float32."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    inv = jnp.float32(theta) ** (
        -jnp.arange(half, dtype=jnp.float32) / jnp.float32(half))
    ang = positions.astype(jnp.float32)[..., None] * inv      # [B, S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
    return (x32 * cos + turned * sin).astype(x.dtype)


class GPTGroupedAttention(Layer):
    """Attention for what ``GPTAttention`` cannot express: fewer K/V
    heads than query heads, a head size of its own, rotary or no
    positions, a sliding window, no biases, RMSNorm on q and k (over a
    head, before the positions). Separate q/k/v projections (each
    splits over 'mp' by whole heads)."""

    def __init__(self, config: GPTConfig, layer: int):
        super().__init__()
        self.num_heads = config.num_heads
        self.num_kv_heads = config.num_kv_heads
        self.head_dim = config.head_dim
        self.use_flash = config.use_flash_attention
        self.rope_theta = config.rope_theta if config.layer_rope(layer) \
            else None
        self.window = config.layer_window(layer)
        dt = config.dtype or "float32"
        init = I.Normal(std=config.initializer_range)
        qd = self.num_heads * self.head_dim
        kvd = self.num_kv_heads * self.head_dim

        def mk(shape, spec, bias=False):
            p = create_parameter_with_attr(
                shape, dt, None, bias, default_initializer=I.Constant(0.0)
                if bias else init)
            p.dist_spec = spec
            return p

        h = config.hidden_size
        self.q_w = mk([h, qd], (None, "mp"))
        self.k_w = mk([h, kvd], (None, "mp"))
        self.v_w = mk([h, kvd], (None, "mp"))
        self.out_w = mk([qd, h], ("mp", None))
        self.has_bias = bool(config.bias)
        if self.has_bias:
            self.q_b = mk([qd], ("mp",), True)
            self.k_b = mk([kvd], ("mp",), True)
            self.v_b = mk([kvd], ("mp",), True)
            self.out_b = mk([h], (None,), True)
        self.qk_norm_eps = float(config.layer_norm_eps) \
            if config.qk_norm else None
        if config.qk_norm:
            ones = I.Constant(1.0)
            self.q_norm_w = create_parameter_with_attr(
                [self.head_dim], dt, None, False, default_initializer=ones)
            self.k_norm_w = create_parameter_with_attr(
                [self.head_dim], dt, None, False, default_initializer=ones)

    def _biases(self):
        return [self.q_b, self.k_b, self.v_b, self.out_b] \
            if self.has_bias else []

    def _norms(self):
        return [self.q_norm_w, self.k_norm_w] \
            if self.qk_norm_eps is not None else []

    def _qkv(self, x, positions, q_w, k_w, v_w, extra):
        """``(q [B, S, H, D], k, v [B, S, Hk, D])`` of ``x``: the three
        projections, their biases, the q and k norms and the positions
        (``positions`` None: 0 .. S-1). ``extra``: the biases, then the
        q and k norms' weights."""
        import jax.numpy as jnp
        nh, nkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        theta, norm_eps = self.rope_theta, self.qk_norm_eps
        nb = len(self._biases())
        biases, norms = extra[:nb], extra[nb:]
        b, s, _ = x.shape
        q, k, v = x @ q_w, x @ k_w, x @ v_w
        if self.has_bias:
            q, k, v = q + biases[0], k + biases[1], v + biases[2]
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        if norm_eps is not None:
            q = _rms_norm(q, norms[0], norm_eps)
            k = _rms_norm(k, norms[1], norm_eps)
        if theta is not None:
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(s, dtype=jnp.int32), (b, s))
            q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        return q, k, v

    def forward(self, x, kv_cache=None):
        nh, hd = self.num_heads, self.head_dim
        window, use_flash = self.window, self.use_flash
        has_bias = self.has_bias
        qkv = self._qkv

        def out(a, out_w, biases):
            b, s = a.shape[:2]
            o = a.reshape(b, s, nh * hd) @ out_w
            return o + biases[3] if has_bias else o

        extra = self._biases() + self._norms()
        if kv_cache is None:
            def fn(x, q_w, k_w, v_w, out_w, *extra):
                from ..ops.flash_attention import attention_bshd
                q, k, v = qkv(x, None, q_w, k_w, v_w, extra)
                a = attention_bshd(q, k, v, causal=True,
                                   scale=1.0 / math.sqrt(hd),
                                   use_flash=use_flash, window=window)
                return out(a, out_w, extra)
            return apply_op("grouped_attention", fn, x, self.q_w, self.k_w,
                            self.v_w, self.out_w, *extra)

        from ..ops.paged_attention import paged_attention_update
        k_leaves, pool_def = jax.tree_util.tree_flatten(kv_cache.k)
        v_leaves, _ = jax.tree_util.tree_flatten(kv_cache.v)
        nk, ne = len(k_leaves), len(extra)

        def fn(x, tables, ctx, valid, positions, q_w, k_w, v_w, out_w,
               *rest, **kw):
            extra, pool_leaves = rest[:ne], rest[ne:]
            kp = jax.tree_util.tree_unflatten(pool_def, pool_leaves[:nk])
            vp = jax.tree_util.tree_unflatten(pool_def, pool_leaves[nk:])
            q, k, v = qkv(x, positions, q_w, k_w, v_w, extra)
            a, kp2, vp2 = paged_attention_update(
                q, k, v, kp, vp, tables, ctx, valid, positions,
                window=window, **kw)
            return (out(a, out_w, extra),
                    *jax.tree_util.tree_leaves(kp2),
                    *jax.tree_util.tree_leaves(vp2))

        res = apply_op(
            "paged_attention", fn, x, kv_cache.block_tables,
            kv_cache.ctx_len, kv_cache.valid, kv_cache.positions,
            self.q_w, self.k_w, self.v_w, self.out_w, *extra,
            *k_leaves, *v_leaves, page_size=kv_cache.page_size,
            kind=kv_cache.kind, use_flash=use_flash,
            use_pallas=kv_cache.use_pallas, mesh=kv_cache.mesh)
        k_pool = jax.tree_util.tree_unflatten(pool_def, res[1:1 + nk])
        v_pool = jax.tree_util.tree_unflatten(pool_def, res[1 + nk:])
        return res[0], k_pool, v_pool


class GPTLatentAttention(Layer):
    """Multi-head latent attention (DeepSeek-V2), under the scope
    ``mla``. With ``h`` the normed input:

    - ``q = RMSNorm(h Wqa) Wqb`` (sub-scope ``q_proj``), per head
      ``[nope | rope]``, RoPE on the rope part;
    - ``[c | k_pe] = h Wkva``, ``c = RMSNorm(c)``, RoPE on ``k_pe``, one
      key part all heads share (``kv_proj``): ``[c | k_pe]`` is all the
      cache keeps of a token, ``kv_lora_rank + qk_rope_head_dim`` values
      for every head;
    - per head ``[k_nope | v] = c Wkvb``, ``k = [k_nope | k_pe]``,
      scores ``q . k / sqrt(nope + rope)``, causal;
      ``out = concat(softmax v) Wo`` (``out_proj``).

    A prefill (and a call without a cache) computes that as written,
    every head's keys and values made from the latent (``kv_proj``). A
    decode step computes the same function in absorbed form: each
    head's ``q_nope`` through its key up-projection (``absorb``) meets
    the cached rows directly, its softmax-weighted sum of latents goes
    through its value up-projection (``v_up``). The cache's write and
    the attention over it are ``ops.paged_attention``'s
    (``paged_latent_attention_update``)."""

    def __init__(self, config: GPTConfig, layer: int):
        super().__init__()
        self.num_heads = config.num_heads
        self.nope, self.rope = config.qk_nope_head_dim, \
            config.qk_rope_head_dim
        self.v_dim, self.latent = config.v_head_dim, config.kv_lora_rank
        self.eps = float(config.layer_norm_eps)
        self.rope_theta = config.rope_theta
        self.use_flash = config.use_flash_attention
        dt = config.dtype or "float32"
        init = I.Normal(std=config.initializer_range)
        h, nh = config.hidden_size, self.num_heads

        def mk(shape, spec=None, ones=False):
            p = create_parameter_with_attr(
                shape, dt, None, False,
                default_initializer=I.Constant(1.0) if ones else init)
            if spec is not None:
                p.dist_spec = spec
            return p

        self.q_a_w = mk([h, config.q_lora_rank])
        self.q_a_norm_w = mk([config.q_lora_rank], ones=True)
        self.q_b_w = mk([config.q_lora_rank, nh * (self.nope + self.rope)],
                        (None, "mp"))
        self.kv_a_w = mk([h, config.latent_width])
        self.kv_a_norm_w = mk([self.latent], ones=True)
        self.kv_b_w = mk([self.latent, nh * (self.nope + self.v_dim)],
                         (None, "mp"))
        self.out_w = mk([nh * self.v_dim, h], ("mp", None))

    def _weights(self):
        return [self.q_a_w, self.q_a_norm_w, self.q_b_w, self.kv_a_w,
                self.kv_a_norm_w, self.kv_b_w, self.out_w]

    def forward(self, x, kv_cache=None):
        import jax.numpy as jnp
        nh, nope, rope, dv, lat = self.num_heads, self.nope, self.rope, \
            self.v_dim, self.latent
        eps, theta, use_flash = self.eps, self.rope_theta, self.use_flash
        scale = 1.0 / math.sqrt(nope + rope)
        scope = jax.named_scope

        def project(x, positions, q_a, q_norm, q_b, kv_a, kv_norm):
            """``(q_nope, q_pe, c, k_pe)``: [B, S, H, nope], [B, S, H,
            rope], [B, S, latent], [B, S, 1, rope]."""
            b, s, _ = x.shape
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(s, dtype=jnp.int32), (b, s))
            with scope("mla"):
                with scope("q_proj"):
                    q = (_rms_norm(x @ q_a, q_norm, eps) @ q_b).reshape(
                        b, s, nh, nope + rope)
                    q_pe = _rope(q[..., nope:], positions, theta)
                with scope("kv_proj"):
                    ckr = x @ kv_a
                    c = _rms_norm(ckr[..., :lat], kv_norm, eps)
                    k_pe = _rope(ckr[..., None, lat:], positions, theta)
            return q[..., :nope], q_pe, c, k_pe

        def expand(q_nope, q_pe, c, k_pe, kv_b):
            """Every head's query, key and value, [B, S, H, D]."""
            b, s = c.shape[:2]
            with scope("mla"), scope("kv_proj"):
                kv = (c @ kv_b).reshape(b, s, nh, nope + dv)
                k = jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(k_pe, (b, s, nh, rope))], -1)
            return jnp.concatenate([q_nope, q_pe], -1), k, kv[..., nope:]

        def out(o, out_w):
            b, s = o.shape[:2]
            with scope("mla"), scope("out_proj"):
                return o.reshape(b, s, nh * dv) @ out_w

        if kv_cache is None:
            def fn(x, q_a, q_norm, q_b, kv_a, kv_norm, kv_b, out_w):
                from ..ops.flash_attention import attention_bshd
                parts = project(x, None, q_a, q_norm, q_b, kv_a, kv_norm)
                o = attention_bshd(*expand(*parts, kv_b), causal=True,
                                   scale=scale, use_flash=use_flash)
                return out(o, out_w)
            return apply_op("latent_attention", fn, x, *self._weights())

        from ..ops.paged_attention import paged_latent_attention_update
        leaves, pool_def = jax.tree_util.tree_flatten(kv_cache.k)

        def fn(x, tables, ctx, valid, positions, q_a, q_norm, q_b, kv_a,
               kv_norm, kv_b, out_w, *pool_leaves, page_size, kind,
               use_pallas):
            pool = jax.tree_util.tree_unflatten(pool_def, pool_leaves)
            q_nope, q_pe, c, k_pe = project(x, positions, q_a, q_norm, q_b,
                                            kv_a, kv_norm)
            latent = jnp.concatenate([c, k_pe[:, :, 0]], -1)
            kw = dict(page_size=page_size, kind=kind, scale=scale,
                      value_dim=lat, use_pallas=use_pallas)
            if kind == "prefill":
                o, pool = paged_latent_attention_update(
                    None, latent, pool, tables, ctx, valid, positions,
                    expanded=expand(q_nope, q_pe, c, k_pe, kv_b),
                    use_flash=use_flash, **kw)
            else:
                w = kv_b.reshape(lat, nh, nope + dv)
                with scope("mla"), scope("absorb"):
                    q = jnp.concatenate([jnp.einsum(
                        "bshd,chd->bshc", q_nope, w[..., :nope],
                        preferred_element_type=jnp.float32).astype(
                            q_nope.dtype), q_pe], -1)
                u, pool = paged_latent_attention_update(
                    q, latent, pool, tables, ctx, valid, positions, **kw)
                with scope("mla"), scope("v_up"):
                    o = jnp.einsum("bshc,chd->bshd", u, w[..., nope:])
            return (out(o, out_w), *jax.tree_util.tree_leaves(pool))

        res = apply_op(
            "paged_attention", fn, x, kv_cache.block_tables,
            kv_cache.ctx_len, kv_cache.valid, kv_cache.positions,
            *self._weights(), *leaves, page_size=kv_cache.page_size,
            kind=kv_cache.kind, use_pallas=kv_cache.use_pallas)
        return res[0], jax.tree_util.tree_unflatten(pool_def, res[1:]), \
            kv_cache.v


class GPTPowerRetention(GPTGroupedAttention):
    """Power retention of degree 2 in attention's place (``ops/retention.py``):
    ``GPTGroupedAttention``'s projections, q/k norms and positions, a
    forget gate a K/V head ``sigmoid(u . w_g + b_g)`` from the layer's
    normed input, and the squared scaled dot product over the gated past.
    A sequence keeps one slot of two pools (``S``, ``z``) a layer: the
    ``state`` kind; the slot is the last column of its block-table row."""

    CHUNK = 128          # positions a prefill's chunk computes at once

    def __init__(self, config: GPTConfig, layer: int):
        super().__init__(config, layer)
        self.eps = float(config.retention_eps)
        self.gated = bool(config.retention_gate)
        if self.gated:
            dt = config.dtype or "float32"
            self.gate_w = create_parameter_with_attr(
                [config.hidden_size, self.num_kv_heads], dt, None, False,
                default_initializer=I.Normal(std=config.initializer_range))
            # a bias of 3 to 8: gates of about 0.95 to 0.9997 at
            # initialisation, so a state carries from tens to thousands
            # of positions
            self.gate_b = create_parameter_with_attr(
                [self.num_kv_heads], dt, None, False,
                default_initializer=I.Uniform(3.0, 8.0))

    def _weights(self):
        return [self.q_w, self.k_w, self.v_w, self.out_w, *self._biases(),
                *self._norms()] + (
            [self.gate_w, self.gate_b] if self.gated else [])

    def _project(self, u, positions, weights):
        """``(q [B, S, Hk, G, d], k, v [B, S, Hk, d], log gamma [B, S,
        Hk] float32)`` of the normed input ``u``."""
        import jax.numpy as jnp
        nkv = self.num_kv_heads
        q_w, k_w, v_w, _, *rest = weights
        extra = len(self._biases()) + len(self._norms())
        with jax.named_scope("retention"), jax.named_scope("qkv"):
            b, s, _ = u.shape
            q, k, v = self._qkv(u, positions, q_w, k_w, v_w, rest[:extra])
            if self.gated:
                log_gate = jax.nn.log_sigmoid(
                    jnp.dot(u, rest[extra],
                            preferred_element_type=jnp.float32)
                    + rest[extra + 1].astype(jnp.float32))
            else:
                log_gate = jnp.zeros((b, s, nkv), jnp.float32)
        return q.reshape(b, s, nkv, self.num_heads // nkv, self.head_dim), \
            k, v, log_gate

    def _out(self, y, out_w):
        b, s = y.shape[:2]
        with jax.named_scope("retention"), jax.named_scope("out"):
            return y.reshape(b, s, -1) @ out_w

    def forward(self, x, kv_cache=None):
        import jax.numpy as jnp

        from ..ops.paged_attention import kernel_by_default
        from ..ops.retention import retention_decode_pools, retention_prefill
        from ..ops.ssm import write_slots
        from .state_cache import split_state_column
        chunk, eps = self.CHUNK, self.eps
        if kv_cache is None:
            def fn(x, *weights):
                q, k, v, g = self._project(x, None, weights)
                y, _, _ = retention_prefill(
                    q, k, v, g, jnp.ones(x.shape[:2], bool), chunk=chunk,
                    eps=eps)
                return self._out(y, weights[3])
            return apply_op("power_retention", fn, x, *self._weights())
        kind = kv_cache.kind
        # who advances the states: the decoder pins it as for attention
        use_pallas = kernel_by_default(kv_cache.page_size) \
            if kv_cache.use_pallas is None else bool(kv_cache.use_pallas)
        if kind == "chunked":
            raise NotImplementedError(
                "a retention layer cannot continue a window from a slot's "
                "state (prefill_chunked, verify): the 'state' kind of "
                "kv_cache_spec()")

        def fn(x, tables, valid, positions, s_pool, z_pool, *weights):
            slots = split_state_column(tables, paged=False)[1].astype(
                jnp.int32)
            q, k, v, g = self._project(x, positions, weights)
            if kind == "prefill":
                y, s, z = retention_prefill(q, k, v, g, valid, chunk=chunk,
                                            eps=eps)
                # a dead row's table is zeros: the trash slot
                with jax.named_scope("retention"), \
                        jax.named_scope("chunk_scan"):
                    s_pool = write_slots(s_pool, slots, s)
                    z_pool = write_slots(z_pool, slots, z)
                return self._out(y, weights[3]), s_pool, z_pool
            y, s_pool, z_pool = retention_decode_pools(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], slots, valid[:, 0],
                s_pool, z_pool, eps=eps, use_pallas=use_pallas)
            return self._out(y[:, None], weights[3]), s_pool, z_pool

        return apply_op("power_retention", fn, x, kv_cache.block_tables,
                        kv_cache.valid, kv_cache.positions, kv_cache.k,
                        kv_cache.v, *self._weights())


class GPTExpertMLP(Layer):
    """Dropless top-k gated experts (``ops.moe.dropless_moe``): the
    weights of one projection of the experts held here are one stacked
    array, the router is as wide as the model has experts (with a
    selection bias as wide where the config asks for one), and a shared
    expert (every token's) has three plain weights of its own."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        dt = config.dtype or "float32"
        init = I.Normal(std=config.initializer_range)
        h, e, i = (config.hidden_size, config.moe_num_experts,
                   config.moe_intermediate_size)
        self.share = e != config.moe_router_experts
        # what dropless_moe is told beyond the weights (the fields'
        # defaults are the op's own: SmallThinker's call is what it was)
        self.options = dict(
            top_k=config.moe_top_k, scoring=config.moe_scoring,
            scale=config.moe_routed_scale, activation=config.moe_activation,
            offset=config.moe_expert_offset,
            token_block=config.moe_token_block)

        def mk(shape):
            return create_parameter_with_attr(
                shape, dt, None, False, default_initializer=init)

        self.router_w = mk([h, config.moe_router_experts])
        self.gate_w = mk([e, h, i])
        self.up_w = mk([e, h, i])
        self.down_w = mk([e, i, h])
        si = config.moe_shared_intermediate_size
        self.has_shared = bool(si)
        if si:
            self.shared_gate_w = mk([h, si])
            self.shared_up_w = mk([h, si])
            self.shared_down_w = mk([si, h])
        self.has_bias = config.moe_selection_bias
        if self.has_bias:
            # zero, as a trained bias starts: each seed's routing is then
            # what it would be without one
            self.router_bias = create_parameter_with_attr(
                [config.moe_router_experts], dt, None, False,
                default_initializer=I.Constant(0.0))

    def forward(self, x, router_in, valid=None):
        """x, router_in: [B, S, H]; valid: [B, S] bool or None. Returns
        ``(out [B, S, H], stats int32 [3 or 6])``: assignments, held
        experts touched, the fullest held expert's rows and, where this
        layer holds a share of the experts, the assignments its own
        experts computed, and whether its grouped products were handed
        the capacity's rows (``narrow_calls``) or all (``wide_calls``)."""
        import jax.numpy as jnp

        from ..ops.moe import dropless_moe
        options, share, has_shared, has_bias = self.options, self.share, \
            self.has_shared, self.has_bias

        def fn(x, router_in, valid, *weights):
            b, s, h = x.shape
            extra = {"shared": weights[4:7]} if has_shared else {}
            if has_bias:
                extra["bias"] = weights[-1]
            out, stats = dropless_moe(
                x.reshape(b * s, h), router_in.reshape(b * s, h),
                *weights[:4], **options, **extra,
                valid=None if valid is None else valid.reshape(b * s))
            counted = [stats["assignments"], stats["experts_touched"],
                       stats["max_expert_load"]]
            if share:
                counted += [stats["local_assignments"],
                            stats["narrow_calls"], stats["wide_calls"]]
            return out.reshape(b, s, h), jnp.stack(counted).astype(
                jnp.int32)

        shared = [self.shared_gate_w, self.shared_up_w,
                  self.shared_down_w] if has_shared else []
        bias = [self.router_bias] if has_bias else []
        return apply_op("moe", fn, x, router_in, valid, self.router_w,
                        self.gate_w, self.up_w, self.down_w, *shared, *bias)


class GPTGatedMLP(Layer):
    """``(silu(x Wg) * (x Wu)) Wd``, no biases: the dense SwiGLU MLP
    (column-, column- and row-split over 'mp'), under the scope
    ``mlp_dense``."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        dt = config.dtype or "float32"
        init = I.Normal(std=config.initializer_range)
        h, i = config.hidden_size, config.intermediate_size

        def mk(shape, spec):
            p = create_parameter_with_attr(
                shape, dt, None, False, default_initializer=init)
            p.dist_spec = spec
            return p

        self.gate_w = mk([h, i], (None, "mp"))
        self.up_w = mk([h, i], (None, "mp"))
        self.down_w = mk([i, h], ("mp", None))

    def forward(self, x):
        def fn(x, w_gate, w_up, w_down):
            with jax.named_scope("mlp_dense"):
                return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down

        return apply_op("gated_mlp", fn, x, self.gate_w, self.up_w,
                        self.down_w)


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
        h = self.fc_in(x)
        if x.shape[-2] > 1:
            # More than one position a row (a prefill, a chunk, a verify
            # window): the barrier makes the up-projection an array of
            # the program. Without it the TPU's compiler may put fc_in's
            # product inside fc_out's fusion as a producer and compute
            # it again for every output tile (1.3B prefill[1,256] took
            # 108 ms for it where [1,512] takes 16: PERF.md, PR 32). The
            # GELU stays free to fuse into fc_out. A decode step's
            # activation is a single tile whichever way it is fused, and
            # the barrier would cost it one fusion boundary a layer.
            h = apply_op("optimization_barrier",
                         jax.lax.optimization_barrier, h)
        return self.dropout(self.fc_out(F.gelu(h, approximate=True)))


class GPTDecoderLayer(Layer):
    """Pre-LN decoder block (the MFU-critical fused pattern the reference
    implements as fused_attention/fused_feedforward CUDA ops —
    /root/reference/paddle/fluid/operators/fused/; here XLA fuses).
    The config's fields say which modules it is made of: the defaults
    give the GPT-2/GPT-3 block."""

    def __init__(self, config: GPTConfig, layer: int = 0):
        super().__init__()
        self.ln_1 = _norm(config)
        if config.retention_degree:
            self.attn = GPTPowerRetention(config, layer)
        elif config.kv_lora_rank:
            self.attn = GPTLatentAttention(config, layer)
        elif config.classic_attention:
            self.attn = GPTAttention(config)
        else:
            self.attn = GPTGroupedAttention(config, layer)
        self.ln_2 = _norm(config)
        self.experts = config.layer_experts(layer)
        self.mlp = GPTExpertMLP(config) if self.experts else (
            GPTGatedMLP(config) if config.mlp_kind == "swiglu"
            else GPTMLP(config))
        self.router_reads_attention_input = \
            config.moe_router_input == "attention_input"
        if config.dtype:
            # the modules shared with the GPT presets are born float32
            self.to(dtype=config.dtype)

    def _mlp(self, x, h, kv_cache=None):
        """``x + mlp(ln_2(x))``; ``h`` is the attention's normed input,
        which a router "placed before attention" reads."""
        m = self.ln_2(x)
        if not self.experts:
            return x + self.mlp(m)
        y, stats = self.mlp(
            m, h if self.router_reads_attention_input else m,
            valid=None if kv_cache is None else kv_cache.valid)
        if kv_cache is not None and kv_cache.aux is not None:
            kv_cache.aux.setdefault("moe", []).append(stats._data)
        return x + y

    def forward(self, x, kv_cache=None):
        h = self.ln_1(x)
        if kv_cache is not None:
            a, k_pool, v_pool = self.attn(h, kv_cache=kv_cache)
            x = self._mlp(x + a, h, kv_cache)
            return _seq_constraint(x), k_pool, v_pool
        x = self._mlp(x + self.attn(h), h)
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
            self.layers = LayerList([GPTDecoderLayer(config, i)
                                     for i in range(config.num_layers)])
        self.ln_f = _norm(config)
        if config.dtype:
            self.embeddings.to(dtype=config.dtype)
            self.ln_f.to(dtype=config.dtype)

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
            tables = self._tables_by_layer(cache)
            for i, layer in enumerate(self.layers):
                h, k_i, v_i = layer(h, kv_cache=cache.layer_view(
                    cache.k[i], cache.v[i], tables[i]))
                k_new.append(k_i)
                v_new.append(v_i)
        return self.ln_f(h), (k_new, v_new)

    def _tables_by_layer(self, cache: GPTKVCache):
        """Each layer's block table. Layers that attend the whole
        context share the one the cache brings. Where some attend a
        window, the cache's table is both kinds' side by side (the
        engine feeds one array): the last ``ring_pages(window,
        page_size)`` columns are the window layers' ring, the columns
        before them the full-context layers' table."""
        cfg = self.config
        windows = [cfg.layer_window(i) for i in range(cfg.num_layers)]
        if not any(windows):
            return [None] * cfg.num_layers
        from ..ops.paged_attention import ring_pages
        ring = ring_pages(cfg.sliding_window, cache.page_size)
        width = cache.block_tables.shape[1]
        if width <= ring:
            raise ValueError(
                f"block tables of {width} columns leave none beside the "
                f"window layers' ring of {ring}")
        full = cache.block_tables[:, :width - ring]
        window = cache.block_tables[:, width - ring:]
        return [window if w else full for w in windows]

    # -- pipeline segmentation hook (pp_layers.LayerDesc consumers) --
    def pipeline_stages(self):
        return [self.embeddings] + list(self.layers) + [self.ln_f]


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config
        if not config.tie_word_embeddings:
            self.lm_head = Linear(
                config.hidden_size, config.vocab_size, bias_attr=False,
                weight_attr=None if config.classic_attention
                else I.Normal(std=config.initializer_range))
            if config.dtype:
                self.lm_head.to(dtype=config.dtype)
        # tokens of a served prefill computed at a time, whole rows,
        # through all the layers (``cached_hidden``); 0: the whole
        # dispatch at once. A retention layer's prefill keeps a row's
        # state and its chunk's feature maps beside the pools, and a
        # [16, 2048] window at once leaves the compiler 80 MB short of
        # the chip (PERF.md, section 4)
        self.prefill_block_tokens = 1024 if config.retention_degree else 0

    def forward(self, input_ids, cache=None):
        if cache is not None:
            h, pools = cached_hidden(self.gpt, input_ids, cache,
                                     self.prefill_block_tokens)
        else:
            h = self.gpt(input_ids)
        tied = self.config.tie_word_embeddings
        w = self.gpt.embeddings.word_embeddings.weight if tied \
            else self.lm_head.weight
        if str(w._data.dtype) != "float32":
            # weights below float32: the logits leave the product's
            # float32 accumulator unrounded (a bfloat16 logit has 8 bits
            # and ties its neighbours; the host's sampler reads float32)
            import jax.numpy as jnp
            logits = apply_op(
                "lm_head", lambda h, w: jnp.einsum(
                    "bsh,vh->bsv" if tied else "bsh,hv->bsv", h, w,
                    preferred_element_type=jnp.float32), h, w)
        elif tied:
            from ..tensor import linalg
            logits = linalg.matmul(h, w, transpose_y=True)
        else:
            logits = self.lm_head(h)
        if cache is not None:
            return logits, pools
        return logits

    # ---- paged KV-cache plumbing (serving.generation engine) ----
    def _state_slot(self):
        """One slot of a retention layer's two pools (``S``, ``z``), in
        ``retention_state_dtype``."""
        from ..ops.retention import slot_shapes
        cfg = self.config
        return tuple((shape, cfg.retention_state_dtype) for shape in
                     slot_shapes(cfg.num_kv_heads, cfg.head_dim))

    def init_kv_pools(self, num_pages: int, page_size: int, dtype=None,
                      window_pages=None, state_slots=None):
        """Zeroed paged K/V pools shaped for this model: a list of
        per-layer arrays in the pool's resident layout
        (``ops.paged_attention.kv_pool_shape``: heads folded into the
        lanes) for the module stack, or one stacked ``[L, ...]`` pair
        (stacked decoder). Page 0 is the trash page and is never
        allocated.
        A layer that attends a window only has ``window_pages`` pages
        (its sequences hold a ring of ``ring_pages`` each, however long
        they grow; ``PagedKVCache`` sizes it from the lanes), or
        ``num_pages`` where the caller names none.
        ``dtype`` may also be the string ``"int8"``: pools then become
        ``(int8 values, f32 per-slot-per-head scales)`` tuples (see
        ops.paged_attention for the quantized-pool contract). Returns
        raw jax arrays ``(k, v)`` — engine plumbing, not Tensors.
        A retention layer keeps no pages: its pair is the ``S`` and
        ``z`` pools of ``state_slots`` slots (``state_cache.state_pools``;
        slot 0 the trash slot)."""
        import jax.numpy as jnp

        from ..ops.paged_attention import latent_pool_shape, new_kv_pool
        from .state_cache import state_pools
        cfg = self.config
        if cfg.retention_degree:
            pools = [state_pools(self._state_slot(), state_slots)
                     for _ in range(cfg.num_layers)]
            return [s for s, _ in pools], [z for _, z in pools]
        dtype = dtype or \
            self.gpt.embeddings.word_embeddings.weight._data.dtype
        if cfg.kv_lora_rank:
            # latent attention: one row a token for all heads, no V
            if isinstance(dtype, str) and dtype == "int8":
                raise ValueError("a latent pool is kept in a float type: "
                                 "int8 pools are not built for it")
            shape = latent_pool_shape(num_pages, page_size, cfg.latent_width)
            return ([jnp.zeros(shape, dtype) for _ in range(cfg.num_layers)],
                    [()] * cfg.num_layers)

        def mk(n, lead=()):
            return new_kv_pool(n, page_size, cfg.num_kv_heads,
                               cfg.head_dim, dtype, lead=lead)

        if cfg.stacked:
            lead = (cfg.num_layers,)
            return mk(num_pages, lead), mk(num_pages, lead)
        pages = [int(window_pages or num_pages) if cfg.layer_window(i)
                 else int(num_pages) for i in range(cfg.num_layers)]
        return [mk(n) for n in pages], [mk(n) for n in pages]

    def kv_cache_spec(self, kv_dtype: str = "") -> dict:
        """Geometry the decode engine sizes its cache from.
        ``kv_dtype`` ('' = model dtype) adds per-token byte accounting
        so sizing and shardcheck agree on pool cost. ``kinds`` says
        what each kind of layer keeps: ``full`` the whole context,
        ``window`` the last ``window`` positions (absent where no layer
        is of that kind). A latent attention's ``full`` kind says
        ``latent``: the values a token a layer of its one pool, which
        holds one row a token for every head and no V (``num_kv_heads``
        1 and ``head_dim`` the row's lanes, ``latent_pool_shape``, are
        the pool's geometry). Retention layers keep no pages: they are
        the ``state`` kind (``state_cache.state_kind``) and ``full``
        lists no layer."""
        from ..ops.paged_attention import kv_pool_bytes, latent_pool_shape
        from .state_cache import state_kind
        cfg = self.config
        if cfg.retention_degree:
            layers = list(range(cfg.num_layers))
            return {"num_layers": cfg.num_layers,
                    "num_heads": cfg.num_heads,
                    "num_kv_heads": cfg.num_kv_heads,
                    "head_dim": cfg.head_dim,
                    "max_seq_len": cfg.max_seq_len,
                    "stacked": False,
                    "kv_dtype": kv_dtype or "",
                    "kv_bytes_per_token": 0,
                    "kinds": {"full": {"layers": [], "window": None},
                              "state": state_kind(layers,
                                                  self._state_slot())}}
        nh, hd = cfg.num_kv_heads, cfg.head_dim
        per_token = cfg.num_layers * 2 * kv_pool_bytes(
            1, 1, nh, hd, kv_dtype or None)
        windows = [cfg.layer_window(i) for i in range(cfg.num_layers)]
        kinds = {"full": {"layers": [i for i, w in enumerate(windows)
                                     if not w], "window": None}}
        if cfg.kv_lora_rank:
            nh, hd = 1, latent_pool_shape(1, 1, cfg.latent_width)[2]
            kinds["full"]["latent"] = cfg.latent_width
            per_token = cfg.num_layers * kv_pool_bytes(
                1, 1, nh, hd, kv_dtype or str(
                    self.gpt.embeddings.word_embeddings.weight._data.dtype))
        if any(windows):
            kinds["window"] = {
                "layers": [i for i, w in enumerate(windows) if w],
                "window": int(cfg.sliding_window)}
        return {"num_layers": cfg.num_layers,
                "num_heads": cfg.num_heads,
                "num_kv_heads": nh,
                "head_dim": hd,
                "max_seq_len": cfg.max_seq_len,
                "stacked": bool(cfg.stacked),
                "kv_dtype": kv_dtype or "",
                "kv_bytes_per_token": int(per_token),
                "kinds": kinds}

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
