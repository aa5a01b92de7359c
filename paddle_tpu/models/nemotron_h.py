"""Nemotron-H: a decoder whose every layer is ONE mixer (NVIDIA,
``model_type`` ``nemotron_h``; NVIDIA-Nemotron-3-Super-120B-A12B's
``config.json``): ``x = x + mixer_l(RMSNorm(x))``, the mixer of layer
``l`` named by letter ``l`` of ``hybrid_override_pattern``:

- ``M`` a Mamba-2 state-space mixer (``ops/ssm.py``): one projection to
  ``[z | xBC | dt]``, a depthwise causal convolution, the selective
  recurrence over ``mamba_num_heads`` heads, ``y * silu(z)`` normalised
  within each of ``n_groups`` groups, one projection back;
- ``*`` grouped-query attention with no positional encoding
  (``GPTGroupedAttention``, the module the other grouped-head decoders
  use);
- ``E`` sigmoid-routed ungated ``relu(x W1)^2 W2`` experts that live in
  a latent of ``moe_latent_size`` (one projection down before them, one
  up after their weighted sum) beside a shared expert that reads the
  hidden state (``ops/moe.py:dropless_moe``; a decode step wide enough
  to touch every held expert computes them all, unsorted:
  ``_every_held_expert``).

Then a final RMSNorm and an untied head. No biases but the
convolution's. The multi-token-prediction module is not built.

What a layer keeps a sequence differs by kind (``kv_cache_spec()``
``kinds``): a ``*`` layer paged keys and values (``full``), an ``M``
layer one slot of a convolution-tail pool and of a state pool
(``state``: constant size however long the sequence), an ``E`` layer
nothing. ``forward(ids, cache=)`` takes the one ``GPTKVCache`` the
serving decoder builds: the pools are per-layer lists (``k[l]``/
``v[l]``: K and V pools, or tail and state pools, or ``()``), and the
block-table row is the full table's columns and then the state slot.

One chip's share of an expert-parallel layer is what the GPT block's
expert layer has: ``moe_num_experts`` experts held from
``moe_expert_offset`` on, of the router's ``moe_router_experts``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import apply_op
from ..nn import initializer as I
from ..nn.initializer_utils import create_parameter_with_attr
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from .gpt import GPTGroupedAttention, GPTRMSNorm, cached_hidden
from .state_cache import split_state_column, state_kind, state_pools

__all__ = ["NemotronHConfig", "NemotronHForCausalLM",
           "nemotron_3_super_120b_a12b"]

NEMOTRON_3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_layers: int = 88             # the leading letters of ``pattern``
    pattern: str = NEMOTRON_3_SUPER_PATTERN
    max_seq_len: int = 262144
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    # ---- '*': grouped-query attention, no positions
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # "always": the flash kernel wherever it supports the shape. A
    # 16-row prefill of 1,024 positions would keep 3 GiB of dense scores
    # beside 3 GiB of the other layers' temporaries and the pools: the
    # chip's compiler refuses it ("Used 15.86G of 15.75G hbm", PR 35)
    use_flash_attention: object = "always"
    # ---- 'M': the state-space mixer
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    mamba_n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    ssm_state_dtype: str = "float32"  # what a slot keeps S in
    # ---- 'E': experts in a latent beside a shared expert
    moe_num_experts: int = 512       # held here, from moe_expert_offset
    moe_router_experts: int = 512
    moe_expert_offset: int = 0
    moe_top_k: int = 22
    moe_routed_scale: float = 5.0
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_intermediate_size: int = 5376
    dtype: str = ""                  # parameters are created in it
    #                                  ('' -> float32)
    # what GPTGroupedAttention reads of a config beyond the sizes
    bias: bool = False
    qk_norm: bool = False
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if not 0 < self.num_layers <= len(self.pattern):
            raise ValueError(
                f"num_layers={self.num_layers} of a pattern of "
                f"{len(self.pattern)} letters")
        if set(self.pattern) - set("M*E"):
            raise ValueError(f"pattern {self.pattern!r}: a layer is "
                             f"'M', '*' or 'E'")
        if self.mamba_num_heads % self.mamba_n_groups:
            raise ValueError("mamba_num_heads must be whole groups")
        if not 0 <= self.moe_expert_offset <= \
                self.moe_router_experts - self.moe_num_experts:
            raise ValueError(
                f"experts {self.moe_expert_offset}.."
                f"{self.moe_expert_offset + self.moe_num_experts - 1} are "
                f"not among the router's {self.moe_router_experts}")

    # ---- what a layer is
    @property
    def kinds(self) -> str:
        """The letters of the layers that are run."""
        return self.pattern[:self.num_layers]

    def layers_of(self, kind: str) -> list:
        return [i for i, c in enumerate(self.kinds) if c == kind]

    def layer_experts(self, layer: int) -> bool:
        return self.kinds[layer] == "E"

    def layer_rope(self, layer: int) -> bool:
        return False

    def layer_window(self, layer: int):
        return None

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups \
            * self.ssm_state_size

    def ssm_sizes(self) -> dict:
        return dict(heads=self.mamba_num_heads,
                    head_dim=self.mamba_head_dim,
                    groups=self.mamba_n_groups, state=self.ssm_state_size)

    def state_bytes_per_slot(self, dtype_bytes: int = 2) -> int:
        """Bytes one sequence holds of one ``M`` layer: the tail in the
        parameters' type and the state in ``ssm_state_dtype``."""
        return int((self.conv_kernel - 1) * self.conv_channels * dtype_bytes
                   + self.mamba_inner * self.ssm_state_size
                   * jnp.dtype(self.ssm_state_dtype).itemsize)

    def num_params(self) -> int:
        """Parameters of the model these fields describe, reckoned
        without building it."""
        h = self.hidden_size
        inner, heads = self.mamba_inner, self.mamba_num_heads
        mamba = h * (inner + self.conv_channels + heads) \
            + self.conv_channels * (self.conv_kernel + 1) + 3 * heads \
            + inner + inner * h
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        attention = h * (qd + 2 * kvd) + qd * h
        experts = h * self.moe_router_experts \
            + 2 * h * self.moe_latent_size \
            + 2 * self.moe_num_experts * self.moe_latent_size \
            * self.moe_intermediate_size \
            + 2 * h * self.moe_shared_intermediate_size
        per = {"M": mamba + h, "*": attention + h, "E": experts + h}
        return int(sum(per[c] for c in self.kinds) + 2 * self.vocab_size * h
                   + h)


def nemotron_3_super_120b_a12b(**kw) -> NemotronHConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B (config.json, ``nemotron_h``):
    88 layers, 40 ``M`` (128 heads of 64, state 128, 8 groups, kernel
    4, chunk 128), 8 ``*`` (32 query heads over 2 K/V heads of 128, no
    positions), 40 ``E`` (22 of 512 ungated relu^2 experts of width
    2,688 in a 1,024-wide latent by sigmoid scores normalised over the
    chosen and scaled 5, beside a shared expert of width 5,376), hidden
    4,096, vocabulary 131,072, untied head: 120.67 B parameters.
    ``num_layers`` keeps the leading layers under their published
    kinds; ``moe_num_experts`` (with ``moe_expert_offset``) is the share
    of the 512 experts held, ``vocab_size`` the rows of the vocabulary
    held; ``dtype`` is the parameters'."""
    return NemotronHConfig(**kw)


class _StepBias(I.Initializer):
    """``dt_bias``: the inverse softplus of a step drawn log-uniform in
    ``[lo, hi]`` and floored (Mamba-2's ``time_step_min``/``max``/
    ``floor``)."""

    def __init__(self, lo=0.001, hi=0.1, floor=1e-4):
        self.lo, self.hi, self.floor = lo, hi, floor

    def __call__(self, shape, dtype):
        u = I.Uniform(0.0, 1.0)(shape, jnp.float32)
        step = jnp.maximum(jnp.exp(
            u * (math.log(self.hi) - math.log(self.lo))
            + math.log(self.lo)), self.floor)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)


class _LogUniform(I.Initializer):
    """``A_log``: the log of a uniform draw in ``[lo, hi]``."""

    def __init__(self, lo=1.0, hi=16.0):
        self.lo, self.hi = lo, hi

    def __call__(self, shape, dtype):
        return jnp.log(I.Uniform(self.lo, self.hi)(
            shape, jnp.float32)).astype(dtype)


def _mk(config, shape, init=None):
    return create_parameter_with_attr(
        shape, config.dtype or "float32", None, False,
        default_initializer=init or I.Normal(std=config.initializer_range))


def _dot(a, w):
    """``a @ w`` left in the product's float32 accumulator."""
    return jnp.dot(a, w, preferred_element_type=jnp.float32)


def _every_held_expert(x, router_in, valid, w_router, w_up, w_down, *,
                        top_k: int, scale: float, offset: int):
    """``dropless_moe``'s sum and counts for ungated ``relu2`` experts
    under ``sigmoid_norm`` routing, with EVERY held expert computed for
    every row: two batched products over the experts, weighted by the
    router's weights (0 where an expert was not chosen, lives elsewhere
    or the row is dead), instead of rows sorted by expert. For a decode
    step whose lanes hand each expert a row or more in the mean: such a
    step reads every held expert's weights whichever way it is laid
    out, and a batched product reads them three times as fast as the
    grouped one reads the experts it touches (7.05 GB at 756 GB/s
    against 4 GB at 256, PERF.md section 6, PR 35)."""
    from ..ops.moe import route_sigmoid_norm
    t, n_held = x.shape[0], w_up.shape[0]
    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            experts, weights = route_sigmoid_norm(router_in, w_router,
                                                  top_k, scale)
            # column n_held: an expert that lives elsewhere, a dead row
            experts = jnp.where(
                (experts >= offset) & (experts < offset + n_held),
                experts - offset, n_held)
            live = jnp.ones((t,), bool) if valid is None else valid
            experts = jnp.where(live[:, None], experts, n_held)
        with jax.named_scope("dispatch"):
            mine = jnp.zeros((t, n_held + 1), jnp.float32).at[
                jnp.arange(t)[:, None], experts].add(weights)[:, :n_held]
            group_sizes = jnp.sum(
                experts[:, :, None] == jnp.arange(n_held), axis=(0, 1),
                dtype=jnp.int32)
        with jax.named_scope("experts"):
            up = jnp.einsum(
                "eth,ehi->eti", jnp.broadcast_to(x, (n_held,) + x.shape),
                w_up, preferred_element_type=jnp.float32)
            down = jnp.einsum(
                "eti,eih->eth", jnp.square(jax.nn.relu(up)).astype(x.dtype),
                w_down, preferred_element_type=jnp.float32)
        with jax.named_scope("combine"):
            out = jnp.einsum("eth,te->th", down, mine).astype(x.dtype)
        stats = {"assignments": top_k * jnp.sum(live, dtype=jnp.int32),
                 "local_assignments": jnp.sum(group_sizes),
                 "experts_touched": jnp.sum(group_sizes > 0),
                 "max_expert_load": jnp.max(group_sizes)}
    return out, stats


class NemotronHMamba(Layer):
    """The ``M`` mixer. ``forward`` takes the layer's normed input and,
    with a cache, the layer's tail and state pools; the slot of a row
    is the last column of its block-table row."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = config
        self.sizes = c.ssm_sizes()
        self.chunk, self.eps = c.chunk_size, float(c.layer_norm_eps)
        self.inner, self.channels = c.mamba_inner, c.conv_channels
        heads = c.mamba_num_heads
        self.in_w = _mk(c, [c.hidden_size, self.inner + self.channels
                            + heads])
        # torch's Conv1d default for a depthwise kernel of 4
        self.conv_w = _mk(c, [self.channels, c.conv_kernel],
                          I.Uniform(-0.5, 0.5))
        self.conv_b = _mk(c, [self.channels], I.Uniform(-0.5, 0.5))
        self.dt_bias = _mk(c, [heads], _StepBias())
        self.a_log = _mk(c, [heads], _LogUniform())
        self.d_skip = _mk(c, [heads], I.Constant(1.0))
        self.norm_w = _mk(c, [self.inner], I.Constant(1.0))
        self.out_w = _mk(c, [self.inner, c.hidden_size])

    def _weights(self):
        return [self.in_w, self.conv_w, self.conv_b, self.dt_bias,
                self.a_log, self.d_skip, self.norm_w, self.out_w]

    def _project_in(self, u, in_w):
        with jax.named_scope("ssm"), jax.named_scope("in_proj"):
            zxd = u @ in_w
        return (zxd[..., :self.inner],
                zxd[..., self.inner:self.inner + self.channels],
                zxd[..., self.inner + self.channels:])

    def _project_out(self, y, z, norm_w, out_w):
        from ..ops.ssm import gated_group_norm
        y = gated_group_norm(y, z, norm_w, groups=self.sizes["groups"],
                             eps=self.eps)
        with jax.named_scope("ssm"), jax.named_scope("out_proj"):
            return y @ out_w

    def _window(self, u, valid, lens, weights):
        """A padded window from zero states: ``(out [R, L, hidden],
        tails, states)``."""
        from ..ops.ssm import ssm_prefill
        in_w, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w, out_w = \
            weights
        z, xbc, dt = self._project_in(u, in_w)
        y, tail, s = ssm_prefill(
            xbc, dt, valid, lens, conv_w, conv_b, dt_bias, a_log, d_skip,
            chunk=self.chunk, **self.sizes)
        return self._project_out(y, z, norm_w, out_w), tail, s

    def forward(self, u, kv_cache=None):
        if kv_cache is None:
            def fn(u, *weights):
                r, length = u.shape[:2]
                return self._window(
                    u, jnp.ones((r, length), bool),
                    jnp.full((r,), length, jnp.int32), weights)[0]
            return apply_op("ssm_mixer", fn, u, *self._weights())
        kind = kv_cache.kind
        if kind == "chunked":
            raise NotImplementedError(
                "a state-space layer cannot continue a window from a "
                "slot's state (prefill_chunked, verify): the 'state' "
                "kind of kv_cache_spec()")

        def fn(u, tables, valid, lens, tail_pool, s_pool, *weights):
            slots = split_state_column(tables, paged=False)[1].astype(
                jnp.int32)
            if kind == "prefill":
                from ..ops.ssm import write_slots
                out, tail, s = self._window(u, valid, lens, weights)
                # a dead row's table is zeros: the trash slot
                with jax.named_scope("ssm"), jax.named_scope("scan"):
                    return (out, write_slots(tail_pool, slots, tail),
                            write_slots(s_pool, slots, s))
            from ..ops.ssm import ssm_decode_pools
            in_w, *core, norm_w, out_w = weights
            z, xbc, dt = self._project_in(u[:, 0], in_w)
            y, tail_pool, s_pool = ssm_decode_pools(
                xbc, dt, slots, valid[:, 0], tail_pool, s_pool, *core,
                **self.sizes)
            return (self._project_out(y, z, norm_w, out_w)[:, None],
                    tail_pool, s_pool)

        return apply_op("ssm_mixer", fn, u, kv_cache.block_tables,
                        kv_cache.valid, kv_cache.ctx_len, kv_cache.k,
                        kv_cache.v, *self._weights())


class NemotronHExperts(Layer):
    """The ``E`` mixer: the router and the shared expert read the
    hidden state, the routed experts a latent of it."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = config
        h, e, lat, i = (c.hidden_size, c.moe_num_experts,
                        c.moe_latent_size, c.moe_intermediate_size)
        self.share = e != c.moe_router_experts
        self.options = dict(
            top_k=c.moe_top_k, scoring="sigmoid_norm",
            scale=c.moe_routed_scale, activation="relu2",
            offset=c.moe_expert_offset)
        self.router_w = _mk(c, [h, c.moe_router_experts])
        self.latent_down_w = _mk(c, [h, lat])
        self.expert_up_w = _mk(c, [e, lat, i])
        self.expert_down_w = _mk(c, [e, i, lat])
        self.latent_up_w = _mk(c, [lat, h])
        self.shared_up_w = _mk(c, [h, c.moe_shared_intermediate_size])
        self.shared_down_w = _mk(c, [c.moe_shared_intermediate_size, h])

    def forward(self, u, valid=None, decode=False):
        """u: [B, S, H]; valid: [B, S] bool or None; ``decode``: the
        rows are a decode step's lanes. Returns ``(out [B, S, H], stats
        int32 [3 or 6])`` as ``GPTExpertMLP`` does."""
        from ..ops.moe import dropless_moe
        options, share = self.options, self.share
        # lanes that hand every expert of the router a row or more in
        # the mean touch every held expert: compute them all, unsorted
        every = decode and u.shape[0] * u.shape[1] * options["top_k"] \
            >= self.router_w.shape[1]

        def fn(u, valid, router_w, down_w, w1, w2, up_w, s1, s2):
            b, s, h = u.shape
            rows = u.reshape(b * s, h)
            with jax.named_scope("moe"), jax.named_scope("latent_down"):
                latent = rows @ down_w
            live = None if valid is None else valid.reshape(b * s)
            if every:
                routed, stats = _every_held_expert(
                    latent, rows, live, router_w, w1, w2,
                    top_k=options["top_k"], scale=options["scale"],
                    offset=options["offset"])
            else:
                routed, stats = dropless_moe(
                    latent, rows, router_w, None, w1, w2, **options,
                    valid=live)
            with jax.named_scope("moe"), jax.named_scope("latent_up"):
                # linear: the shares' parts of the routed sum add up
                out = _dot(routed, up_w)
            with jax.named_scope("moe"), jax.named_scope("shared"):
                # whole on every chip of the deployment: counted once
                shared = jnp.square(jax.nn.relu(rows @ s1))
                out = (out + _dot(shared, s2)).astype(u.dtype)
            counted = [stats["assignments"], stats["experts_touched"],
                       stats["max_expert_load"]]
            if share:
                # every held expert computed sorts no rows: neither count
                counted += [stats["local_assignments"],
                            stats.get("narrow_calls", 0),
                            stats.get("wide_calls", 0)]
            return out.reshape(b, s, h), jnp.stack(counted).astype(
                jnp.int32)

        return apply_op(
            "latent_moe", fn, u, valid, self.router_w, self.latent_down_w,
            self.expert_up_w, self.expert_down_w, self.latent_up_w,
            self.shared_up_w, self.shared_down_w)


class NemotronHBlock(Layer):
    """``x + mixer(RMSNorm(x))`` for one letter of the pattern."""

    def __init__(self, config: NemotronHConfig, layer: int):
        super().__init__()
        self.kind = config.kinds[layer]
        self.norm = GPTRMSNorm(config.hidden_size, config.layer_norm_eps,
                               config.dtype or "float32")
        self.mixer = GPTGroupedAttention(config, layer) \
            if self.kind == "*" else {
                "M": NemotronHMamba, "E": NemotronHExperts}[self.kind](config)

    def forward(self, x, kv_cache=None):
        """Without a cache the new ``x``; with one ``(x, k', v')``, the
        layer's two pools (``()`` twice for a layer that keeps
        nothing)."""
        u = self.norm(x)
        if self.kind == "E":
            y, stats = self.mixer(
                u, valid=None if kv_cache is None else kv_cache.valid,
                decode=kv_cache is not None and kv_cache.kind == "decode")
            if kv_cache is not None and kv_cache.aux is not None:
                kv_cache.aux.setdefault("moe", []).append(stats._data)
            return x + y if kv_cache is None else (x + y, (), ())
        if kv_cache is None:
            return x + self.mixer(u)
        y, k, v = self.mixer(u, kv_cache=kv_cache)
        return x + y, k, v


class NemotronHModel(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embeddings = _mk(config, [config.vocab_size,
                                       config.hidden_size])
        self.layers = LayerList([NemotronHBlock(config, i)
                                 for i in range(config.num_layers)])
        self.norm_f = GPTRMSNorm(config.hidden_size, config.layer_norm_eps,
                                 config.dtype or "float32")

    def forward(self, input_ids, cache=None):
        h = apply_op("embedding", lambda w, ids: jnp.take(
            w, ids.astype(jnp.int32), axis=0), self.embeddings, input_ids)
        if cache is None:
            for layer in self.layers:
                h = layer(h)
            return self.norm_f(h)
        # one row of the block table: the full layers' columns, then the
        # state slot (where the model has layers of that kind)
        full = cache.block_tables
        if self.config.layers_of("M"):
            full = split_state_column(full, paged=True)[0]
        k_new, v_new = [], []
        for i, layer in enumerate(self.layers):
            view = cache.layer_view(
                cache.k[i], cache.v[i], full if layer.kind == "*" else None)
            h, k_i, v_i = layer(h, kv_cache=view)
            k_new.append(k_i)
            v_new.append(v_i)
        return self.norm_f(h), (k_new, v_new)


class NemotronHForCausalLM(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        self.lm_head = _mk(config, [config.hidden_size, config.vocab_size])
        # tokens of a served prefill computed at a time, whole rows,
        # through all the layers (``gpt.cached_hidden``): a 16-row
        # prefill of 2,048 positions is sixteen passes of one row over
        # the donated pools, keeps a row's temporaries, and its body is
        # the program a one-row prefill is. Not an option: 1,024 is the
        # largest body of more than one row that the chip's compiler
        # gave a schedule that ends (PERF.md section 6, PR 35)
        self.prefill_block_tokens = 1024

    def forward(self, input_ids, cache=None):
        if cache is None:
            h, pools = self.backbone(input_ids), None
        else:
            h, pools = cached_hidden(self.backbone, input_ids, cache,
                                     self.prefill_block_tokens)
        # the logits leave the product's float32 accumulator unrounded
        logits = apply_op("lm_head", lambda h, w: jnp.einsum(
            "bsh,hv->bsv", h, w, preferred_element_type=jnp.float32),
            h, self.lm_head)
        return logits if cache is None else (logits, pools)

    # ---- cache plumbing (serving.generation engine)
    def _state_slot(self):
        """One slot of an ``M`` layer's two pools: the tail in the
        parameters' type, the state in ``ssm_state_dtype``."""
        from ..ops.ssm import slot_shapes
        cfg = self.config
        tail, s = slot_shapes(conv_kernel=cfg.conv_kernel, **cfg.ssm_sizes())
        return ((tail, self.backbone.embeddings._data.dtype),
                (s, cfg.ssm_state_dtype))

    def init_kv_pools(self, num_pages: int, page_size: int, dtype=None,
                      window_pages=None, state_slots=None):
        """Zeroed pools, a pair a layer: a ``*`` layer paged K and V
        pools of ``num_pages`` pages (``ops.paged_attention``; page 0
        the trash page), an ``M`` layer a tail pool and a state pool of
        ``state_slots`` slots (``state_cache.state_pools``; slot 0 the
        trash slot), an ``E`` layer ``()`` twice. ``dtype`` is the K/V
        pools' (``"int8"`` too); raw jax arrays."""
        from ..ops.paged_attention import new_kv_pool
        cfg = self.config
        own = self.backbone.embeddings._data.dtype
        k, v = [], []
        for kind in cfg.kinds:
            if kind == "*":
                k.append(new_kv_pool(num_pages, page_size, cfg.num_kv_heads,
                                     cfg.head_dim, dtype or own))
                v.append(new_kv_pool(num_pages, page_size, cfg.num_kv_heads,
                                     cfg.head_dim, dtype or own))
            elif kind == "M":
                tail, s = state_pools(self._state_slot(), state_slots)
                k.append(tail)
                v.append(s)
            else:
                k.append(())
                v.append(())
        return k, v

    def kv_cache_spec(self, kv_dtype: str = "") -> dict:
        """Geometry the decode engine sizes its cache from. ``kinds``:
        ``full`` the ``*`` layers (the whole context, paged), ``state``
        the ``M`` layers (``state_cache.state_kind``; absent where the
        model has none); ``E`` layers keep nothing."""
        from ..ops.paged_attention import kv_pool_bytes
        cfg = self.config
        full, state = cfg.layers_of("*"), cfg.layers_of("M")
        kinds = {"full": {"layers": full, "window": None}}
        if state:
            kinds["state"] = state_kind(state, self._state_slot())
        return {"num_layers": cfg.num_layers,
                "num_heads": cfg.num_heads,
                "num_kv_heads": cfg.num_kv_heads,
                "head_dim": cfg.head_dim,
                "max_seq_len": cfg.max_seq_len,
                "stacked": False,
                "kv_dtype": kv_dtype or "",
                "kv_bytes_per_token": int(len(full) * 2 * kv_pool_bytes(
                    1, 1, cfg.num_kv_heads, cfg.head_dim,
                    kv_dtype or None)),
                "kinds": kinds}

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())
