"""The plain reference of the GPT-2 / GPT-3 decoder: forward pass and
causal-LM loss in straightforward ``jax.numpy``, float32, at
``jax.default_matmul_precision("highest")``; no kernels, no cache, no
batching tricks. It follows Radford et al. 2019 / Brown et al. 2020:
learned absolute positions, pre-LayerNorm blocks, dense causal softmax
attention, GELU (the tanh form GPT-2 calls ``gelu_new``), a final
LayerNorm and a head tied to the token embedding.

It is given the model's own parameter arrays (``state_arrays(model)``'s
first dict), not a copy and whatever their dtype (every array is cast
to float32 before it is used), in either layout the program has:

- the module stack: ``gpt.layers.<i>.attn.qkv_proj.weight`` ...
- the stacked decoder: ``gpt.decoder.qkv_w`` ... with a leading layer
  axis.

One departure from the papers, which the program shares and which is a
layout and not mathematics: the fused QKV projection's 3H outputs are
ordered (head, q|k|v, head_dim), not (q|k|v, head, head_dim).

One block is jitted and called once per layer from a Python loop, so a
24-layer model compiles one small program, not a 24-layer one.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_STACKED = "gpt.decoder."
_BLOCK_KEYS = {
    "ln1_w": "ln_1.weight", "ln1_b": "ln_1.bias",
    "qkv_w": "attn.qkv_proj.weight", "qkv_b": "attn.qkv_proj.bias",
    "out_w": "attn.out_proj.weight", "out_b": "attn.out_proj.bias",
    "ln2_w": "ln_2.weight", "ln2_b": "ln_2.bias",
    "fc1_w": "mlp.fc_in.weight", "fc1_b": "mlp.fc_in.bias",
    "fc2_w": "mlp.fc_out.weight", "fc2_b": "mlp.fc_out.bias",
}


def layer_params(params: dict, i: int) -> dict:
    """Layer ``i``'s twelve arrays under the short names, from either
    layout (a stacked array is indexed, which copies one layer)."""
    if _STACKED + "qkv_w" in params:
        return {k: params[_STACKED + k][i] for k in _BLOCK_KEYS}
    return {k: params[f"gpt.layers.{i}.{name}"]
            for k, name in _BLOCK_KEYS.items()}


def num_layers(params: dict) -> int:
    if _STACKED + "qkv_w" in params:
        return int(params[_STACKED + "qkv_w"].shape[0])
    return 1 + max(int(k.split(".")[2]) for k in params
                   if k.startswith("gpt.layers."))


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("num_heads", "eps", "dtype"))
def block(x, p, *, num_heads: int, eps: float, dtype=jnp.float32):
    """One pre-LN decoder block over ``x`` [B, S, H], in ``dtype``
    (float32; bfloat16 is the control's)."""
    with jax.default_matmul_precision("highest"):
        p = {k: v.astype(dtype) for k, v in p.items()}
        b, s, hidden = x.shape
        hd = hidden // num_heads
        h = _layer_norm(x, p["ln1_w"], p["ln1_b"], eps)
        qkv = (h @ p["qkv_w"] + p["qkv_b"]).reshape(b, s, num_heads, 3, hd)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, hidden)
        x = x + attn @ p["out_w"] + p["out_b"]
        h = _layer_norm(x, p["ln2_w"], p["ln2_b"], eps)
        x = x + _gelu_tanh(h @ p["fc1_w"] + p["fc1_b"]) @ p["fc2_w"] \
            + p["fc2_b"]
        return x


@functools.partial(jax.jit, static_argnames=("dtype",))
def _embed(wte, wpe, ids, *, dtype):
    return (jnp.take(wte, ids, axis=0).astype(dtype)
            + wpe[:ids.shape[-1]].astype(dtype))


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(h, ln_w, ln_b, wte, *, eps, dtype):
    with jax.default_matmul_precision("highest"):
        h = _layer_norm(h, ln_w.astype(dtype), ln_b.astype(dtype), eps)
        return (h @ wte.astype(dtype).T).astype(jnp.float32)


def hidden_states(params: dict, ids, cfg, dtype=jnp.float32):
    """``ids`` [B, S] -> the last block's output [B, S, H] (before the
    final LayerNorm)."""
    x = _embed(params["gpt.embeddings.word_embeddings.weight"],
               params["gpt.embeddings.position_embeddings"],
               jnp.asarray(ids), dtype=dtype)
    for i in range(num_layers(params)):
        x = block(x, layer_params(params, i), num_heads=cfg.num_heads,
                  eps=cfg.layer_norm_eps, dtype=dtype)
    return x


# ------------------------------------------- the protocol (README.md)
def logits(params: dict, ids, cfg, positions=None, dtype=jnp.float32):
    """Float32 logits [B, S, V], or [B, len(positions), V] for the
    sequence positions asked for (the head is the largest product).
    ``cfg`` is the program's config object; read here: ``num_heads``,
    ``layer_norm_eps``."""
    h = hidden_states(params, ids, cfg, dtype)
    if positions is not None:
        h = h[:, jnp.asarray(positions)]
    return _head(h, params["gpt.ln_f.weight"], params["gpt.ln_f.bias"],
                 params["gpt.embeddings.word_embeddings.weight"],
                 eps=cfg.layer_norm_eps, dtype=dtype)


def control_logits(params: dict, ids, cfg, positions=None):
    """The control (``run.py --control``): the same mathematics one
    precision step below the float32 these configurations state, every
    array and every activation in bfloat16. Put in the program's place
    it has to come out as not correct."""
    return logits(params, ids, cfg, positions, dtype=jnp.bfloat16)


@jax.jit
def _shifted_cross_entropy(lg, labels):
    logp = jax.nn.log_softmax(lg[:, :-1].astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def causal_lm_loss(params: dict, ids, labels, cfg):
    """Mean next-token cross entropy: position t's logits against
    ``labels[t + 1]``."""
    return _shifted_cross_entropy(logits(params, ids, cfg),
                                  jnp.asarray(labels))
