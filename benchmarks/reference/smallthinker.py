"""The plain reference of the SmallThinker decoder (PowerInfer,
SmallThinker-21BA3B-Instruct ``config.json``): forward pass and
causal-LM loss in straightforward ``jax.numpy``, float32, at
``jax.default_matmul_precision("highest")``; no kernels, no cache, no
sorting of tokens by expert. Layer ``l`` of ``T`` tokens ``x``:

- ``h = RMSNorm(x; w_in)``, ``RMSNorm(x; w) = x * rsqrt(mean(x^2) +
  eps) * w``;
- ``q = h Wq -> [T, Hq, D]``, ``k = h Wk``, ``v = h Wv -> [T, Hkv, D]``,
  no biases; on the layers ``rope_layout`` marks, ``q`` and ``k`` get
  rotate-half RoPE over the whole head at absolute positions, on the
  others nothing is added (NoPE);
- scores ``q_i . k_j / sqrt(D)``, visible where ``j <= i`` and, on the
  layers ``sliding_window_layout`` marks, ``i - j < window`` (the token
  itself among the ``window``); K/V head ``g`` serves query heads
  ``g*G .. g*G+G-1``; ``x' = x + concat(softmax(scores) v) Wo``;
- ``m = RMSNorm(x'; w_post)``; the router reads **h**, the attention's
  normed input ("router placed before attention"): ``s = h Wr``, the
  ``top_k`` largest a token, softmax over those alone;
- ``E_e(m) = (relu(m Wg_e) * (m Wu_e)) Wd_e``;
  ``x_out = x' + sum_{e in top_k} w_e E_e(m)``: no shared expert, no
  token dropped, no capacity.

After the last layer RMSNorm and an untied head.

It is given the model's own parameter arrays (``state_arrays(model)``'s
first dict; whatever their dtype, each is cast to float32 where it is
used) and the program's config object, of which it reads ``num_heads``,
``num_kv_heads``, ``head_dim``, ``layer_norm_eps``, ``rope_theta``,
``rope_layout``, ``sliding_window``, ``sliding_window_layout`` and
``moe_top_k``. It imports nothing of the program.

So that one ``[1, 9216]`` sequence of the published widths fits beside
a served model, queries go in blocks (a block's scores against every
key, then the mask) and experts one at a time (every token through
expert ``e``, weighted by its router weight for ``e``, which is 0 where
``e`` was not chosen): more arithmetic than a sorted dispatch, and no
dependence on one. One layer is jitted and called once per layer from a
Python loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_LAYER_KEYS = {
    "w_in": "ln_1.weight", "wq": "attn.q_w", "wk": "attn.k_w",
    "wv": "attn.v_w", "wo": "attn.out_w", "w_post": "ln_2.weight",
    "wr": "mlp.router_w", "wg": "mlp.gate_w", "wu": "mlp.up_w",
    "wd": "mlp.down_w",
}
QUERY_BLOCK = 256


def layer_params(params: dict, i: int) -> dict:
    return {k: params[f"gpt.layers.{i}.{name}"]
            for k, name in _LAYER_KEYS.items()}


def num_layers(params: dict) -> int:
    return 1 + max(int(k.split(".")[2]) for k in params
                   if k.startswith("gpt.layers."))


def _fp8(x):
    """An activation as the control holds it: rounded to float8 e4m3
    (3 bits of mantissa where bfloat16 has 7), computed on in float32."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _same(x):
    return x


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, H, D] at positions 0 .. S-1; rotate-half."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "head_dim", "eps", "theta", "window",
    "top_k", "control"))
def layer(x, p, *, num_heads, num_kv_heads, head_dim, eps, theta, window,
          top_k, control=False):
    """One layer over ``x`` [S, H] (one sequence). ``theta`` None: no
    positions; ``window`` None: the whole context. With ``control``
    every activation that enters a product is rounded to float8."""
    rnd = _fp8 if control else _same
    with jax.default_matmul_precision("highest"):
        f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
        s = x.shape[0]
        group = num_heads // num_kv_heads
        h = _rms_norm(x, f32(p["w_in"]), eps)
        q = (rnd(h) @ f32(p["wq"])).reshape(s, num_heads, head_dim)
        k = (rnd(h) @ f32(p["wk"])).reshape(s, num_kv_heads, head_dim)
        v = (rnd(h) @ f32(p["wv"])).reshape(s, num_kv_heads, head_dim)
        if theta is not None:
            q, k = _rope(q, theta), _rope(k, theta)
        q, k, v = rnd(q), rnd(k), rnd(v)
        qg = q.reshape(s, num_kv_heads, group, head_dim)
        blk = math.gcd(s, QUERY_BLOCK)
        cols = jnp.arange(s)[None, :]

        def attend(i):
            rows = i * blk + jnp.arange(blk)[:, None]
            qb = jax.lax.dynamic_slice_in_dim(qg, i * blk, blk, 0)
            sc = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(head_dim)
            seen = cols <= rows
            if window is not None:
                seen = seen & (rows - cols < window)
            pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            return jnp.einsum("hgqk,khd->qhgd", rnd(pr), v)

        a = jax.lax.map(attend, jnp.arange(s // blk)).reshape(
            s, num_heads * head_dim)
        x = x + rnd(a) @ f32(p["wo"])

        m = _rms_norm(x, f32(p["w_post"]), eps)
        scores = rnd(h) @ f32(p["wr"])              # the router reads h
        top, chosen = jax.lax.top_k(scores, top_k)
        weight = jnp.zeros_like(scores).at[
            jnp.arange(s)[:, None], chosen].set(jax.nn.softmax(top, -1))
        mr = rnd(m)

        def expert(acc, e):
            wg, wu, wd, w_e = e
            act = jax.nn.relu(mr @ f32(wg)) * (mr @ f32(wu))
            return acc + w_e[:, None] * (rnd(act) @ f32(wd)), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (p["wg"], p["wu"], p["wd"], weight.T))
        return x + y


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, w_norm, w_head, *, eps, control):
    rnd = _fp8 if control else _same
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(h, w_norm.astype(jnp.float32), eps)
        return rnd(h) @ w_head.astype(jnp.float32)


def hidden_states(params: dict, ids, cfg, control: bool = False):
    """``ids`` [B, S] -> the last layer's output [B, S, H] (before the
    final RMSNorm), a sequence at a time."""
    wte = params["gpt.embeddings.word_embeddings.weight"]
    n = num_layers(params)
    rope = tuple(cfg.rope_layout) or (1,) * n
    windowed = tuple(cfg.sliding_window_layout) or (1,) * n
    out = []
    for row in jnp.asarray(ids):
        x = jnp.take(wte, row, axis=0).astype(jnp.float32)
        for i in range(n):
            x = layer(
                x, layer_params(params, i), num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                eps=float(cfg.layer_norm_eps),
                theta=float(cfg.rope_theta) if rope[i] else None,
                window=int(cfg.sliding_window)
                if windowed[i] and cfg.sliding_window else None,
                top_k=cfg.moe_top_k, control=control)
        out.append(x)
    return jnp.stack(out)


# ------------------------------------------- the protocol (README.md)
def logits(params: dict, ids, cfg, positions=None, control: bool = False):
    """Float32 logits [B, S, V], or [B, len(positions), V] for the
    sequence positions asked for (the head is the largest product)."""
    h = hidden_states(params, ids, cfg, control)
    if positions is not None:
        h = h[:, jnp.asarray(positions)]
    return _head(h, params["gpt.ln_f.weight"], params["lm_head.weight"],
                 eps=float(cfg.layer_norm_eps), control=control)


def control_logits(params: dict, ids, cfg, positions=None):
    """The control (``run.py --control``): the same mathematics one
    precision step below the bfloat16 this configuration states. Every
    activation that enters a product (the normed inputs of the
    projections, of the router and of the experts, q, k, v, the
    attention weights and outputs, the experts' gated activations, the
    head's input) is rounded to float8 e4m3; weights and accumulation
    stay as they are. Put in the program's place it has to come out as
    not correct."""
    return logits(params, ids, cfg, positions, control=True)


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
