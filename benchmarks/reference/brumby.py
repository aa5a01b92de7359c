"""The plain reference of the Brumby decoder (manifestai, Brumby-14B-Base
``config.json``, ``model_type`` ``brumby``): forward pass and causal-LM
loss in straightforward ``jax.numpy``, float32, at
``jax.default_matmul_precision("highest")``; no kernels, no cache, no
chunks and no feature map: the quadratic form of power retention
(Gelada, Buckman, Zhang & Bach, arXiv:2507.04239) written out over every
pair of positions. Layer ``l`` of ``T`` tokens ``x``:

- ``u = RMSNorm(x; g1)``, ``RMSNorm(x; g) = x * rsqrt(mean(x^2) + eps) *
  g``;
- ``q = u Wq -> [T, 40, 128]``, ``k = u Wk``, ``v = u Wv -> [T, 8,
  128]``, no biases; ``q`` and ``k`` get RMSNorm over the 128 of a head
  (``gq``, ``gk``), then rotate-half RoPE over the whole head at
  absolute positions (theta ``cfg.rope_theta``);
- the gate of K/V head ``h``: ``gamma_t[h] = sigmoid(u_t . w_g[h] +
  b_g[h])``, ``G_t = sum_{s <= t} log gamma_s``;
- query head ``i`` reads K/V head ``h = i // 5``: ``a_ts = exp(G_t[h] -
  G_s[h]) * (q_t[i] . k_s[h] / sqrt(128))^2`` for ``s <= t``, ``y_t[i]
  = sum_s a_ts v_s[h] / (sum_s a_ts + eps)``;
- ``x' = x + concat_i(y_t[i]) Wo``; ``m = RMSNorm(x'; g2)``; ``x_out =
  x' + (silu(m Wg) * (m Wu)) Wd``.

After the last layer RMSNorm and an untied head.

It is given the model's own parameter arrays (``state_arrays(model)``'s
first dict; whatever their dtype, each is cast to float32 where it is
used) and the program's config object, of which it reads ``num_heads``,
``num_kv_heads``, ``head_dim``, ``layer_norm_eps``, ``rope_theta`` and
``retention_eps``. It imports nothing of the program.

So that one ``[1, 4096]`` sequence of the published widths fits beside a
served model, queries go in blocks (a block's weights ``a`` against every
key, then the mask), the MLP ``COLUMN_BLOCK`` of its columns at a time and
the head a block of vocabulary rows at a time. One layer is jitted and
called once per layer from a Python loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_KEYS = {
    "g1": "ln_1.weight", "wq": "attn.q_w", "wk": "attn.k_w",
    "wv": "attn.v_w", "wo": "attn.out_w", "gq": "attn.q_norm_w",
    "gk": "attn.k_norm_w", "wg_gate": "attn.gate_w", "bg": "attn.gate_b",
    "g2": "ln_2.weight", "wg": "mlp.gate_w", "wu": "mlp.up_w",
    "wd": "mlp.down_w",
}
QUERY_BLOCK = 256
COLUMN_BLOCK = 2048
HEAD_BLOCK = 16384      # at most this many vocabulary rows at a time


def layer_params(params: dict, i: int) -> dict:
    return {k: params[f"gpt.layers.{i}.{name}"] for k, name in _KEYS.items()}


def num_layers(params: dict) -> int:
    return 1 + max(int(k.split(".")[2]) for k in params
                   if k.startswith("gpt.layers."))


def _fp8(x):
    """An activation as the control holds it: rounded to float8 e4m3
    (3 bits of mantissa where bfloat16 has 7), computed on in float32."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _same(x):
    return x


def _f32(a):
    return jnp.asarray(a, dtype=jnp.float32)


def _fp8_weight(a):
    """A product's weight as the control holds it: float8 e4m3 too."""
    return _f32(a).astype(jnp.float8_e4m3fn).astype(jnp.float32)


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


def retention(q, k, v, log_gate, *, eps):
    """Power retention of degree 2, the quadratic form. q: [S, Hk, G, d];
    k, v: [S, Hk, d]; log_gate: [S, Hk]. Returns ``[S, Hk, G, d]``."""
    s, _, _, d = q.shape
    big_g = jnp.cumsum(log_gate, axis=0)                  # [S, Hk]
    blk = math.gcd(s, QUERY_BLOCK)
    cols = jnp.arange(s)[None, :]

    def block(i):
        rows = i * blk + jnp.arange(blk)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        gb = jax.lax.dynamic_slice_in_dim(big_g, i * blk, blk, 0)
        dots = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(d)
        decay = jnp.exp(jnp.where(cols <= rows, gb.T[:, :, None]
                                  - big_g.T[:, None, :], -jnp.inf))
        a = jnp.square(dots) * decay[:, None]             # [Hk, G, q, k]
        y = jnp.einsum("hgqk,khd->qhgd", a, v)
        return y / (jnp.moveaxis(jnp.sum(a, axis=-1), 2, 0)[..., None]
                    + eps)

    return jax.lax.map(block, jnp.arange(s // blk)).reshape(q.shape)


def dense_mlp(m, p, rnd=_same, wt=_f32):
    """``(silu(m Wg) * (m Wu)) Wd``, a block of columns at a time."""
    width = p["wg"].shape[1]
    blk = math.gcd(width, COLUMN_BLOCK)
    mr = rnd(m)

    def columns(j, acc):
        wg = jax.lax.dynamic_slice_in_dim(p["wg"], j * blk, blk, axis=1)
        wu = jax.lax.dynamic_slice_in_dim(p["wu"], j * blk, blk, axis=1)
        wd = jax.lax.dynamic_slice_in_dim(p["wd"], j * blk, blk, axis=0)
        return acc + rnd(jax.nn.silu(mr @ wt(wg)) * (mr @ wt(wu))) @ wt(wd)

    return jax.lax.fori_loop(0, width // blk, columns, jnp.zeros_like(m))


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "num_kv_heads", "head_dim", "eps", "theta", "ret_eps",
    "control"))
def layer(x, p, *, num_heads, num_kv_heads, head_dim, eps, theta, ret_eps,
          control=False):
    """One layer over ``x`` [S, H] (one sequence). With ``control`` the
    residual stream, every activation that enters a product and the
    product's weight are rounded to float8."""
    rnd, wt = (_fp8, _fp8_weight) if control else (_same, _f32)
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        group = num_heads // num_kv_heads
        x = rnd(x)      # the residual stream as the last layer left it
        u = rnd(_rms_norm(x, _f32(p["g1"]), eps))
        q = (u @ wt(p["wq"])).reshape(s, num_heads, head_dim)
        k = (u @ wt(p["wk"])).reshape(s, num_kv_heads, head_dim)
        v = (u @ wt(p["wv"])).reshape(s, num_kv_heads, head_dim)
        q = _rope(_rms_norm(q, _f32(p["gq"]), eps), theta)
        k = _rope(_rms_norm(k, _f32(p["gk"]), eps), theta)
        log_gate = jax.nn.log_sigmoid(u @ wt(p["wg_gate"]) + _f32(p["bg"]))
        y = retention(rnd(q).reshape(s, num_kv_heads, group, head_dim),
                      rnd(k), rnd(v), log_gate, eps=ret_eps)
        x = rnd(x + rnd(y.reshape(s, num_heads * head_dim)) @ wt(p["wo"]))
        return x + dense_mlp(_rms_norm(x, _f32(p["g2"]), eps), p, rnd, wt)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, w_norm, w_head, *, eps, control):
    """The final norm and the head, a block of vocabulary rows at a
    time (the largest divisor of the vocabulary up to ``HEAD_BLOCK``)."""
    rnd, wt = (_fp8, _fp8_weight) if control else (_same, _f32)
    vocab = w_head.shape[1]
    blk = max(b for b in range(1, min(vocab, HEAD_BLOCK) + 1)
              if vocab % b == 0)
    with jax.default_matmul_precision("highest"):
        h = rnd(_rms_norm(h, w_norm.astype(jnp.float32), eps))

        def rows(j, out):
            w = jax.lax.dynamic_slice_in_dim(w_head, j * blk, blk, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, h @ wt(w), j * blk, axis=2)

        return jax.lax.fori_loop(
            0, vocab // blk, rows,
            jnp.zeros(h.shape[:2] + (vocab,), jnp.float32))


def hidden_states(params: dict, ids, cfg, control: bool = False):
    """``ids`` [B, S] -> the last layer's output [B, S, H] (before the
    final RMSNorm), a sequence at a time."""
    wte = params["gpt.embeddings.word_embeddings.weight"]
    out = []
    for row in jnp.asarray(ids):
        x = jnp.take(wte, row, axis=0).astype(jnp.float32)
        for i in range(num_layers(params)):
            x = layer(
                x, layer_params(params, i), num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                eps=float(cfg.layer_norm_eps), theta=float(cfg.rope_theta),
                ret_eps=float(cfg.retention_eps), control=control)
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
    precision step below the bfloat16 this configuration states.
    Everything the program holds in bfloat16 is held in float8 e4m3:
    every activation that enters a product (the normed inputs of the
    projections, of the gate and of the MLP, q, k, v, the retention's
    outputs, the gated activations, the head's input), every weight of a
    product (cast as it stands, no scale) and the residual stream
    between blocks (the embedding rows among it). What the program
    computes in float32 stays float32: norms and their weights, the
    gate's bias, sigmoid and decays, the retention's weights, sums and
    normaliser (the state the program keeps), accumulators. Put in the
    program's place it has to come out as not correct."""
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
