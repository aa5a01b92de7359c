"""The plain reference of the GLM-4.7-Flash decoder (zai-org,
GLM-4.7-Flash ``config.json``, ``model_type`` ``glm4_moe_lite``):
forward pass and causal-LM loss in straightforward ``jax.numpy``,
float32, at ``jax.default_matmul_precision("highest")``; no kernels, no
cache, no absorbed form, no sorting of tokens by expert. Layer ``l`` of
``T`` tokens ``x``, ``RMSNorm(x; g) = x * rsqrt(mean(x^2) + eps) * g``:

- ``h = RMSNorm(x; g1)``;
- ``q = RMSNorm(h Wqa; gq) Wqb -> [T, H, nope + rope]``; rotate-half
  RoPE on each head's last ``rope`` dimensions at absolute positions;
- ``[c | k_pe] = h Wkva -> [T, latent + rope]``, ``c = RMSNorm(c; gkv)``,
  RoPE on ``k_pe``: one rotary key part that every head shares;
- ``[k_nope_h | v_h] = c Wkvb -> [T, H, nope + dv]`` (the *expanded*
  form: every head's keys and values made from the latent),
  ``k_h = [k_nope_h | k_pe]``, no biases;
- scores ``q_h . k_h / sqrt(nope + rope)``, causal;
  ``x' = x + concat_h(softmax(scores) v_h) Wo``;
- ``m = RMSNorm(x'; g2)``; on a layer ``moe_layout`` does not mark
  (layer 0) ``x_out = x' + (silu(m Wg) * (m Wu)) Wd``;
- on the others ``s = sigmoid(m Wr)``, ``C`` the ``top_k`` experts of
  largest ``s + b`` (``b`` the selection bias: ``topk_method``
  ``noaux_tc``, one group; it chooses and does not weigh), ``w_e = scale
  * s_e / sum_{c in C} s_c``; ``E(m) = (silu(m Wg_e) * (m Wu_e)) Wd_e``;
  ``x_out = x' + E_shared(m) + sum_{e in C, e held} w_e E_e(m)``.

**The share.** The parameters hold ``held = Wg.shape[0]`` of the
router's experts, ``cfg.moe_expert_offset .. + held - 1``: one chip's
share of an expert-parallel layer. ``w_e`` is normalised over all the
chosen, only the held ones are computed, the shared expert is whole,
and that partial sum goes on to the next layer (as
``reference/kexaone.py`` has it). With every expert held it is the
uncut layer.

No multi-token-prediction module. After the last layer RMSNorm and an
untied head.

It is given the model's own parameter arrays (``state_arrays(model)``'s
first dict; each is cast to float32 where it is used, a layer at a
time) and the program's config object, of which it reads
``num_heads``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``kv_lora_rank``, ``layer_norm_eps``, ``rope_theta``,
``moe_layout``, ``moe_top_k``, ``moe_routed_scale`` and
``moe_expert_offset``. It imports nothing of the program.

So that one ``[1, 4096]`` sequence of the published widths fits beside
a served model, queries go in blocks (a block's scores against every
key, then the mask), experts one at a time (every token through expert
``e``, weighted by its router weight for ``e``, 0 where ``e`` was not
chosen) and the dense MLP ``COLUMN_BLOCK`` of its columns at a time.
One layer is jitted and called once per layer from a Python loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_ATTENTION_KEYS = {
    "g1": "ln_1.weight", "wqa": "attn.q_a_w", "gq": "attn.q_a_norm_w",
    "wqb": "attn.q_b_w", "wkva": "attn.kv_a_w", "gkv": "attn.kv_a_norm_w",
    "wkvb": "attn.kv_b_w", "wo": "attn.out_w", "g2": "ln_2.weight",
}
_DENSE_KEYS = {"wg": "mlp.gate_w", "wu": "mlp.up_w", "wd": "mlp.down_w"}
_SPARSE_KEYS = dict(
    _DENSE_KEYS, wr="mlp.router_w", br="mlp.router_bias",
    sg="mlp.shared_gate_w", su="mlp.shared_up_w", sd="mlp.shared_down_w")
QUERY_BLOCK = 256
COLUMN_BLOCK = 2048


def layer_params(params: dict, i: int, sparse: bool) -> dict:
    keys = dict(_ATTENTION_KEYS, **(_SPARSE_KEYS if sparse else _DENSE_KEYS))
    return {k: params[f"gpt.layers.{i}.{name}"] for k, name in keys.items()}


def num_layers(params: dict) -> int:
    return 1 + max(int(k.split(".")[2]) for k in params
                   if k.startswith("gpt.layers."))


def _f32(a):
    return jnp.asarray(a, dtype=jnp.float32)


def _same(x):
    return x


def _fp8(x):
    """An activation as the control holds it: rounded to float8 e4m3
    (3 bits of mantissa where bfloat16 has 7), computed on in float32."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _fp8_weight(a):
    """A product's weight as the control holds it: float8 e4m3 too."""
    return _f32(a).astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, H, D] at positions 0 .. S-1; rotate-half over all D."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def router_weights(m, wr, br, *, top_k, scale, wt=_f32):
    """``[T, E_all]``: ``scale * s_e / sum of the chosen s`` where
    expert ``e`` is among a token's ``top_k`` largest ``s + br`` (``s =
    sigmoid(m Wr)``), 0 elsewhere."""
    scores = jax.nn.sigmoid(m @ wt(wr))
    _, chosen = jax.lax.top_k(scores + _f32(br), top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return jnp.zeros_like(scores).at[
        jnp.arange(m.shape[0])[:, None], chosen].set(
            scale * top / jnp.sum(top, axis=-1, keepdims=True))


def gated(m, wg, wu, wd, rnd=_same, wt=_f32):
    return rnd(jax.nn.silu(m @ wt(wg)) * (m @ wt(wu))) @ wt(wd)


def experts(m, p, *, top_k, scale, offset, shared=True, rnd=_same,
            wt=_f32):
    """The expert layer's addition to the residual stream for ``m`` [T,
    H]: the held experts' part of the routed sum (``p["wg"]`` holds
    experts ``offset ..``) and, with ``shared``, the shared expert."""
    held = p["wg"].shape[0]
    mr = rnd(m)
    weight = router_weights(mr, p["wr"], p["br"], top_k=top_k, scale=scale,
                            wt=wt)
    weight = jax.lax.dynamic_slice_in_dim(weight, offset, held, axis=1)

    def expert(acc, e):
        wg, wu, wd, w_e = e
        return acc + w_e[:, None] * gated(mr, wg, wu, wd, rnd, wt), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                        (p["wg"], p["wu"], p["wd"], weight.T))
    if shared:
        y = y + gated(mr, p["sg"], p["su"], p["sd"], rnd, wt)
    return y


def dense_mlp(m, p, rnd=_same, wt=_f32):
    """``(silu(m Wg) * (m Wu)) Wd``, a block of columns at a time."""
    width = p["wg"].shape[1]
    blk = math.gcd(width, COLUMN_BLOCK)
    mr = rnd(m)

    def columns(j, acc):
        wg = jax.lax.dynamic_slice_in_dim(p["wg"], j * blk, blk, axis=1)
        wu = jax.lax.dynamic_slice_in_dim(p["wu"], j * blk, blk, axis=1)
        wd = jax.lax.dynamic_slice_in_dim(p["wd"], j * blk, blk, axis=0)
        return acc + gated(mr, wg, wu, wd, rnd, wt)

    return jax.lax.fori_loop(0, width // blk, columns, jnp.zeros_like(m))


def attention(h, p, *, num_heads, nope, rope, dv, latent, eps, theta,
              rnd=_same, wt=_f32):
    """The latent attention's output for the normed input ``h`` [S, H]
    of one sequence, in the expanded form, before ``Wo``: [S, heads *
    dv]."""
    s = h.shape[0]
    q = rnd(_rms_norm(h @ wt(p["wqa"]), _f32(p["gq"]), eps)) @ wt(p["wqb"])
    q = q.reshape(s, num_heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    ckr = h @ wt(p["wkva"])
    c = rnd(_rms_norm(ckr[:, :latent], _f32(p["gkv"]), eps))
    k_pe = _rope(ckr[:, None, latent:], theta)                  # [S, 1, r]
    kv = (c @ wt(p["wkvb"])).reshape(s, num_heads, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (s, num_heads, rope))], -1)
    q, k, v = rnd(q), rnd(k), rnd(kv[..., nope:])
    blk = math.gcd(s, QUERY_BLOCK)
    cols = jnp.arange(s)[None, :]

    def attend(i):
        rows = i * blk + jnp.arange(blk)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(nope + rope)
        pr = jax.nn.softmax(jnp.where(cols <= rows, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", rnd(pr), v)

    return jax.lax.map(attend, jnp.arange(s // blk)).reshape(
        s, num_heads * dv)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "nope", "rope", "dv", "latent", "eps", "theta", "top_k",
    "scale", "offset", "control"))
def layer(x, p, *, num_heads, nope, rope, dv, latent, eps, theta, top_k,
          scale, offset, control=False):
    """One layer over ``x`` [S, H] (one sequence); ``p`` without a
    router: the dense MLP. With ``control`` the residual stream, every
    activation that enters a product and the product's weight are
    rounded to float8."""
    rnd, wt = (_fp8, _fp8_weight) if control else (_same, _f32)
    with jax.default_matmul_precision("highest"):
        x = rnd(x)      # the residual stream as the last layer left it
        h = rnd(_rms_norm(x, _f32(p["g1"]), eps))
        a = attention(h, p, num_heads=num_heads, nope=nope, rope=rope,
                      dv=dv, latent=latent, eps=eps, theta=theta, rnd=rnd,
                      wt=wt)
        x = rnd(x + rnd(a) @ wt(p["wo"]))
        m = _rms_norm(x, _f32(p["g2"]), eps)
        if "wr" in p:
            return x + experts(m, p, top_k=top_k, scale=scale,
                               offset=offset, rnd=rnd, wt=wt)
        return x + dense_mlp(m, p, rnd, wt)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, w_norm, w_head, *, eps, control):
    rnd, wt = (_fp8, _fp8_weight) if control else (_same, _f32)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(h, w_norm.astype(jnp.float32), eps)
        return rnd(h) @ wt(w_head)


def hidden_states(params: dict, ids, cfg, control: bool = False):
    """``ids`` [B, S] -> the last layer's output [B, S, H] (before the
    final RMSNorm), a sequence at a time."""
    wte = params["gpt.embeddings.word_embeddings.weight"]
    n = num_layers(params)
    sparse = tuple(cfg.moe_layout) or (1,) * n
    out = []
    for row in jnp.asarray(ids):
        x = jnp.take(wte, row, axis=0).astype(jnp.float32)
        for i in range(n):
            x = layer(
                x, layer_params(params, i, bool(sparse[i])),
                num_heads=cfg.num_heads, nope=cfg.qk_nope_head_dim,
                rope=cfg.qk_rope_head_dim, dv=cfg.v_head_dim,
                latent=cfg.kv_lora_rank, eps=float(cfg.layer_norm_eps),
                theta=float(cfg.rope_theta), top_k=cfg.moe_top_k,
                scale=float(cfg.moe_routed_scale),
                offset=int(cfg.moe_expert_offset), control=control)
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
    projections, of the router, of the experts and of the dense MLP, the
    normed latent, q, k, v, the attention weights and outputs, the gated
    activations, the head's input), every weight of a product (cast as
    it stands, no scale) and the residual stream between blocks. Norm
    weights, the selection bias and what the program computes in float32
    (norms, scores, softmax, accumulators) stay float32. Put in the
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
