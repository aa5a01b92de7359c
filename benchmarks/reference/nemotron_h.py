"""The plain reference of the Nemotron-H decoder (NVIDIA,
NVIDIA-Nemotron-3-Super-120B-A12B ``config.json``, ``model_type``
``nemotron_h``): forward pass and causal-LM loss in straightforward
``jax.numpy``, float32, at ``jax.default_matmul_precision("highest")``;
no kernels, no cache, no chunked form, no sorting of tokens by expert.
Every layer is one mixer, ``x = x + mixer_l(RMSNorm(x; g_l))``,
``RMSNorm(x; g) = x * rsqrt(mean(x^2) + eps) * g``, the mixer named by
letter ``l`` of ``cfg.pattern`` (``u`` the normed input, ``T`` tokens):

- ``M`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC_t = silu(b_c +
  sum_j w_c[:, j] * xBC_{t-K+1+j})`` (depthwise, causal, zeros before
  0); ``[xs | B | C] = xBC`` with ``xs [T, H, P]``, ``B, C [T, G, N]``,
  head ``h`` reading group ``h // (H / G)``; ``d_t = softplus(dt_t +
  dt_bias)``, ``a_t = exp(-exp(A_log) * d_t)``; ``S_t[h] = a_t[h] *
  S_{t-1}[h] + d_t[h] * outer(xs_t[h], B_t[g])`` from ``S_{-1} = 0``,
  **position by position** (``lax.scan``); ``y_t[h] = S_t[h] C_t[g] +
  D[h] * xs_t[h]``; ``y = y * silu(z)``, RMS-normalised within each of
  the ``G`` groups of channels, times ``g_n``; ``mixer = y W_out``.
- ``*``: ``q = u Wq -> [T, heads, d]``, ``k = u Wk``, ``v = u Wv ->
  [T, kv_heads, d]``, no biases, no positions of any kind; scores ``q_i
  . k_j / sqrt(d)`` over ``j <= i``; K/V head ``g`` serves query heads
  ``g * heads / kv_heads ..``; ``mixer = concat(softmax(scores) v) Wo``.
- ``E``: ``s = sigmoid(u Wr)``, ``C`` the ``top_k`` experts of largest
  ``s``, ``w_e = scale * s_e / sum_{c in C} s_c``; ``v = u W_down``
  (the latent); ``mixer = (sum_{e in C, e held} w_e * relu(v W1_e)^2
  W2_e) W_up + relu(u Ws1)^2 Ws2``.

**The share.** The parameters hold ``held = W1.shape[0]`` of the
router's experts, ``cfg.moe_expert_offset .. + held - 1``: one chip's
share of an expert-parallel layer. ``w_e`` is normalised over all the
chosen, only the held ones are computed, ``W_up`` is applied to that
partial sum (it is linear), the shared expert is whole, and that
partial result goes on to the next layer: what the absent experts
would add is left out, here as in the program.

After the last layer RMSNorm and an untied head. No selection bias on
the router and no multi-token-prediction module are built.

It is given the model's own parameter arrays (``state_arrays(model)``'s
first dict; whatever their dtype, each is cast to float32 where it is
used) and the program's config object, of which it reads ``pattern``,
``num_heads``, ``num_kv_heads``, ``head_dim``, ``layer_norm_eps``,
``mamba_num_heads``, ``mamba_head_dim``, ``mamba_n_groups``,
``ssm_state_size``, ``moe_top_k``, ``moe_routed_scale`` and
``moe_expert_offset``. It imports nothing of the program.

So that one ``[1, 4096]`` sequence of the published widths fits beside
a served model, queries go in blocks and experts one at a time (every
token through expert ``e``, weighted by its router weight for ``e``,
which is 0 where ``e`` was not chosen; each expert's weights are cast
as it is reached). One layer is jitted and called once per layer from a
Python loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_KEYS = {
    "M": {"wi": "in_w", "wc": "conv_w", "bc": "conv_b", "dtb": "dt_bias",
          "alog": "a_log", "dskip": "d_skip", "gn": "norm_w",
          "wo": "out_w"},
    "*": {"wq": "q_w", "wk": "k_w", "wv": "v_w", "wo": "out_w"},
    "E": {"wr": "router_w", "wdn": "latent_down_w", "w1": "expert_up_w",
          "w2": "expert_down_w", "wup": "latent_up_w",
          "s1": "shared_up_w", "s2": "shared_down_w"},
}
QUERY_BLOCK = 256


def layer_params(params: dict, i: int, kind: str) -> dict:
    p = {k: params[f"backbone.layers.{i}.mixer.{name}"]
         for k, name in _KEYS[kind].items()}
    p["g"] = params[f"backbone.layers.{i}.norm.weight"]
    return p


def num_layers(params: dict) -> int:
    return 1 + max(int(k.split(".")[2]) for k in params
                   if k.startswith("backbone.layers."))


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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# ----------------------------------------------------------- 'M'
def recurrence(xs, b, c, d, a, dskip, s0=None):
    """The selective recurrence, a position at a time. xs: [T, H, P];
    b, c: [T, G, N]; d, a: [T, H]; dskip: [H]. Returns ``(y [T, H, P],
    S_T [H, P, N])``."""
    t, heads, p = xs.shape
    groups, n = b.shape[1:]
    per = heads // groups

    def step(s, at):
        xs_t, b_t, c_t, d_t, a_t = at
        b_h = jnp.repeat(b_t, per, axis=0)                   # [H, N]
        c_h = jnp.repeat(c_t, per, axis=0)
        s = a_t[:, None, None] * s + d_t[:, None, None] \
            * xs_t[:, :, None] * b_h[:, None, :]
        return s, jnp.sum(s * c_h[:, None, :], axis=-1) \
            + dskip[:, None] * xs_t

    s_last, y = jax.lax.scan(
        step, jnp.zeros((heads, p, n), jnp.float32) if s0 is None else s0,
        (xs, b, c, d, a))
    return y, s_last


def mamba(u, p, *, heads, head_dim, groups, state, eps, rnd=_same,
          wt=_f32):
    """The ``M`` mixer over ``u`` [T, hidden] (one sequence from a zero
    state)."""
    t = u.shape[0]
    inner, gn = heads * head_dim, groups * state
    zxd = rnd(u) @ wt(p["wi"])
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:inner + inner + 2 * gn],
                  zxd[:, inner + inner + 2 * gn:])
    wc, k = _f32(p["wc"]), p["wc"].shape[1]
    padded = jnp.pad(rnd(xbc), ((k - 1, 0), (0, 0)))
    xbc = rnd(jax.nn.silu(_f32(p["bc"]) + sum(
        padded[j:j + t] * wc[:, j] for j in range(k))))
    xs = xbc[:, :inner].reshape(t, heads, head_dim)
    b = xbc[:, inner:inner + gn].reshape(t, groups, state)
    c = xbc[:, inner + gn:].reshape(t, groups, state)
    d = jax.nn.softplus(dt + _f32(p["dtb"]))
    a = jnp.exp(-jnp.exp(_f32(p["alog"])) * d)
    y, _ = recurrence(xs, b, c, d, a, _f32(p["dskip"]))
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(
        t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return rnd(y.reshape(t, inner) * _f32(p["gn"])) @ wt(p["wo"])


# ----------------------------------------------------------- '*'
def attention(u, p, *, num_heads, num_kv_heads, head_dim, rnd=_same,
              wt=_f32):
    s = u.shape[0]
    group = num_heads // num_kv_heads
    h = rnd(u)
    q = rnd(h @ wt(p["wq"])).reshape(s, num_kv_heads, group, head_dim)
    k = rnd(h @ wt(p["wk"])).reshape(s, num_kv_heads, head_dim)
    v = rnd(h @ wt(p["wv"])).reshape(s, num_kv_heads, head_dim)
    blk = math.gcd(s, QUERY_BLOCK)
    cols = jnp.arange(s)[None, :]

    def attend(i):
        rows = i * blk + jnp.arange(blk)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        sc = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(head_dim)
        pr = jax.nn.softmax(jnp.where(cols <= rows, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", rnd(pr), v)

    a = jax.lax.map(attend, jnp.arange(s // blk)).reshape(
        s, num_heads * head_dim)
    return rnd(a) @ wt(p["wo"])


# ----------------------------------------------------------- 'E'
def router_weights(u, wr, *, top_k, scale, wt=_f32):
    """``[T, E_all]``: ``scale * s_e / sum of the chosen s`` where
    expert ``e`` is among a token's ``top_k`` largest ``s = sigmoid(u
    Wr)``, 0 elsewhere."""
    scores = jax.nn.sigmoid(u @ wt(wr))
    top, chosen = jax.lax.top_k(scores, top_k)
    return jnp.zeros_like(scores).at[
        jnp.arange(u.shape[0])[:, None], chosen].set(
            scale * top / jnp.sum(top, axis=-1, keepdims=True))


def experts(u, p, *, top_k, scale, offset, shared=True, rnd=_same,
            wt=_f32):
    """The ``E`` mixer for ``u`` [T, hidden]: the held experts' part of
    the routed sum in the latent (``p["w1"]`` holds experts ``offset
    ..``), projected up, and with ``shared`` the shared expert."""
    held = p["w1"].shape[0]
    ur = rnd(u)
    weight = router_weights(ur, p["wr"], top_k=top_k, scale=scale, wt=wt)
    weight = jax.lax.dynamic_slice_in_dim(weight, offset, held, axis=1)
    latent = rnd(ur @ wt(p["wdn"]))

    def expert(acc, e):
        w1, w2, w_e = e
        return acc + w_e[:, None] * (
            rnd(_relu2(latent @ wt(w1))) @ wt(w2)), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(latent),
                             (p["w1"], p["w2"], weight.T))
    y = rnd(routed) @ wt(p["wup"])
    if shared:
        y = y + rnd(_relu2(ur @ wt(p["s1"]))) @ wt(p["s2"])
    return y


@functools.partial(jax.jit, static_argnames=(
    "kind", "num_heads", "num_kv_heads", "head_dim", "eps", "heads",
    "mamba_head_dim", "groups", "state", "top_k", "scale", "offset",
    "control"))
def layer(x, p, *, kind, num_heads, num_kv_heads, head_dim, eps, heads,
          mamba_head_dim, groups, state, top_k, scale, offset,
          control=False):
    """One layer over ``x`` [S, hidden] (one sequence). With
    ``control`` the residual stream, every activation that enters a
    product and the product's weight are rounded to float8."""
    rnd, wt = (_fp8, _fp8_weight) if control else (_same, _f32)
    with jax.default_matmul_precision("highest"):
        x = rnd(x)      # the residual stream as the last layer left it
        u = _rms_norm(x, _f32(p["g"]), eps)
        if kind == "M":
            return x + mamba(u, p, heads=heads, head_dim=mamba_head_dim,
                             groups=groups, state=state, eps=eps, rnd=rnd,
                             wt=wt)
        if kind == "*":
            return x + attention(u, p, num_heads=num_heads,
                                 num_kv_heads=num_kv_heads,
                                 head_dim=head_dim, rnd=rnd, wt=wt)
        return x + experts(u, p, top_k=top_k, scale=scale, offset=offset,
                           rnd=rnd, wt=wt)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, w_norm, w_head, *, eps, control):
    rnd, wt = (_fp8, _fp8_weight) if control else (_same, _f32)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(h, w_norm.astype(jnp.float32), eps)
        return rnd(h) @ wt(w_head)


def hidden_states(params: dict, ids, cfg, control: bool = False):
    """``ids`` [B, S] -> the last layer's output [B, S, H] (before the
    final RMSNorm), a sequence at a time."""
    wte = params["backbone.embeddings"]
    kinds = cfg.pattern[:num_layers(params)]
    out = []
    for row in jnp.asarray(ids):
        x = jnp.take(wte, row, axis=0).astype(jnp.float32)
        for i, kind in enumerate(kinds):
            x = layer(
                x, layer_params(params, i, kind), kind=kind,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, eps=float(cfg.layer_norm_eps),
                heads=cfg.mamba_num_heads,
                mamba_head_dim=cfg.mamba_head_dim,
                groups=cfg.mamba_n_groups, state=cfg.ssm_state_size,
                top_k=cfg.moe_top_k, scale=float(cfg.moe_routed_scale),
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
    return _head(h, params["backbone.norm_f.weight"], params["lm_head"],
                 eps=float(cfg.layer_norm_eps), control=control)


def control_logits(params: dict, ids, cfg, positions=None):
    """The control (``run.py --control``): the same mathematics one
    precision step below the bfloat16 this configuration states, as
    ``reference/kexaone.py`` has it. Everything the program holds in
    bfloat16 is held in float8 e4m3: every activation that enters a
    product (the normed inputs of the projections and of the router,
    the convolution's inputs and outputs, q, k, v, the attention
    weights and outputs, the latent, the experts' activations and their
    weighted sum, the gated and normed ``y``, the head's input), every
    weight of a product (cast as it stands, no scale) and the residual
    stream between layers (the embedding rows among it). What the
    program computes in float32 stays float32: norms and their weights,
    the convolution's kernel, ``dt_bias``, ``A_log``, ``D``, the steps,
    the decays and the state, scores, softmax and accumulators. Put in
    the program's place it has to come out as not correct."""
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
