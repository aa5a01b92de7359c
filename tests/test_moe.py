"""MoE + expert parallelism tests.

Oracle pattern: the naive gate is a dense softmax mixture, checkable
against an explicit per-expert loop (reference test analog:
test_moe_api.py over moe_layer.py:261).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed.fleet as fleet
from paddle_tpu.distributed.mesh_utils import set_global_mesh
from paddle_tpu.incubate.distributed.models.moe import MoELayer
from paddle_tpu.jit import TrainStep

B, S, D, F, E = 4, 8, 16, 32, 4


def _x(seed=0):
    rng = np.random.RandomState(seed)
    return paddle.to_tensor(rng.randn(B, S, D).astype("float32"))


def _np(t):
    return np.asarray(t.numpy())


class TestGates:
    @pytest.mark.parametrize("gate", ["gshard", "switch", "naive"])
    def test_forward_shapes(self, gate):
        paddle.seed(0)
        moe = MoELayer(D, F, E, gate=gate)
        out = moe(_x())
        assert out.shape == [B, S, D]
        assert np.isfinite(_np(out)).all()
        assert moe.l_aux is not None
        assert np.isfinite(float(moe.l_aux.numpy()))

    def test_naive_gate_matches_dense_mixture(self):
        paddle.seed(0)
        moe = MoELayer(D, F, E, gate="naive")
        x = _x(1)
        out = _np(moe(x))

        xt = _np(x).reshape(-1, D)
        wg = _np(moe.gate_weight)
        logits = xt @ wg
        p = np.exp(logits - logits.max(1, keepdims=True))
        p /= p.sum(1, keepdims=True)
        ref = np.zeros_like(xt)

        def gelu(a):
            return 0.5 * a * (1 + np.tanh(
                np.sqrt(2 / np.pi) * (a + 0.044715 * a ** 3)))
        for e in range(E):
            h = gelu(xt @ _np(moe.w1)[e] + _np(moe.b1)[e])
            fe = h @ _np(moe.w2)[e] + _np(moe.b2)[e]
            ref += p[:, e:e + 1] * fe
        np.testing.assert_allclose(out.reshape(-1, D), ref, rtol=2e-4,
                                   atol=2e-4)

    def test_gshard_top2_combine_renormalized(self):
        paddle.seed(0)
        moe = MoELayer(D, F, E, gate="gshard", capacity_factor=100.0)
        x = _x(2)
        moe(x)  # no drops at huge capacity
        # re-derive combine weights: each token's two gate values sum to 1
        from paddle_tpu.incubate.distributed.models.moe import _gshard_gate
        import jax.numpy as jnp
        xt = jnp.asarray(_np(x).reshape(-1, D))
        wg = jnp.asarray(_np(moe.gate_weight))
        combine, aux = _gshard_gate(xt, wg, E, moe._capacity(B * S))
        sums = np.asarray(combine.sum(axis=(1, 2)))
        np.testing.assert_allclose(sums, np.ones_like(sums), atol=1e-5)

    def test_switch_capacity_drops_tokens(self):
        paddle.seed(0)
        # capacity 1 per expert: at most E tokens survive out of B*S
        moe = MoELayer(D, F, E, gate="switch", capacity_factor=E / (B * S))
        out = _np(moe(_x(3)))
        dropped = np.all(out.reshape(-1, D) == 0, axis=1).sum()
        assert dropped >= B * S - E

    def test_grads_flow_to_experts_and_gate(self):
        paddle.seed(0)
        moe = MoELayer(D, F, E, gate="gshard")
        out = moe(_x(4))
        out.sum().backward()
        for p in (moe.gate_weight, moe.w1, moe.w2, moe.b1):
            assert p.grad is not None
            assert np.abs(_np(p.grad)).sum() > 0, p.name

    def test_unknown_gate_raises(self):
        with pytest.raises(ValueError, match="unknown gate"):
            MoELayer(D, F, E, gate="bogus")


class _MoENet(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.proj = paddle.nn.Linear(D, D)
        self.moe = MoELayer(D, F, E, gate="gshard")

    def forward(self, x):
        return self.moe(self.proj(x))


class TestExpertParallel:
    def _run(self, hybrid, steps=3):
        paddle.seed(0)
        if hybrid:
            s = fleet.DistributedStrategy()
            s.hybrid_configs = hybrid
            fleet.init(is_collective=True, strategy=s)
        else:
            set_global_mesh(None)
        net = _MoENet()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=net.parameters())
        step = TrainStep(net, lambda o, y: ((o - y) ** 2).mean(), opt)
        x = _x(5)
        y = _x(6)
        losses = [float(step(x, y).numpy()) for _ in range(steps)]
        net_params = {n: _np(p) for n, p in net.named_parameters()}
        set_global_mesh(None)
        return losses, net_params, net

    @pytest.mark.slow
    def test_ep4_matches_single(self):
        import jax
        jax.config.update("jax_default_matmul_precision", "highest")
        single, p1, _ = self._run(None)
        ep, p2, _ = self._run({"dp_degree": 1, "ep_degree": 4})
        np.testing.assert_allclose(single, ep, rtol=1e-4, atol=1e-4)
        for n in p1:
            np.testing.assert_allclose(p1[n], p2[n], rtol=1e-4, atol=1e-4,
                                       err_msg=n)

    @pytest.mark.slow
    def test_dp2_ep4_matches_single(self):
        import jax
        jax.config.update("jax_default_matmul_precision", "highest")
        single, p1, _ = self._run(None)
        hyb, p2, _ = self._run({"dp_degree": 2, "ep_degree": 4})
        np.testing.assert_allclose(single, hyb, rtol=1e-4, atol=1e-4)
        for n in p1:
            np.testing.assert_allclose(p1[n], p2[n], rtol=1e-4, atol=1e-4,
                                       err_msg=n)

    def test_expert_weights_sharded_over_ep(self):
        _, _, net = self._run({"dp_degree": 1, "ep_degree": 4}, steps=1)
        w1 = net.moe.w1._data
        shard_experts = {sh.data.shape[0] for sh in w1.addressable_shards}
        assert shard_experts == {E // 4}


# ---------------------------------------------------------------------
# ops.moe.dropless_moe: what it was for SmallThinker, and what it learnt
def _dropless_moe_pr29(x, router_in, w_router, w_gate, w_up, w_down, *,
                       top_k, valid=None):
    """``ops.moe.dropless_moe`` as PR 29 wrote it (softmax over the
    chosen, ReLU gate, every expert held), kept here verbatim as the
    judge of "today's arguments give what they gave"."""
    import jax
    import jax.numpy as jnp
    t, hidden = x.shape
    n_experts = w_gate.shape[0]
    logits = jnp.dot(router_in.astype(jnp.float32),
                     w_router.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    top, experts = jax.lax.top_k(logits, top_k)
    experts, weights = experts.astype(jnp.int32), jax.nn.softmax(top, -1)
    if valid is not None:
        experts = jnp.where(valid[:, None], experts, n_experts)
    flat = experts.reshape(t * top_k)
    order = jnp.argsort(flat, stable=True)
    rows = x[order // top_k]
    group_sizes = jnp.bincount(
        flat, length=n_experts + 1)[:n_experts].astype(jnp.int32)
    gate = jax.lax.ragged_dot(rows, w_gate, group_sizes)
    up = jax.lax.ragged_dot(rows, w_up, group_sizes)
    act = (jax.nn.relu(gate) * up).astype(x.dtype)
    down = jax.lax.ragged_dot(act, w_down, group_sizes)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * top_k, dtype=order.dtype))
    per = down[back].astype(jnp.float32).reshape(t, top_k, hidden)
    w = weights
    if valid is not None:
        w = jnp.where(valid[:, None], w, 0.0)
        per = jnp.where(valid[:, None, None], per, 0.0)
    out = jnp.sum(per * w[:, :, None], axis=1).astype(x.dtype)
    return out, {"assignments": jnp.sum(group_sizes),
                 "experts_touched": jnp.sum(group_sizes > 0),
                 "max_expert_load": jnp.max(group_sizes)}


def _moe_arrays(seed, t=24, h=16, e_all=8, held=8, i=12, dtype="float32"):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    x, r = rng.standard_normal((2, t, h)).astype(np.float32)
    wr = rng.standard_normal((h, e_all)).astype(np.float32)
    wg, wu = rng.standard_normal((2, held, h, i)).astype(np.float32)
    wd = rng.standard_normal((held, i, h)).astype(np.float32)
    return [jnp.asarray(a, dtype) for a in (x, r, wr, wg, wu, wd)]


def _share_full_width(x, router_in, w_router, w_gate, w_up, w_down, *,
                      top_k, scoring, scale, activation, offset, valid):
    """A share's layer as it was before its capacity: all ``T * top_k``
    sorted rows handed to the grouped products (sigmoid routing)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.moe import _ACTIVATIONS, route_sigmoid_norm
    t, hidden = x.shape
    n_held = w_up.shape[0]
    act = _ACTIVATIONS[activation]
    experts, weights = route_sigmoid_norm(router_in, w_router, top_k, scale)
    experts = jnp.where((experts >= offset) & (experts < offset + n_held),
                        experts - offset, n_held)
    if valid is not None:
        experts = jnp.where(valid[:, None], experts, n_held)
    flat = experts.reshape(t * top_k)
    order = jnp.argsort(flat, stable=True)
    rows = x[order // top_k]
    sizes = jnp.bincount(flat, length=n_held + 1)[:n_held].astype(jnp.int32)
    if w_gate is None:
        h = act(jax.lax.ragged_dot(rows, w_up, sizes))
    else:
        h = act(jax.lax.ragged_dot(rows, w_gate, sizes)) \
            * jax.lax.ragged_dot(rows, w_up, sizes)
    down = jax.lax.ragged_dot(h.astype(x.dtype), w_down, sizes)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * top_k, dtype=order.dtype))
    per = down[back].astype(jnp.float32).reshape(t, top_k, hidden)
    here = experts < n_held
    per = jnp.where(here[:, :, None], per, 0.0)
    w = jnp.where(here, weights, 0.0)
    return jnp.sum(per * w[:, :, None], axis=1).astype(x.dtype)


class TestDroplessMoe:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("masked", [False, True],
                             ids=["all", "some-dead"])
    def test_todays_arguments_give_what_they_gave(self, masked, dtype):
        """Bit for bit, and the same lowered program but for the one
        count it now also returns."""
        import jax
        from paddle_tpu.ops.moe import dropless_moe
        args = _moe_arrays(0, dtype=dtype)
        valid = np.arange(24) % 3 != 0 if masked else None
        new, s_new = dropless_moe(*args, top_k=2, valid=valid)
        old, s_old = _dropless_moe_pr29(*args, top_k=2, valid=valid)
        assert np.array_equal(np.asarray(new, np.float32),
                              np.asarray(old, np.float32))
        for key, value in s_old.items():
            assert int(s_new[key]) == int(value), key
        assert int(s_new["local_assignments"]) == int(s_new["assignments"])

        def body(fn):
            text = jax.jit(lambda *a: fn(*a, top_k=2, valid=valid)[0]) \
                .lower(*args).as_text()
            return text[text.index("{"):]
        assert body(dropless_moe) == body(_dropless_moe_pr29)

    @pytest.mark.parametrize("activation", ["relu", "silu"])
    @pytest.mark.parametrize("scoring", ["softmax_top_k", "sigmoid_norm"])
    def test_scoring_and_activation_against_every_expert_computed(
            self, scoring, activation):
        """A share of 3 experts (2..4 of 8) against an explicit loop
        over tokens and their chosen experts."""
        from paddle_tpu.ops.moe import dropless_moe
        x, r, wr, wg, wu, wd = (np.asarray(a) for a in _moe_arrays(
            1, held=3))
        k, offset, scale = 3, 2, 2.5
        got, stats = dropless_moe(x, r, wr, wg, wu, wd, top_k=k,
                                  scoring=scoring, scale=scale,
                                  activation=activation, offset=offset)
        s = r @ wr
        if scoring == "sigmoid_norm":
            s = 1 / (1 + np.exp(-s))
        want = np.zeros_like(x)
        local = 0
        for tok in range(x.shape[0]):
            top = np.argsort(-s[tok])[:k]
            if scoring == "sigmoid_norm":
                w = scale * s[tok, top] / s[tok, top].sum()
            else:
                w = np.exp(s[tok, top] - s[tok, top].max())
                w /= w.sum()
            for weight, ex in zip(w, top):
                if not offset <= ex < offset + 3:
                    continue
                local += 1
                g = x[tok] @ wg[ex - offset]
                g = np.maximum(g, 0) if activation == "relu" \
                    else g / (1 + np.exp(-g))
                want[tok] += weight * ((g * (x[tok] @ wu[ex - offset]))
                                       @ wd[ex - offset])
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
        assert int(stats["assignments"]) == x.shape[0] * k
        assert int(stats["local_assignments"]) == local

    def test_shared_expert_is_added_once_a_token(self):
        from paddle_tpu.ops.moe import dropless_moe
        x, r, wr, wg, wu, wd = (np.asarray(a) for a in _moe_arrays(2))
        rng = np.random.default_rng(3)
        sg, su = rng.standard_normal((2, 16, 12)).astype(np.float32)
        sd = rng.standard_normal((12, 16)).astype(np.float32)
        kw = dict(top_k=2, activation="silu")
        routed, _ = dropless_moe(x, r, wr, wg, wu, wd, **kw)
        both, _ = dropless_moe(x, r, wr, wg, wu, wd, shared=(sg, su, sd),
                               **kw)
        g = x @ sg
        shared = ((g / (1 + np.exp(-g))) * (x @ su)) @ sd
        np.testing.assert_allclose(np.asarray(both) - np.asarray(routed),
                                   shared, atol=2e-4)

    def test_ungated_relu2_experts_in_a_latent_against_a_loop(self):
        """``w_gate=None``, ``activation="relu2"``: ``relu(x W1)^2 W2``,
        the experts reading a latent half as wide as what the router
        reads; a share of 3 experts (2..4 of 8), some rows dead,
        against an explicit loop over tokens and their chosen
        experts."""
        from paddle_tpu.ops.moe import dropless_moe
        rng = np.random.default_rng(5)
        t, wide, lat, inter, k, offset, scale = 24, 16, 8, 12, 3, 2, 5.0
        r = rng.standard_normal((t, wide)).astype(np.float32)
        x = rng.standard_normal((t, lat)).astype(np.float32)
        wr = rng.standard_normal((wide, 8)).astype(np.float32)
        w1 = rng.standard_normal((3, lat, inter)).astype(np.float32)
        w2 = rng.standard_normal((3, inter, lat)).astype(np.float32)
        valid = np.arange(t) % 4 != 0
        got, stats = dropless_moe(
            x, r, wr, None, w1, w2, top_k=k, scoring="sigmoid_norm",
            scale=scale, activation="relu2", offset=offset, valid=valid)
        s = 1 / (1 + np.exp(-(r @ wr)))
        want = np.zeros_like(x)
        local = 0
        for tok in np.flatnonzero(valid):
            top = np.argsort(-s[tok])[:k]
            w = scale * s[tok, top] / s[tok, top].sum()
            for weight, ex in zip(w, top):
                if offset <= ex < offset + 3:
                    local += 1
                    h = np.square(np.maximum(x[tok] @ w1[ex - offset], 0))
                    want[tok] += weight * (h @ w2[ex - offset])
        assert tuple(got.shape) == (t, lat)
        np.testing.assert_allclose(np.asarray(got), want, atol=5e-4,
                                   rtol=1e-5)
        assert not np.asarray(got)[~valid].any()
        assert int(stats["assignments"]) == int(valid.sum()) * k
        assert int(stats["local_assignments"]) == local
        # in blocks of rows it is the same numbers and the same counts
        many, s2 = dropless_moe(
            x, r, wr, None, w1, w2, top_k=k, scoring="sigmoid_norm",
            scale=scale, activation="relu2", offset=offset, valid=valid,
            token_block=8)
        np.testing.assert_allclose(np.asarray(many), np.asarray(got),
                                   atol=1e-5, rtol=0)
        assert {n: int(v) for n, v in stats.items()} == \
            {n: int(v) for n, v in s2.items()}

    def test_a_gate_makes_relu2_a_gated_expert(self):
        """The activation is the gate's where there is a gate:
        ``(relu(x Wg)^2 * (x Wu)) Wd``."""
        from paddle_tpu.ops.moe import dropless_moe
        x, r, wr, wg, wu, wd = (np.asarray(a) for a in _moe_arrays(6))
        got, _ = dropless_moe(x, r, wr, wg, wu, wd, top_k=8,
                              scoring="sigmoid_norm", activation="relu2")
        s = 1 / (1 + np.exp(-(r @ wr)))
        w = s / s.sum(1, keepdims=True)
        want = sum(w[:, e:e + 1] * ((np.square(np.maximum(x @ wg[e], 0))
                                     * (x @ wu[e])) @ wd[e])
                   for e in range(8))
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-3,
                                   rtol=1e-4)

    @pytest.mark.parametrize("masked", [False, True], ids=["all", "some-dead"])
    @pytest.mark.parametrize("routing", ["even", "to-the-held"])
    @pytest.mark.parametrize("kind", ["gated-silu", "ungated-relu2"])
    def test_a_share_hands_its_products_the_capacitys_rows(
            self, kind, routing, masked):
        """64 tokens x 4 of a router of 16 outputs, experts 4..7 held:
        256 sorted rows, a capacity of 128. Routed evenly (about 64 rows
        held) the products are handed 128 and give what handing all 256
        gives; a router biased to the held experts sends them all 256
        and the layer, handed all, is still dropless."""
        from paddle_tpu.ops.moe import dropless_moe
        rng = np.random.default_rng(8)
        t, wide, k, offset, held = 64, 16, 4, 4, 4
        gated = kind == "gated-silu"
        lat = wide if gated else 8
        act = (lambda g: g / (1 + np.exp(-g))) if gated \
            else (lambda g: np.square(np.maximum(g, 0)))
        r = rng.standard_normal((t, wide)).astype(np.float32)
        x = rng.standard_normal((t, lat)).astype(np.float32)
        wr = 0.5 * rng.standard_normal((wide, 16)).astype(np.float32)
        if routing == "to-the-held":
            r[:, 0] = 1.0
            wr[0, offset:offset + held] += 20.0
        wg = rng.standard_normal((held, lat, 12)).astype(np.float32) \
            if gated else None
        wu = rng.standard_normal((held, lat, 12)).astype(np.float32)
        wd = rng.standard_normal((held, 12, lat)).astype(np.float32)
        valid = np.arange(t) % 5 != 0 if masked else None
        kw = dict(top_k=k, scoring="sigmoid_norm", scale=2.5, offset=offset,
                  activation="silu" if gated else "relu2", valid=valid)
        got, stats = dropless_moe(x, r, wr, wg, wu, wd, **kw)
        narrow = routing == "even"
        assert (int(stats["narrow_calls"]), int(stats["wide_calls"])) == \
            ((1, 0) if narrow else (0, 1))
        assert (int(stats["local_assignments"]) <= 128) == narrow
        # today's full width: every sorted row handed to the products
        want = _share_full_width(x, r, wr, wg, wu, wd, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        # the same tokens twice, a block of 64 at a time: each block
        # takes the branch its own rows choose, and the call counts once
        twice = dict(kw, valid=None if valid is None
                     else np.concatenate([valid, valid]), token_block=t)
        both, s2 = dropless_moe(*(np.concatenate([a, a]) for a in (x, r)),
                                wr, wg, wu, wd, **twice)
        np.testing.assert_allclose(np.asarray(both),
                                   np.concatenate([got, got]), atol=1e-6)
        assert (int(s2["narrow_calls"]), int(s2["wide_calls"])) == \
            (int(stats["narrow_calls"]), int(stats["wide_calls"]))
        if narrow:
            return
        s = 1 / (1 + np.exp(-(r @ wr)))
        loop = np.zeros_like(x)
        for tok in range(t) if valid is None else np.flatnonzero(valid):
            top = np.argsort(-s[tok])[:k]
            for weight, ex in zip(2.5 * s[tok, top] / s[tok, top].sum(),
                                  top):
                if offset <= ex < offset + held:
                    e = ex - offset
                    h = act(x[tok] @ (wg[e] if gated else wu[e]))
                    if gated:
                        h = h * (x[tok] @ wu[e])
                    loop[tok] += weight * (h @ wd[e])
        np.testing.assert_allclose(np.asarray(got), loop, atol=5e-4,
                                   rtol=1e-4)

    def test_a_whole_layer_has_no_capacity_and_no_branch(self):
        """Every expert held: no ``conditional`` in the program and no
        count beyond the four; a share of them has both."""
        import jax
        from paddle_tpu.ops.moe import dropless_moe
        args = _moe_arrays(9, t=64)

        def program(**kw):
            fn = jax.jit(lambda *a: dropless_moe(*a, top_k=4, **kw))
            return fn.lower(*args).compile().as_text(), fn(*args)[1]
        text, stats = program()
        assert " conditional(" not in text
        assert set(stats) == {"assignments", "local_assignments",
                              "experts_touched", "max_expert_load"}
        share = _moe_arrays(9, t=64, e_all=32)
        text = jax.jit(lambda *a: dropless_moe(*a, top_k=4, offset=8)[0]) \
            .lower(*share).compile().as_text()
        assert " conditional(" in text

    def test_unknown_options_raise(self):
        from paddle_tpu.ops.moe import dropless_moe
        args = _moe_arrays(4)
        with pytest.raises(ValueError, match="scoring"):
            dropless_moe(*args, top_k=2, scoring="tanh")
        with pytest.raises(ValueError, match="activation"):
            dropless_moe(*args, top_k=2, activation="gelu")
