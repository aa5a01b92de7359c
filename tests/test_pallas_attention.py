"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh).

Oracle: the dense XLA softmax reference at highest matmul precision —
mirrors the reference's OpTest numpy-oracle pattern (SURVEY §4.1) for the
flash_attn op (/root/reference/paddle/phi/api/yaml/ops.yaml:546).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_attention import _mha_reference, mha

B, H, S, D = 1, 2, 256, 64


def _rand(seed):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, H, S, D), jnp.float32),
            jnp.asarray(rng.randn(B, H, S, D), jnp.float32),
            jnp.asarray(rng.randn(B, H, S, D), jnp.float32))


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _rand(0)
    out = mha(q, k, v, causal)
    ref = _mha_reference(q, k, v, causal, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_reference(causal):
    q, k, v = _rand(1)
    sc = 1.0 / np.sqrt(D)

    def loss_pallas(q, k, v):
        return jnp.sum(jnp.square(mha(q, k, v, causal)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(_mha_reference(q, k, v, causal, sc)))

    gp = jax.grad(loss_pallas, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        a, b = np.asarray(a), np.asarray(b)
        err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)
        assert err < 1e-4, (name, err)


def test_backward_bf16_inputs():
    q, k, v = _rand(2)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(q, k, v):
        return jnp.sum(jnp.square(mha(q, k, v, True).astype(jnp.float32)))

    gq, gk, gv = jax.grad(loss, (0, 1, 2))(q, k, v)
    assert gq.dtype == jnp.bfloat16
    for g in (gq, gk, gv):
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_backward_kernels_match_reference(causal):
    """Exercise the blocked dq/dkv KERNELS directly (at S=256 the public
    mha VJP dispatches to the XLA recompute fallback, so without this the
    ~200 kernel lines would ship untested)."""
    from paddle_tpu.ops.pallas_attention import _mha_bwd, _mha_fwd
    q, k, v = _rand(4)
    sc = 1.0 / np.sqrt(D)
    rng = np.random.RandomState(9)
    g = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)

    out, lse = _mha_fwd(q, k, v, causal, sc, 128, 128)
    dq, dk, dv = _mha_bwd(q, k, v, out, lse, g, causal, sc, 128, 128)

    _, vjp = jax.vjp(lambda a, b, c: _mha_reference(a, b, c, causal, sc),
                     q, k, v)
    rq, rk, rv = vjp(g)
    for a, b, name in zip((dq, dk, dv), (rq, rk, rv), "qkv"):
        a, b = np.asarray(a), np.asarray(b)
        err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)
        assert err < 1e-4, (name, err)


def test_unaligned_seq_raises():
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(B, H, 192, D), jnp.float32)
    with pytest.raises(ValueError, match="multiples of the block"):
        mha(q, q, q, False)


def test_lse_residual_shape():
    from paddle_tpu.ops.pallas_attention import _mha_fwd, LANES
    q, k, v = _rand(3)
    out, lse = _mha_fwd(q, k, v, True, 1.0 / np.sqrt(D), 128, 128)
    assert out.shape == (B, H, S, D)
    assert lse.shape == (B * H, S, LANES)
    # lanes are replicated copies of the row statistic
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]),
                               np.asarray(lse[:, :, 64]), rtol=0, atol=0)


def test_preferred_gates_by_seq_length(monkeypatch):
    # measured policy (PERF.md): XLA softmax path below FLAGS_flash_min_seqlen,
    # Pallas kernel at/above it — preferred() implements the routing
    from paddle_tpu.framework import place
    from paddle_tpu.ops import flash_attention as fa
    import paddle_tpu

    monkeypatch.setattr(place, "on_tpu", lambda: True)
    mk = lambda s: jnp.zeros((2, s, 4, 64), jnp.bfloat16)
    assert fa.supported(mk(512), mk(512), mk(512), None, True)
    assert not fa.preferred(mk(512), mk(512), mk(512), None, True)
    assert fa.preferred(mk(2048), mk(2048), mk(2048), None, True)
    paddle_tpu.set_flags({"FLAGS_flash_min_seqlen": 512})
    try:
        assert fa.preferred(mk(512), mk(512), mk(512), None, True)
    finally:
        paddle_tpu.set_flags({"FLAGS_flash_min_seqlen": 2048})
