"""The plain reference against the program's ``GPTForCausalLM`` at
``gpt_tiny`` on the CPU, in both parameter layouts, through the
protocol the runners call: ``logits(params, ids, cfg, positions=None)``
and ``causal_lm_loss(params, ids, labels, cfg)``."""
import numpy as np
import pytest

# float32 on the CPU, same mathematics in another order of operations
# (fused QKV split, softmax, LayerNorm): differences are rounding, a few
# 1e-6 on logits of order 1. 1e-4 passes rounding and fails any change
# of mathematics (a dropped bias moves logits by 1e-2 or more).
ATOL = 1e-4


@pytest.fixture(scope="module", params=[False, True],
                ids=["module-stack", "stacked"])
def model(request):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    paddle.seed(7)
    m = GPTForCausalLM(gpt_tiny(stacked=request.param,
                                use_flash_attention=False))
    m.eval()
    return m


def _ids(model, shape=(2, 48)):
    return np.random.default_rng(0).integers(
        1, model.config.vocab_size, shape, dtype=np.int64)


def test_logits_agree(model):
    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import state_arrays

    from benchmarks.reference import gpt as ref
    ids = _ids(model)
    want = np.asarray(model(paddle.to_tensor(ids)).numpy())
    got = np.asarray(ref.logits(state_arrays(model)[0], ids, model.config))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    some = [3, 17, 40]
    part = np.asarray(ref.logits(state_arrays(model)[0], ids, model.config,
                                 positions=some))
    np.testing.assert_allclose(part, want[:, some], atol=ATOL, rtol=0)


def test_loss_agrees(model):
    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.models import GPTPretrainingCriterion

    from benchmarks.reference import gpt as ref
    ids = _ids(model)
    x = paddle.to_tensor(ids)
    want = float(GPTPretrainingCriterion()(model(x), x).numpy())
    got = float(ref.causal_lm_loss(state_arrays(model)[0], ids, ids,
                                   model.config))
    assert abs(got - want) <= ATOL


def test_padding_does_not_reach_earlier_positions(model):
    """What the serving parity relies on when it pads every sequence
    to one length."""
    from paddle_tpu.jit.functional import state_arrays

    from benchmarks.reference import gpt as ref
    params = state_arrays(model)[0]
    ids = _ids(model, (1, 40))
    padded = np.zeros((1, 64), np.int64)
    padded[:, :40] = ids
    a = np.asarray(ref.logits(params, ids, model.config))
    b = np.asarray(ref.logits(params, padded, model.config))
    np.testing.assert_allclose(b[:, :40], a, atol=1e-5, rtol=0)


def test_arrays_of_another_dtype_are_computed_in_float32(model):
    """Given bf16 arrays (``serve.weights_dtype``), the reference is the
    float32 mathematics of those rounded weights: float32 out, equal to
    what it gives for the same values stored as float32."""
    import jax.numpy as jnp
    from paddle_tpu.jit.functional import state_arrays

    from benchmarks.reference import gpt as ref
    params = {k: v.astype(jnp.bfloat16)
              for k, v in state_arrays(model)[0].items()}
    ids = _ids(model)
    got = np.asarray(ref.logits(params, ids, model.config))
    assert got.dtype == np.float32
    widened = {k: v.astype(jnp.float32) for k, v in params.items()}
    np.testing.assert_array_equal(
        got, np.asarray(ref.logits(widened, ids, model.config)))


def test_control_is_the_same_mathematics_one_step_down(model):
    """bfloat16 throughout: float32 out, not the reference's numbers,
    and within bfloat16's rounding of them (2**-8 a value, a few
    operations deep at this size)."""
    from paddle_tpu.jit.functional import state_arrays

    from benchmarks.reference import gpt as ref
    params, ids = state_arrays(model)[0], _ids(model)
    want = np.asarray(ref.logits(params, ids, model.config))
    got = np.asarray(ref.control_logits(params, ids, model.config))
    assert got.dtype == np.float32
    err = np.abs(got - want).max()
    assert 0.0 < err <= 0.05 * np.abs(want).max()
