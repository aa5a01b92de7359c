"""The plain reference against the program's ``GPTForCausalLM`` at
``gpt_tiny`` on the CPU, in both parameter layouts."""
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
    got = np.asarray(ref.logits(state_arrays(model)[0], ids,
                                num_heads=model.config.num_heads,
                                eps=model.config.layer_norm_eps))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    some = [3, 17, 40]
    part = np.asarray(ref.logits(state_arrays(model)[0], ids,
                                 num_heads=model.config.num_heads,
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
                                   num_heads=model.config.num_heads))
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
    a = np.asarray(ref.logits(params, ids,
                              num_heads=model.config.num_heads))
    b = np.asarray(ref.logits(params, padded,
                              num_heads=model.config.num_heads))
    np.testing.assert_allclose(b[:, :40], a, atol=1e-5, rtol=0)
