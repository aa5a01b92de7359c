"""GPT flagship model tests (paddle_tpu/models/gpt.py)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, GPTModel,
                               GPTPretrainingCriterion, gpt_tiny)


def make(batch=2, seq=16, **kw):
    paddle.seed(0)
    cfg = gpt_tiny(use_flash_attention=False, **kw)
    m = GPTForCausalLM(cfg)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, seq))
        .astype("int64"))
    return m, cfg, ids


class TestGPTForward:
    def test_logits_shape(self):
        m, cfg, ids = make()
        assert m(ids).shape == [2, 16, cfg.vocab_size]

    def test_tied_embedding_logits(self):
        m, cfg, ids = make()
        m.eval()
        h = m.gpt(ids).numpy()                       # [B,S,H]
        w = m.gpt.embeddings.word_embeddings.weight.numpy()
        np.testing.assert_allclose(m(ids).numpy(), h @ w.T, rtol=1e-4,
                                   atol=1e-4)

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        m, cfg, ids = make()
        m.eval()
        base = m(ids).numpy()
        mut = ids.numpy().copy()
        mut[:, -1] = (mut[:, -1] + 1) % cfg.vocab_size
        out2 = m(paddle.to_tensor(mut)).numpy()
        np.testing.assert_allclose(base[:, :-1], out2[:, :-1], rtol=1e-4,
                                   atol=1e-5)
        assert not np.allclose(base[:, -1], out2[:, -1], atol=1e-5)

    def test_flash_matches_reference_path(self):
        """use_flash=False XLA path == flash path numerics (CPU: both XLA)."""
        m, cfg, ids = make()
        m.eval()
        base = m(ids).numpy()
        for lyr in m.gpt.layers:
            lyr.attn.use_flash = True
        np.testing.assert_allclose(m(ids).numpy(), base, rtol=1e-4, atol=1e-5)


class TestCriterion:
    def test_shift_by_one_vs_numpy(self):
        crit = GPTPretrainingCriterion()
        rng = np.random.RandomState(0)
        logits = rng.randn(2, 5, 7).astype("float32")
        labels = rng.randint(0, 7, (2, 5)).astype("int64")
        loss = float(crit(paddle.to_tensor(logits),
                          paddle.to_tensor(labels)).numpy())
        lg = logits[:, :-1].reshape(-1, 7)
        lb = labels[:, 1:].reshape(-1)
        e = np.exp(lg - lg.max(1, keepdims=True))
        p = e / e.sum(1, keepdims=True)
        expect = -np.log(p[np.arange(len(lb)), lb]).mean()
        np.testing.assert_allclose(loss, expect, rtol=1e-4)

    def test_ignore_index(self):
        crit = GPTPretrainingCriterion(ignore_index=-100)
        logits = np.random.randn(1, 4, 5).astype("float32")
        labels = np.array([[1, 2, -100, -100]], "int64")
        loss = float(crit(paddle.to_tensor(logits),
                          paddle.to_tensor(labels)).numpy())
        assert np.isfinite(loss)


class TestGPTTrain:
    @pytest.mark.slow
    def test_train_step_decreases_loss(self):
        m, cfg, ids = make(seq=32)
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        from paddle_tpu.jit import TrainStep
        step = TrainStep(m, lambda o, y: crit(o, y), opt)
        l0 = float(step(ids, ids).numpy())
        for _ in range(10):
            l = float(step(ids, ids).numpy())
        assert l < l0

    def test_dropout_applied_in_train(self):
        m, cfg, ids = make(dropout=0.5)
        m.train()
        a = m(ids).numpy()
        b = m(ids).numpy()
        assert not np.allclose(a, b)   # dropout keys advance
        m.eval()
        c = m(ids).numpy()
        d = m(ids).numpy()
        np.testing.assert_allclose(c, d)

    def test_num_params(self):
        m, cfg, ids = make()
        n = m.num_params()
        # embedding 256*64 + pos 128*64 + 2 blocks + ln_f
        assert n > 256 * 64


class TestMLPBarrier:
    """Over more than one position a row ``GPTMLP.forward`` holds
    ``fc_in``'s output behind an ``optimization_barrier`` (PERF.md, PR
    32): it changes what the TPU's compiler may fuse, and nothing else.
    Loss and every parameter's gradient through the module stack are
    bitwise those of the plain expression ``fc_out(gelu(fc_in(x)))`` in
    its place."""

    @staticmethod
    def _plain(self, x):
        from paddle_tpu.nn import functional as F
        return self.dropout(self.fc_out(F.gelu(self.fc_in(x),
                                               approximate=True)))

    @staticmethod
    def _eager(m, ids):
        crit = GPTPretrainingCriterion()
        m.clear_gradients()
        loss = crit(m(ids), ids)
        loss.backward()
        return loss.numpy(), {n: p.grad.numpy()
                              for n, p in m.named_parameters()}

    @staticmethod
    def _jitted(m, ids):
        import jax
        from paddle_tpu.jit.functional import functional_call, state_arrays
        crit = GPTPretrainingCriterion()
        params, buffers = state_arrays(m)

        def loss_fn(params):
            logits = functional_call(m, params, buffers, ids)
            return crit(logits, ids)._data
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        return np.asarray(loss), {n: np.asarray(g) for n, g in grads.items()}

    @staticmethod
    def _barriers(m, ids):
        import jax
        from paddle_tpu.jit.functional import functional_call, state_arrays
        params, buffers = state_arrays(m)
        return str(jax.make_jaxpr(lambda p: functional_call(
            m, p, buffers, ids))(params)).count("optimization_barrier")

    @pytest.mark.parametrize("what", ["loss", "grads"])
    @pytest.mark.parametrize("mode", ["eager", "jit"])
    def test_equals_the_unbarriered_expression(self, monkeypatch, mode,
                                               what):
        from paddle_tpu.models.gpt import GPTMLP
        m, cfg, ids = make()
        m.eval()
        run = self._eager if mode == "eager" else self._jitted
        loss, grads = run(m, ids)
        assert self._barriers(m, ids) == cfg.num_layers    # one a block
        assert self._barriers(m, ids[:, :1]) == 0   # a decode step's shape
        monkeypatch.setattr(GPTMLP, "forward", self._plain)
        assert self._barriers(m, ids) == 0
        plain_loss, plain_grads = run(m, ids)
        if what == "loss":
            assert np.isfinite(loss) and loss.tobytes() == \
                plain_loss.tobytes()
            return
        assert len(grads) == len(plain_grads) > 10
        for name, g in grads.items():
            assert np.abs(g).max() > 0 or "bias" in name, name
            assert g.tobytes() == plain_grads[name].tobytes(), name
