"""Step-time ablation for the BERT-base pretraining config (round-4
verdict item 4: the 45.2% vs gpt2-medium 51.8% MFU gap at s=512).

Same methodology as ablate_13b.py: knock one component out of the
compiled train step, re-time the WHOLE window, attribute end-to-end
(an isolated microbenchmark times its own dispatch, not the step).

Usage: python tools/ablate_bert.py [variant ...]
  base        unmodified step (b=32 s=512 AMP O2, bench.py config)
  noattn      self-attention replaced by identity (removes s^2 matmuls)
  nomlm       MLM decoder matmul over the 30k vocab replaced by a
              1024-wide slice (attributes the tied-embedding projection)
  notransform MLM transform Linear+LN removed (decoder kept)
  nonsp       NSP head + pooler removed from the loss
  noembed     token_type + position adds removed (word emb kept)
  nopooler    pooler tanh removed (NSP reads h[:,0] directly)
  gptcrit     single CE over full seq like the GPT criterion (removes
              the ignore_index masking machinery)
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(variant, steps=20, windows=3, batch=32, seq=512):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   BertPretrainingCriterion)
    from paddle_tpu.models import bert as bert_mod

    paddle.seed(0)
    cfg = BertConfig(hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    patches = []

    def patch(obj, name, repl):
        patches.append((obj, name, getattr(obj, name)))
        setattr(obj, name, repl)

    if variant == "noattn":
        cls = bert_mod.BertSelfAttention
        patch(cls, "forward", lambda self, x, attn_mask=None: x)
    elif variant == "noembed":
        cls = bert_mod.BertEmbeddings

        def word_only(self, input_ids, token_type_ids=None):
            return self.dropout(self.layer_norm(
                self.word_embeddings(input_ids)))
        patch(cls, "forward", word_only)
    elif variant == "nopooler":
        cls = bert_mod.BertPooler
        patch(cls, "forward", lambda self, h: h[:, 0])
    elif variant in ("nomlm", "notransform"):
        cls = BertForPretraining

        def fwd(self, input_ids, token_type_ids=None, attention_mask=None,
                _variant=variant):
            seq_out, pooled = self.bert(input_ids, token_type_ids,
                                        attention_mask)
            from paddle_tpu.tensor import linalg
            w = self.bert.embeddings.word_embeddings.weight
            if _variant == "notransform":
                h = seq_out
            else:
                h = self.transform_ln(F.gelu(self.transform(seq_out),
                                             approximate=True))
            if _variant == "nomlm":
                mlm_logits = linalg.matmul(h, w[:1024], transpose_y=True)
            else:
                mlm_logits = linalg.matmul(h, w, transpose_y=True)
            nsp_logits = self.nsp_head(pooled)
            return mlm_logits, nsp_logits
        patch(cls, "forward", fwd)

    model = BertForPretraining(cfg)
    crit = BertPretrainingCriterion(ignore_index=-1000)

    if variant == "gptcrit":
        def loss_fn(out, labels, nsp):
            mlm_logits, _ = out
            b, s, v = mlm_logits.shape
            return F.cross_entropy(mlm_logits.reshape([b * s, v]),
                                   labels.reshape([b * s]))
    elif variant == "nonsp":
        def loss_fn(out, labels, nsp):
            return crit(out, labels, None)
    else:
        def loss_fn(out, labels, nsp):
            return crit(out, labels, nsp)

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = TrainStep(model, loss_fn, opt, amp_level="O2")
    rng = np.random.RandomState(0)
    vocab_hi = 1024 if variant == "nomlm" else cfg.vocab_size
    ids = paddle.to_tensor(
        rng.randint(0, vocab_hi, (batch, seq)).astype("int64"))
    nsp = paddle.to_tensor(rng.randint(0, 2, (batch,)).astype("int64"))
    try:
        loss = step.run_steps(steps, ids, ids, nsp, n_inputs=1)
        assert np.isfinite(float(loss.numpy()))
        best = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            loss = step.run_steps(steps, ids, ids, nsp, n_inputs=1)
            float(loss.numpy())
            best = min(best, (time.perf_counter() - t0) / steps)
    except Exception as e:
        print(f"{variant:12s}  FAILED: {type(e).__name__}: {e}")
        for obj, name, orig in patches:
            setattr(obj, name, orig)
        return None
    for obj, name, orig in patches:
        setattr(obj, name, orig)
    tok_s = batch * seq / best
    print(f"{variant:12s}  {best * 1e3:8.2f} ms/step  {tok_s:10.0f} tok/s")
    return best


if __name__ == "__main__":
    variants = sys.argv[1:] or ["base", "noattn", "nomlm", "notransform",
                                "nonsp", "noembed", "nopooler", "gptcrit"]
    base = None
    for v in variants:
        t = run(v)
        if v == "base":
            base = t
        elif base and t:
            print(f"{'':12s}  -> {v} saves {(base - t) / base * 100:.1f}% "
                  f"of the base step")
