"""The plain reference of ``negated_gpt.NegatedGPT``: the GPT
reference's logits, negated; the protocol of benchmarks/README.md."""
import jax
import jax.numpy as jnp

from benchmarks.reference import gpt


def logits(params, ids, cfg, positions=None):
    return -gpt.logits(params, ids, cfg, positions=positions)


def causal_lm_loss(params, ids, labels, cfg):
    logp = jax.nn.log_softmax(logits(params, ids, cfg)[:, :-1], axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.asarray(labels)[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
