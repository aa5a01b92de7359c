"""A model of "another architecture" for the rehearsal: the program's
GPT with every logit negated. It exists so that the tests can show that
``model.class`` and ``reference`` are both live: it is ``correct``
against ``reference/gpt_negated.py`` and not against ``gpt``."""
from paddle_tpu.models import GPTForCausalLM


class NegatedGPT(GPTForCausalLM):
    def forward(self, input_ids, cache=None):
        if cache is None:
            return -super().forward(input_ids)
        logits, pools = super().forward(input_ids, cache=cache)
        return -logits, pools


class SometimesNegatedGPT(GPTForCausalLM):
    """A fault planted where tokens are produced: the logits of every
    fed position p with p % 4 == 1 are negated on the cached (served)
    path, so about a quarter of the served tokens are the reference's
    worst and the others are sound."""

    def forward(self, input_ids, cache=None):
        if cache is None:
            return super().forward(input_ids)
        import paddle_tpu as paddle
        logits, pools = super().forward(input_ids, cache=cache)
        hit = (cache.positions % 4 == 1).unsqueeze(-1)
        return paddle.where(hit, -logits, logits), pools
