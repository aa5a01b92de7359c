"""Flagship model families (the reference keeps GPT/ERNIE in external repos
driven by fleet; here they ship in-tree as the hybrid-parallel north star —
SURVEY §3.3 / BASELINE GPT-3 1.3B config)."""
from .gpt import (  # noqa: F401
    GPTConfig, GPTKVCache, GPTModel, GPTForCausalLM,
    GPTPretrainingCriterion, gpt2_medium,
    gpt_tiny, gpt2_small, gpt2_large, gpt3_1p3b, smallthinker_21ba3b,
    k_exaone_236b_a23b, glm_4p7_flash, brumby_14b_base,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig, NemotronHForCausalLM, nemotron_3_super_120b_a12b,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining,
    BertForSequenceClassification, BertPretrainingCriterion, bert_tiny,
    bert_base,
)
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieModel, ErnieForSequenceClassification, ernie_tiny,
    ernie_base, ernie_3_0_10b,
)
