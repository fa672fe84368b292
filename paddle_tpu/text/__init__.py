"""paddle.text equivalent + transformer model zoo (BERT / GPT).

The reference ships text datasets (python/paddle/text/datasets/) and the
ERNIE/GPT model definitions live in external repos; here the flagship
transformer models are first-class since they anchor the perf baselines
(the BERT-base and GPT training configs).
"""
from .models import (  # noqa: F401
    BertModel, BertForPretraining, GPTModel, GPTForCausalLM, gpt3_1p3b,
    bert_base, TransformerLMConfig,
)
from . import datasets  # noqa: F401
from .datasets import (  # noqa: F401
    Conll05st, Imdb, Imikolov, Movielens, UCIHousing, WMT14, WMT16,
)
