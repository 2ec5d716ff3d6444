"""Model families matching the BASELINE capability configs (BASELINE.md):
GPT (config 4 flagship), BERT (config 3), LLaMA (config 5); vision models
(configs 1–2) live in paddle_tpu.vision.models.
"""
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTModel,
    GPTForCausalLM,
    GPTPretrainingCriterion,
    MoEBlock,
    gpt_config,
    gpt_sharding_rules,
    match_sharding,
)
from .gpt_pipe import (  # noqa: F401
    GPTForCausalLMPipe,
    gpt_pipe_sharding_rules,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertModel,
    BertForPretraining,
    BertForSequenceClassification,
    bert_config,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaModel,
    LlamaForCausalLM,
    LlamaPretrainingCriterion,
    llama_config,
    llama_sharding_rules,
)
from .keye_vl2 import (  # noqa: F401
    KeyeVL2Config,
    KeyeVL2Model,
    KeyeVL2ForCausalLM,
)
from .mellum2 import (  # noqa: F401
    Mellum2Config,
    Mellum2Model,
    Mellum2ForCausalLM,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    NemotronHModel,
    NemotronHForCausalLM,
)
from .ling3 import (  # noqa: F401
    Ling3Config,
    Ling3Model,
    Ling3ForCausalLM,
)
from .lfm2 import (  # noqa: F401
    Lfm2MoeConfig,
    Lfm2MoeModel,
    Lfm2MoeForCausalLM,
)
