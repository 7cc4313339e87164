"""Model zoo (language models; vision lives in paddle_tpu.vision.models)."""

from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    gpt_tiny,
    gpt_124m,
    gpt_350m,
    gpt_1_3b,
    gpt_6_7b,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    llama_tiny,
    llama_160m,
    llama_7b,
)
from .evabyte import (  # noqa: F401
    EvaByteConfig,
    EvaByteForCausalLM,
    evabyte_6_5b,
    evabyte_tiny,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    NemotronHForCausalLM,
    nemotron_3_super_120b_a12b,
    nemotron_h_tiny,
)
from .ouro import (  # noqa: F401
    OuroConfig,
    OuroForCausalLM,
    ouro_2_6b,
    ouro_tiny,
)
from .sdar import (  # noqa: F401
    SdarConfig,
    SdarForBlockDiffusion,
    sdar_30b_a3b,
    sdar_tiny,
)
from .smallthinker import (  # noqa: F401
    SmallThinkerConfig,
    SmallThinkerForCausalLM,
    smallthinker_21b_a3b,
    smallthinker_tiny,
)
from .zaya import (  # noqa: F401
    CompressedConvAttention,
    ZayaConfig,
    ZayaForCausalLM,
    zaya1_8b,
    zaya_tiny,
)
from .wide_deep import WideDeep  # noqa: F401
from .deepfm import DeepFM  # noqa: F401
from .deepspeech import DeepSpeech2, deepspeech2_tiny  # noqa: F401
from .conformer import Conformer, conformer_tiny  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
    bert_base,
    bert_base_config,
    bert_tiny,
    bert_tiny_config,
)
