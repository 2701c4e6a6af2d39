"""TPU-native continuous-batching LLM inference (slot-pool KV cache,
chunked prefill under a token budget, persistent one-compile decode
loop, per-request token streaming). See engine.py for the architecture,
api.py for the Serve integration."""

from ray_tpu.inference.engine import EngineConfig, InferenceEngine
from ray_tpu.inference.prefix_cache import RadixPrefixCache
from ray_tpu.inference.scheduler import (FINISH_CANCELLED, FINISH_DEADLINE,
                                         FINISH_EOS, FINISH_LENGTH,
                                         Request, RequestHandle, Scheduler)
from ray_tpu.inference.api import LLMDeployment
from ray_tpu.inference.kv_quant import slot_gain as kv_quant_slot_gain

__all__ = ["EngineConfig", "InferenceEngine", "LLMDeployment",
           "RadixPrefixCache", "Request", "RequestHandle", "Scheduler",
           "kv_quant_slot_gain",
           "FINISH_CANCELLED", "FINISH_DEADLINE", "FINISH_EOS",
           "FINISH_LENGTH"]
