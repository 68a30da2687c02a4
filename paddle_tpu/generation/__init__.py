"""Autoregressive generation subsystem: ring KV cache, decode-shaped
flash attention, sampling, and the jitted (prefill, decode) pair behind
``Model.generate()`` / ``inference.Predictor``'s generation mode.

See docs/architecture.md "Generation & KV cache".
"""
from .api import GenerationConfig, GenerationSession, generate  # noqa: F401
from .block_diffusion import BlockDiffusionConfig  # noqa: F401
from .kv_cache import (KVCache, QuantKVCache,  # noqa: F401
                       quantize_kv, resolve_cache_dtype)
from .paged_cache import (AdmissionPlan, PageAllocator,  # noqa: F401
                          PagedKVCache, QuantPagedKVCache)
from .sampling import (apply_temperature, apply_top_k,  # noqa: F401
                       apply_top_p, sample)
from .speculative import (SpeculativeConfig,  # noqa: F401
                          SpeculativeSession, ngram_propose, spec_accept)

__all__ = [
    "GenerationConfig", "GenerationSession", "generate", "KVCache",
    "QuantKVCache", "quantize_kv", "resolve_cache_dtype",
    "PagedKVCache", "QuantPagedKVCache", "PageAllocator",
    "AdmissionPlan",
    "sample", "apply_temperature", "apply_top_k", "apply_top_p",
    "SpeculativeConfig", "SpeculativeSession", "ngram_propose",
    "spec_accept", "BlockDiffusionConfig",
]
