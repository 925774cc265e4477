from .configs import ModelConfig, get_config, LLAMA3_8B, LLAMA3_70B, TINY
from . import hybrid, llama, mla

__all__ = ["ModelConfig", "get_config", "LLAMA3_8B", "LLAMA3_70B", "TINY",
           "hybrid", "llama", "mla", "family"]


def family(cfg: ModelConfig):
    """The module that holds ``cfg``'s block: ``init_params``, ``forward``,
    ``decode_step`` and ``prefill_with_prefix`` under one set of signatures.
    A layer pattern (layer_pattern set) names models/hybrid.py, whose layers
    are state-space, expert and attention mixers in that pattern; latent
    attention (kv_lora_rank > 0) names models/mla.py; everything else is
    models/llama.py's block."""
    if cfg.layer_pattern:
        return hybrid
    return mla if cfg.kv_lora_rank else llama
