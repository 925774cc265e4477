from .configs import ModelConfig, get_config, LLAMA3_8B, LLAMA3_70B, TINY
from . import hybrid, llama, mla
from .binding import Bound, bind, family

__all__ = ["ModelConfig", "get_config", "LLAMA3_8B", "LLAMA3_70B", "TINY",
           "hybrid", "llama", "mla", "family", "bind", "Bound"]
