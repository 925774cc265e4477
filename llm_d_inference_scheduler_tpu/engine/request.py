"""Engine-side request/response types."""

from __future__ import annotations

import dataclasses
import enum
import time
from typing import Any


class FinishReason(str, enum.Enum):
    STOP = "stop"          # EOS or stop sequence
    LENGTH = "length"      # hit max_tokens
    ABORT = "abort"        # client disconnect / eviction
    CACHE_THRESHOLD = "cache_threshold"  # shared-storage connector probe (SURVEY §2.10)


@dataclasses.dataclass
class EngineRequest:
    request_id: str
    prompt_token_ids: list[int]
    max_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0            # 0 = disabled
    top_p: float = 1.0        # 1.0 = disabled
    stop_token_ids: tuple[int, ...] = ()
    ignore_eos: bool = False   # benchmark/test knob (vLLM-compatible)
    stream: bool = False
    # Shared-storage disaggregation probe (reference
    # connector_shared_storage.go:30-271): if the prefix-cache hit ratio at
    # prefill is below this threshold, finish immediately with
    # finish_reason="cache_threshold" so the sidecar can prefill remotely.
    cache_hit_threshold: float | None = None
    # P/D disaggregation handshake (mirrors the reference's kv_transfer_params
    # relay, /root/reference pkg/sidecar/proxy/connector_nixlv2.go:109-131):
    kv_transfer_params: dict[str, Any] | None = None
    # Multimodal prefill (E/P/D phase 2): encoder output vectors [M, D] to
    # splice in at prompt positions mm_positions (placeholder tokens).
    mm_embeds: Any = None          # np.ndarray [M, D] | None
    mm_positions: list[int] | None = None
    arrival_time: float = dataclasses.field(default_factory=time.monotonic)
    # time.monotonic() at the engine's first admission pop (engine-set; the
    # start of jetstream:admit_to_first_token_seconds).
    admit_time: float | None = None


@dataclasses.dataclass
class TokenEvent:
    """One emitted token (or terminal event) on a request's output stream."""
    request_id: str
    token_id: int | None
    text: str = ""
    finish_reason: FinishReason | None = None
    # Set on the first event so servers can report TTFT.
    is_first: bool = False
    # Terminal event may carry KV handoff params back to the sidecar connector.
    kv_transfer_params: dict[str, Any] | None = None
    # usage accounting
    prompt_tokens: int = 0
    completion_tokens: int = 0
    cached_tokens: int = 0
