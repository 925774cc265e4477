"""Engine configuration."""

from __future__ import annotations

import dataclasses

from ..models.configs import ModelConfig, get_config


@dataclasses.dataclass
class EngineConfig:
    model: str = "tiny"
    served_model_name: str | None = None
    backend: str = "tpu"          # "tpu" (JAX) | "sim" (CPU simulator)
    # Which of this process's devices the engine is bound to (weights, KV
    # pages, step inputs; the first device of a tp/pp slice). One engine
    # per chip: several engines in one process take one index each; a
    # server process that was shown only its own chip keeps 0.
    device_index: int = 0
    max_batch: int = 8            # decode batch slots
    max_model_len: int = 2048
    hbm_kv_blocks: int = 0        # 0 = derive from max_batch * max_model_len
    tokenizer: str = "byte"
    seed: int = 0
    port: int = 8200
    host: str = "127.0.0.1"
    # sim backend knobs (mirrors llm-d-inference-sim's role in the reference
    # e2e suite, /root/reference test/e2e — SURVEY §4):
    sim_prefill_ms_per_token: float = 0.02
    sim_decode_ms_per_token: float = 2.0
    # Simulated P/D KV-import cost per block pulled from the prefill pod's
    # staged export (the decode leg of the 2-phase tpu-dcn protocol). Real
    # engines measure this pull (x-kv-pull-ms, PR 6); the sim sleeps it so
    # CPU-only P/D benches price the hop — notably the multi-turn scenario
    # (bench.py --multi-turn), where a warm turn routed through the hop
    # pays this pull for blocks the decode pod already holds.
    sim_kv_pull_ms_per_block: float = 0.2
    # Per-peer override of the flat scalar above: maps the PREFILL peer's
    # "host:port" (the staged export's remote_host:remote_port) to its own
    # ms/block pull cost, so CPU-only benches can shape SKEWED transfer
    # topologies — 2 fast pairs, N slow (bench.py --shadow, the
    # NetKV/ROADMAP-item-2 scenario). Peers absent from the map fall back
    # to sim_kv_pull_ms_per_block; an empty map (the default) is
    # bit-identical to the flat-scalar behavior.
    sim_kv_pull_ms_per_peer: dict[str, float] = dataclasses.field(
        default_factory=dict)
    # P/D role advertised to the router via labels/metadata.
    role: str = "both"            # "prefill" | "decode" | "both" | "encode"
    engine_id: str = ""
    checkpoint_path: str = ""     # orbax dir; empty = random init (dev/bench)
    enable_prefix_caching: bool = True  # automatic prefix caching (block reuse)
    warmup: bool = False          # compile prefill/decode/sample before serving
    # Every text prompt is written into its pages in windows, by one
    # function (TpuEngine._write_prefill_window). When > 0, a prompt whose
    # un-cached suffix exceeds this many tokens is written in windows of this
    # size (rounded up to a KV-block multiple), one window an engine step
    # for each slot still prefilling (at most core.PREFILL_STEP_TOKENS of
    # prompt a step), interleaved with the decode chunks of established
    # lanes — bounding the decode stall a long-context prefill can cause to
    # ~one window, or that budget when several lanes wait, instead of the
    # full prompt. Windows after the first ride the
    # prefix-continuation jits (the same O(prefix) path prefix-cache hits
    # use). A suffix that fits has one window, written as it is admitted.
    # 0 = one window a prompt. Multimodal prompts always have one (the
    # embed splice targets absolute positions in the first forward).
    prefill_chunk: int = 0
    # Secure serving for the engine's HTTP surface (the in-cluster legs the
    # sidecar's use-tls-for-prefiller/decoder knobs target): cert dir with
    # tls.crt/tls.key, or a self-signed certificate when secure_serving is
    # on without a path (router/tlsutil.py). Note: the host-staged /kv
    # fallback's importer dials plain http (trusted-mesh side channel, like
    # the reference's NIXL handshake) — TLS engines doing P/D rely on the
    # device/shard transfer wires, which are not HTTP.
    secure_serving: bool = False
    cert_path: str = ""
    enable_cert_reload: bool = False
    # Outbound TLS verification for the engine's own client legs — encoder
    # /ec pulls and the host-staged /kv pull + release DELETEs against TLS
    # peers. Default skip-verify (in-cluster pod-local certs, mirroring the
    # sidecar's per-leg insecure-skip-verify flags); a CA bundle path turns
    # real verification on (router/tlsutil.py client_verify).
    client_insecure_skip_verify: bool = True
    client_ca_cert_path: str = ""
    # The most decode steps fused into one device dispatch (a loop over the
    # decode step + sampler on device, its step count an operand). Amortizes
    # per-dispatch latency, the per-chunk readback and the device's
    # once-a-chunk work at the cost of bursty token streaming, of
    # up-to-(chunk-1) wasted steps for sequences that hit a stop condition
    # mid-chunk, and of an arrival's wait for the chunk that runs: the loop
    # dispatches a quarter of it, or half, where a slot is open and nobody
    # waits (core.TpuEngine._chunk_steps; PERF.md section 6, PR 43 and PR 57,
    # has the lengths on the chip). 1 = classic per-step decode.
    decode_chunk: int = 8
    # Pallas paged-attention decode kernel. None = auto: enabled on a real
    # TPU backend for unsharded engines whose head_dim is lane-aligned
    # (head_dim % 128 == 0 — Mosaic DMA slice constraint); measured 1.76×
    # faster than the XLA gather path at llama3-8b shapes on v5e.
    pallas_attention: bool | None = None
    # Interpret the kernels: for tests on the CPU only. The server CLI has no
    # flag for it, so a served engine cannot run the interpreter by accident.
    pallas_interpret: bool = False
    # Tensor parallelism: shard params (Megatron TP) + KV pages (kv-head axis)
    # over a tp-sized mesh axis; remaining devices form the dp axis. 1 = the
    # single-device layout (no mesh). The path of a 70B model over a multi-host slice.
    tp_size: int = 1
    # Expert parallelism (MoE models): shard the experts axis over ep_size
    # devices (composes with tp_size; total devices = tp_size * ep_size).
    ep_size: int = 1
    # Pipeline parallelism for serving (parallel/pp_serve.py): shard the
    # layer stack + KV pages over pp_size stages on a (pp, tp, ep) mesh;
    # decode/prefill/prefix-prefill/embed all run the stage ring. Composes
    # with tp_size and ep_size, with prefix caching, and with multi-host
    # (stages span hosts on the global mesh).
    pp_size: int = 1
    # Multi-host serving (engine/multihost.py): when dist_coordinator is set
    # ("host:port" of the jax.distributed coordinator), all dist_num_processes
    # engine processes form ONE global mesh (tp_size*ep_size must equal the
    # global device count / dp replicas). Process 0 serves; others replay
    # device ops from the leader's instruction channel on dist_instr_port.
    dist_coordinator: str = ""
    dist_num_processes: int = 1
    dist_process_id: int = 0
    dist_instr_port: int = 8790
    dist_instr_host: str = ""     # leader bind / follower dial; default host
    # Follower liveness deadline: no instruction/ping within this window →
    # LeaderLost (exit for group restart). Production default 30 s; raise on
    # contended CI boxes where compile bursts starve the ping thread.
    dist_recv_timeout_s: float = 30.0
    # Wire for dist sharded KV handoff: "device" = jax.experimental.transfer
    # pulls (ICI/DCN), "host" = per-process TCP shard servers
    # (engine/shard_wire.py), "auto" = host on the cpu backend (whose
    # transfer backend cannot carry same-host cross-process pulls — see
    # shard_wire.py docstring), device otherwise.
    kv_wire: str = "auto"
    # KV cache event stream (ZMQ PUB) feeding the router's precise prefix
    # scorer; 0 disables, -1 = port + 1000.
    kv_events_port: int = -1
    # P/D KV handoff data path: "device" = jax.experimental.transfer
    # device-to-device pull (ICI same-slice / DCN cross-slice — the NIXL
    # analogue), "host" = host-staged bytes over HTTP, "auto" = device when
    # the transfer server starts, host otherwise. The HTTP path always
    # remains as the cross-stack fallback.
    kv_transfer: str = "auto"

    # Deterministic fault injection on the HTTP generate surface (chaos
    # shim, router/resilience.py FaultInjector — applies to both the sim
    # and the tpu backend since it sits at the server layer). Spec grammar:
    # comma-separated "kind:pct[:arg]" with kind in reset|http503|delay|
    # stall (arg = milliseconds for delay/stall); the fault decision is a
    # stable hash of (chaos_seed, kind, request id), so a given request id
    # always takes the same fault — hermetic, reproducible failover tests.
    # Empty falls back to the ENGINE_CHAOS env var (same grammar).
    chaos: str = ""
    chaos_seed: int = 0
    # Where POST /debug/profile/start writes a jax.profiler trace of this
    # process; empty = the two endpoints do not exist (engine/server.py
    # ProfileControl, docs/observability.md).
    profile_dir: str = ""

    def resolved_kv_events_port(self) -> int:
        return self.port + 1000 if self.kv_events_port == -1 else self.kv_events_port

    @property
    def model_config(self) -> ModelConfig:
        return get_config(self.model)

    @property
    def model_name(self) -> str:
        return self.served_model_name or self.model
