"""TP-sharded serving: decode/prefill with paged KV over a device mesh.

The reference never shards tensors (SURVEY §2.12 — intra-engine parallelism is
vLLM's `--tensor-parallel-size`, outside the repo); this module is the
TPU-native equivalent for the engine half: Megatron-style TP from
``shardings.param_pspecs`` plus KV pages sharded on the kv-head axis, so the
paged-attention gather/scatter stays collective-free and each block's single
all-reduce rides ICI. ``dp`` shards the decode batch across the mesh
(multi-host serving replicates the controller, dp-shards the lanes).

Everything is plain jit over sharded inputs — XLA propagates the shardings
through decode_step/prefill and inserts the psums; no shard_map needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kvcache import pages
from ..models import llama
from ..models.configs import ModelConfig
from .shardings import param_pspecs

SERVE_AXES = ("dp", "tp", "ep")


def make_serve_mesh(devices=None, tp: int = 1, ep: int = 1) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) % (tp * ep):
        raise ValueError(f"{len(devices)} devices not divisible by "
                         f"tp*ep={tp}*{ep}")
    arr = np.array(devices).reshape(len(devices) // (tp * ep), tp, ep)
    return Mesh(arr, SERVE_AXES)


def validate_tp(cfg: ModelConfig, tp: int, ep: int = 1) -> None:
    """TP must divide every sharded dim (kv heads bound the paged-KV shard);
    EP must divide the expert count."""
    for dim, name in ((cfg.n_kv_heads, "n_kv_heads"), (cfg.n_heads, "n_heads"),
                      (cfg.d_ff, "d_ff"), (cfg.vocab_size, "vocab_size")):
        if dim % tp:
            raise ValueError(f"tp={tp} does not divide {name}={dim}")
    if ep > 1:
        if not cfg.n_experts:
            raise ValueError("ep>1 requires an MoE config (n_experts > 0)")
        if cfg.n_experts % ep:
            raise ValueError(f"ep={ep} does not divide n_experts={cfg.n_experts}")


def serve_shardings(cfg: ModelConfig, mesh: Mesh):
    """(param shardings pytree, kv-page sharding) for an engine on `mesh`."""
    validate_tp(cfg, mesh.shape["tp"], mesh.shape.get("ep", 1))
    params = jax.tree.map(lambda s: NamedSharding(mesh, s), param_pspecs(cfg))
    return params, pages.page_sharding(mesh)


def init_sharded_params(cfg: ModelConfig, mesh: Mesh, key, dtype=None):
    """Init parameters directly into their TP shards (no host round-trip)."""
    shardings, _ = serve_shardings(cfg, mesh)
    return jax.jit(
        lambda k: llama.init_params(cfg, k, dtype=dtype),
        out_shardings=shardings)(key)


def dryrun_serve(cfg: ModelConfig, devices, tp: int = 2, ep: int = 1,
                 decode_steps: int = 3, atol: float = 2e-3) -> None:
    """Prefill + N decode steps with TP/EP-sharded params/pages and a
    dp-sharded batch; asserts logits match the unsharded single-device path.

    Driver-facing stepping stone to a 70B deployment (TP-sharded
    decode): proves the serving jits compile and execute SPMD over a mesh.
    """
    mesh = make_serve_mesh(devices, tp=tp, ep=ep)
    dp = mesh.shape["dp"]
    B = max(2, dp)
    block = cfg.kv_block_size
    prompt_len = min(block + block // 2, cfg.max_seq_len - decode_steps - 1)
    max_blocks = -(-(prompt_len + decode_steps) // block) + 1
    f32 = jnp.float32  # keep the cross-path comparison numerically tight
    geom = pages.PageGeometry.for_model(
        cfg, 1 + B * max_blocks, dtype="float32")  # +1 trash block

    rng = np.random.default_rng(0)
    tokens_np = rng.integers(1, cfg.vocab_size, (B, prompt_len)).astype(np.int32)
    seq_lens_np = np.full((B,), prompt_len, np.int32)
    tables_np = np.zeros((B, max_blocks), np.int32)
    for b in range(B):
        tables_np[b] = 1 + b * max_blocks + np.arange(max_blocks)

    def prefill(params, tokens, seq_lens, k_pages, v_pages, tables):
        logits, (k_new, v_new) = llama.forward(params, cfg, tokens, want_kv=True)
        k_pages, v_pages = pages.write_sequences(
            k_pages, v_pages, k_new, v_new, tables, seq_lens)
        last = jnp.take_along_axis(
            logits, (seq_lens - 1)[:, None, None], axis=1)[:, 0]
        return last, k_pages, v_pages

    def run(sharded: bool):
        if sharded:
            params = init_sharded_params(cfg, mesh, jax.random.key(0), dtype=f32)
            k_pages, v_pages = pages.alloc(
                geom, sharding=pages.page_sharding(mesh))
            batch = NamedSharding(mesh, P("dp"))
            batch2 = NamedSharding(mesh, P("dp", None))
        else:
            params = llama.init_params(cfg, jax.random.key(0), dtype=f32)
            k_pages, v_pages = pages.alloc(geom)
            batch = batch2 = None

        def put(x, s):
            return jax.device_put(x, s) if s is not None else jnp.asarray(x)

        tokens = put(tokens_np, batch2)
        seq_lens = put(seq_lens_np, batch)
        tables = put(tables_np, batch2)

        prefill_fn = jax.jit(prefill, donate_argnums=(3, 4))
        decode_fn = jax.jit(
            lambda p, t, pos, kp, vp, bt: llama.decode_step(p, cfg, t, pos, kp, vp, bt),
            donate_argnums=(3, 4))

        last, k_pages, v_pages = prefill_fn(params, tokens, seq_lens,
                                            k_pages, v_pages, tables)
        outs = [np.asarray(last)]
        toks = jnp.argmax(last, axis=-1).astype(jnp.int32)
        positions = jnp.asarray(seq_lens_np)
        for _ in range(decode_steps):
            logits, k_pages, v_pages = decode_fn(
                params, put(np.asarray(toks), batch), put(np.asarray(positions), batch),
                k_pages, v_pages, tables)
            outs.append(np.asarray(logits))
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            positions = positions + 1
        return outs

    sharded = run(sharded=True)
    plain = run(sharded=False)
    for i, (a, b) in enumerate(zip(sharded, plain)):
        if not np.allclose(a, b, atol=atol, rtol=atol):
            diff = float(np.max(np.abs(a - b)))
            raise AssertionError(
                f"sharded serving logits diverge at step {i}: max|Δ|={diff}")
