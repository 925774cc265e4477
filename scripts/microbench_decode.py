"""Decode-step component microbenchmark on the real chip.

Times the pieces of the fused decode step in isolation — forward (layers +
lm head) without KV writes, the paged-attention kernel, the current-token KV
scatter, and the sampler — at several batch sizes, so a regression in one
component is visible without reading a profiler trace. Prints one JSON line
per (component, B). Everything runs in this one process (one process per
chip).

Usage: python scripts/microbench_decode.py [--model llama3-3b] [--batches 16,32,64]
"""

from __future__ import annotations

import argparse

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, *args, iters=20):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama3-3b")
    ap.add_argument("--batches", default="16,32,64")
    ap.add_argument("--ctx", type=int, default=152)
    ap.add_argument("--max-model-len", type=int, default=512)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()

    from llm_d_inference_scheduler_tpu.engine.sampling import sample_tokens
    from llm_d_inference_scheduler_tpu.models import llama
    from llm_d_inference_scheduler_tpu.models.configs import get_config
    from llm_d_inference_scheduler_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_pallas,
    )

    mcfg = get_config(args.model)
    block = mcfg.kv_block_size
    params = llama.init_params(mcfg, jax.random.key(0))

    for B in [int(b) for b in args.batches.split(",")]:
        max_blocks = args.max_model_len // block
        n_blocks = 1 + B * max_blocks
        L, G, D = mcfg.n_layers, mcfg.n_kv_heads, mcfg.head_dim
        k_pages = jnp.zeros((L, n_blocks, block, G, D), jnp.bfloat16)
        v_pages = jnp.zeros_like(k_pages)
        tables = np.zeros((B, max_blocks), np.int32)
        for b in range(B):
            tables[b] = np.arange(1 + b * max_blocks, 1 + (b + 1) * max_blocks)
        tables = jnp.asarray(tables)
        tokens = jnp.ones((B,), jnp.int32)
        positions = jnp.full((B,), args.ctx, jnp.int32)

        # full decode step: scan of 8 steps (keeps the production scan +
        # donation semantics), reported per-step. params passed as an
        # argument — closing over them bakes GBs of constants into the graph.
        def chain(params, k_pages, v_pages):
            def body(carry, _):
                kp, vp = carry
                logits, kp, vp = llama.decode_step(
                    params, mcfg, tokens, positions, kp, vp, tables,
                    use_pallas=True)
                return (kp, vp), logits[:, 0]

            (kp, vp), ls = jax.lax.scan(body, (k_pages, v_pages), None, length=8)
            return ls.sum()

        ms = timeit(jax.jit(chain), params, k_pages, v_pages, iters=5) / 8
        print(json.dumps({"component": "decode_step(all)", "B": B,
                          "ms_per_step": round(ms, 3)}))

        # attention kernel alone
        q = jnp.ones((B, mcfg.n_heads, D), jnp.bfloat16)
        cur = jnp.ones((B, G, D), jnp.bfloat16)
        seq_lens = jnp.full((B,), args.ctx + 1, jnp.int32)

        # The stacked pools and a layer index, as decode_step calls it.
        def attn_chain(q, k_pages, v_pages):
            def body(acc, layer):
                o = paged_decode_attention_pallas(q, k_pages, v_pages, layer,
                                                  tables, seq_lens, cur, cur)
                return acc + o.astype(jnp.float32).sum(), None

            acc, _ = jax.lax.scan(body, jnp.float32(0),
                                  jnp.arange(L, dtype=jnp.int32))
            return acc

        ms = timeit(jax.jit(attn_chain), q, k_pages, v_pages, iters=5)
        print(json.dumps({"component": f"pallas_attn x{mcfg.n_layers}L", "B": B,
                          "ms_per_step": round(ms, 3)}))

        # current-token KV scatter alone (all layers fused, K+V)
        k_cur = jnp.ones((L, B, G, D), jnp.bfloat16)
        blk_idx = tables[jnp.arange(B), positions // block]
        slot = positions % block

        def scatter_chain(kp, vp):
            def body(carry, _):
                kp, vp = carry
                kp = kp.at[:, blk_idx, slot].set(k_cur)
                vp = vp.at[:, blk_idx, slot].set(k_cur)
                return (kp, vp), ()

            (kp, vp), _ = jax.lax.scan(body, (kp, vp), None, length=8)
            return kp[0, 0, 0, 0, 0]

        ms = timeit(jax.jit(scatter_chain), k_pages, v_pages, iters=5) / 8
        print(json.dumps({"component": "kv_scatter(K+V, all L)", "B": B,
                          "ms_per_step": round(ms, 3)}))

        # sampler alone
        logits = jnp.ones((B, mcfg.vocab_size), jnp.float32)
        temps = jnp.ones((B,), jnp.float32)
        zeros = jnp.zeros((B,), jnp.int32)
        ones = jnp.ones((B,), jnp.float32)

        def samp_chain(logits):
            def body(acc, k):
                t = sample_tokens(logits, k, temps, zeros, ones)
                return acc + t.sum(), None

            acc, _ = jax.lax.scan(body, jnp.int32(0),
                                  jax.random.split(jax.random.key(1), 8))
            return acc

        ms = timeit(jax.jit(samp_chain), logits, iters=5) / 8
        print(json.dumps({"component": "sample_tokens", "B": B,
                          "ms_per_step": round(ms, 3)}))


if __name__ == "__main__":
    main()
