"""Decode-step component microbenchmark on the real chip.

Times the pieces of the fused decode step in isolation — forward (layers +
lm head) without KV writes, the paged-attention kernel, the current-token KV
scatter, and the sampler — at several points (lanes x context tokens a lane),
so a regression in one component is visible without reading a profiler trace.
Prints one JSON line per (component, point). The kernel's line also gives ms
a call (one layer), us a page fetched, and the share of the chip's peak
bandwidth that the bytes the call needs (chipbench/kernels.py) come to.
Everything runs in this one process (one process per chip).

Usage: python scripts/microbench_decode.py [--model qwen3-4b]
           [--points 16x1000,16x300,8x300]
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
    ap.add_argument("--model", default="qwen3-4b")
    ap.add_argument("--points", default="16x1000,16x300,8x300",
                    help="lanes x context tokens a lane, comma-separated")
    ap.add_argument("--max-model-len", type=int, default=1024)
    args = ap.parse_args(argv)

    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()

    from llm_d_inference_scheduler_tpu.engine.sampling import sample_tokens
    from llm_d_inference_scheduler_tpu.kvcache import pages
    from llm_d_inference_scheduler_tpu.models import llama
    from llm_d_inference_scheduler_tpu.models.configs import get_config

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench"))
    import kernels  # the benchmark's yardstick: peaks, bytes a call needs

    mcfg = get_config(args.model)
    block = mcfg.kv_block_size
    kernel = functools.partial(pages.decode_attention, kernel=True)
    params = llama.init_params(mcfg, jax.random.key(0))

    def report(component, B, ctx, ms, **more):
        print(json.dumps({"component": component, "B": B, "ctx": ctx,
                          "ms_per_step": round(ms, 3), **more}), flush=True)

    for B, ctx in [map(int, pt.split("x")) for pt in args.points.split(",")]:
        max_blocks = args.max_model_len // block
        L, G, D = mcfg.n_layers, mcfg.n_kv_heads, mcfg.head_dim
        k_pages, v_pages = pages.alloc(pages.PageGeometry.for_model(
            mcfg, 1 + B * max_blocks, max_blocks, dtype="bfloat16"))
        tables = np.zeros((B, max_blocks), np.int32)
        for b in range(B):
            tables[b] = np.arange(1 + b * max_blocks, 1 + (b + 1) * max_blocks)
        tables = jnp.asarray(tables)
        tokens = jnp.ones((B,), jnp.int32)
        positions = jnp.full((B,), ctx, jnp.int32)

        # full decode step: scan of 8 steps (keeps the production scan +
        # donation semantics), reported per-step. params passed as an
        # argument — closing over them bakes GBs of constants into the graph.
        def chain(params, k_pages, v_pages):
            def body(carry, _):
                kp, vp = carry
                logits, kp, vp = llama.decode_step(
                    params, mcfg, tokens, positions, kp, vp, tables,
                    attention_fn=kernel)
                return (kp, vp), logits[:, 0]

            (kp, vp), ls = jax.lax.scan(body, (k_pages, v_pages), None, length=8)
            return ls.sum()

        ms = timeit(jax.jit(chain), params, k_pages, v_pages, iters=5) / 8
        report("decode_step(all)", B, ctx, ms)

        # attention kernel alone
        q = jnp.ones((B, mcfg.n_heads, D), jnp.bfloat16)
        cur = jnp.ones((B, G, D), jnp.bfloat16)
        seq_lens = jnp.full((B,), ctx + 1, jnp.int32)

        # The stacked pools and a layer index, as decode_step calls it.
        def attn_chain(q, k_pages, v_pages):
            def body(acc, layer):
                o = kernel(q, k_pages, v_pages, layer, tables, seq_lens, cur,
                           cur)
                return acc + o.astype(jnp.float32).sum(), None

            acc, _ = jax.lax.scan(body, jnp.float32(0),
                                  jnp.arange(L, dtype=jnp.int32))
            return acc

        ms = timeit(jax.jit(attn_chain), q, k_pages, v_pages, iters=5)
        call_s = ms / L * 1e-3
        need = kernels.paged_attention_decode(B * ctx, B, mcfg.n_heads, G, D)
        peak = kernels.peaks(jax.devices()[0].device_kind)["bytes_per_s"]
        report(f"pallas_attn x{L}L", B, ctx, ms,
               ms_per_call=round(call_s * 1e3, 4),
               us_per_page=round(call_s * 1e6 / (B * -(-ctx // block)), 4),
               peak_bandwidth_share_pct=round(
                   100 * need["bytes"] / call_s / peak, 2))

        # current-token KV scatter alone (all layers fused, K+V)
        k_cur = jnp.ones((L, B, G, D), jnp.bfloat16)
        slots = pages.token_slots(k_pages, tables, positions)

        def scatter_chain(kp, vp):
            def body(carry, _):
                return pages.write(*carry, k_cur, k_cur, *slots), ()

            (kp, vp), _ = jax.lax.scan(body, (kp, vp), None, length=8)
            return kp.reshape(-1)[0]

        ms = timeit(jax.jit(scatter_chain), k_pages, v_pages, iters=5) / 8
        report("kv_scatter(K+V, all L)", B, ctx, ms)

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
        report("sample_tokens", B, ctx, ms)


if __name__ == "__main__":
    main()
