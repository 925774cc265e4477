"""Real-checkpoint serving demo: HF llama → convert_hf → serve → verify.

The CI-ish artifact proving the weights path end-to-end (VERDICT r2 item 9):

1. materialise a small HuggingFace ``LlamaForCausalLM`` checkpoint
   (safetensors on disk — the same artifact shape a user downloads),
2. convert it with ``models/convert_hf.py`` into the engine's stacked-layer
   Orbax layout,
3. serve it through the full engine + OpenAI HTTP server,
4. verify greedy decode over HTTP is TOKEN-EXACT vs ``transformers``
   ``generate`` on the same checkpoint, and record throughput.

Writes one JSON artifact (default benchmarks/CHECKPOINT_DEMO.json) and
prints it. Runs on CPU by default so it works anywhere the test suite does
(pass --tpu to use the real chip; reference analogue: the reference router
serves whatever vLLM loaded from the same HF checkpoints, SURVEY.md
preamble).

Usage: python scripts/checkpoint_demo.py [--out PATH] [--tpu]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "CHECKPOINT_DEMO.json"))
    ap.add_argument("--tpu", action="store_true",
                    help="serve on the real chip instead of CPU")
    ap.add_argument("--family", choices=("llama", "qwen3"), default="llama",
                    help="HF architecture to materialise and serve")
    args = ap.parse_args(argv)

    if not args.tpu:
        # Pin the CPU whatever the environment says (a machine with a chip
        # would otherwise open it), before first device use.
        import jax

        jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import torch

    from llm_d_inference_scheduler_tpu.engine import EngineConfig
    from llm_d_inference_scheduler_tpu.engine.server import EngineServer
    from llm_d_inference_scheduler_tpu.models.convert_hf import main as convert

    t0 = time.monotonic()
    torch.manual_seed(7)
    if args.family == "qwen3":
        from transformers import Qwen3Config, Qwen3ForCausalLM

        hf_cfg = Qwen3Config(
            vocab_size=2048, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4, head_dim=48, rms_norm_eps=1e-6,
            tie_word_embeddings=False, rope_theta=10_000.0,
        )
        model = Qwen3ForCausalLM(hf_cfg).eval().float()
    else:
        from transformers import LlamaConfig, LlamaForCausalLM

        hf_cfg = LlamaConfig(
            vocab_size=2048, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
            tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
            rope_theta=10_000.0,
        )
        model = LlamaForCausalLM(hf_cfg).eval().float()

    with tempfile.TemporaryDirectory() as tmp:
        src = os.path.join(tmp, "hf")
        model.save_pretrained(src, safe_serialization=True)
        orbax = os.path.join(tmp, "orbax")
        convert([src, orbax, "--dtype", "float32"])
        t_convert = time.monotonic() - t0

        rng = np.random.default_rng(0)
        prompts = [rng.integers(2, 2048, size=n).tolist() for n in (9, 23, 41)]
        n_gen = 16
        refs = []
        with torch.no_grad():
            for p in prompts:
                refs.append(model.generate(
                    torch.tensor([p]), max_new_tokens=n_gen, do_sample=False,
                    pad_token_id=0)[0, len(p):].tolist())

        async def serve_and_check() -> dict:
            server = EngineServer(EngineConfig(
                model=orbax, backend="tpu", max_batch=4, max_model_len=128,
                decode_chunk=4, port=18470))
            await server.start()
            try:
                import httpx

                results = []
                t_s = time.monotonic()
                async with httpx.AsyncClient(timeout=600) as c:
                    for p in prompts:
                        r = await c.post(
                            "http://127.0.0.1:18470/v1/completions",
                            json={"model": "demo", "prompt": p,
                                  "max_tokens": n_gen, "temperature": 0,
                                  "ignore_eos": True})
                        r.raise_for_status()
                        results.append(r.json()["choices"][0]["text"])
                elapsed = time.monotonic() - t_s
                return {"results": results, "serve_seconds": elapsed}
            finally:
                await server.stop()

        served = asyncio.run(serve_and_check())

        # The OpenAI surface returns text (the byte tokenizer's total decode
        # of the generated ids); decoding the transformers reference ids
        # through the same tokenizer makes the comparison exact up to that
        # decode map.
        from llm_d_inference_scheduler_tpu.engine.tokenizer import get_tokenizer

        tok = get_tokenizer("byte", hf_cfg.vocab_size)
        matches = [got == tok.decode(ref)
                   for got, ref in zip(served["results"], refs)]

        artifact = {
            "demo": "hf-checkpoint-serving",
            "family": args.family,
            "backend": "tpu-chip" if args.tpu else "cpu",
            "hf_config": {"hidden_size": 256, "layers": 4, "vocab": 2048},
            "convert_seconds": round(t_convert, 2),
            "serve_seconds": round(served["serve_seconds"], 2),
            "tokens_generated": n_gen * len(prompts),
            "greedy_decode_exact_vs_transformers": matches,
            "ok": all(matches),
        }

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact))
    return 0 if artifact["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
