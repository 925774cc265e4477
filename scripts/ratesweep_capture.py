"""Capture a loadgen latency-throughput rate sweep as a JSON artifact.

Reproduces the reference's benchmark-harness envelope
(/root/reference/config/manifests/benchmark/benchmark.yaml:19-47: request
rates sweep × fixed duration × fixed input/output lengths) against the FULL
stack on one chip — gateway (flow control + default scorer profile) → HTTP →
engine server → TpuEngine — and writes per-rate p50/p99 TTFT, request
latency, and aggregate output tokens/s to benchmarks/BENCH_ratesweep.json.

Usage:
  python scripts/ratesweep_capture.py [--model llama3-3b] [--batch 32]
      [--rates 2,5,10,20] [--duration 30] [--out PATH]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from scripts.loadgen import run_rate  # noqa: E402


async def capture(args) -> dict:
    from llm_d_inference_scheduler_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()

    from llm_d_inference_scheduler_tpu.engine import EngineConfig
    from llm_d_inference_scheduler_tpu.engine.server import EngineServer
    from llm_d_inference_scheduler_tpu.router.gateway import build_gateway

    eport, gport = 18481, 18480
    server = EngineServer(EngineConfig(
        model=args.model, backend="tpu", max_batch=args.batch,
        max_model_len=512, decode_chunk=16, warmup=True, port=eport))
    await server.start()
    gw = build_gateway(
        f"""
featureGates: {{flowControl: true}}
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {eport}}}
""",
        port=gport, poll_interval=0.05)
    await gw.start()
    try:
        import httpx

        async with httpx.AsyncClient(timeout=5) as probe:
            for _ in range(100):
                try:
                    if (await probe.get(
                            f"http://127.0.0.1:{gport}/health")).status_code == 200:
                        break
                except httpx.HTTPError:
                    pass
                await asyncio.sleep(0.1)

        url = f"http://127.0.0.1:{gport}"
        # Warm the measured prefill bucket + decode chain before the sweep:
        # a cold whole-model compile would shed the whole first rate.
        async with httpx.AsyncClient(timeout=600) as warm:
            r = await warm.post(url + "/v1/completions", json={
                "model": args.model,
                "prompt": "w" * max(args.input_tokens - 1, 1),
                "max_tokens": args.output_tokens, "ignore_eos": True})
            r.raise_for_status()

        rows = []
        for rate in [float(r) for r in args.rates.split(",")]:
            row = await run_rate(url, rate, args.duration, args.input_tokens,
                                 args.output_tokens, stream=True)
            rows.append(row)
            print(json.dumps(row), flush=True)
        return {
            "harness": "loadgen rate sweep (reference benchmark.yaml shape)",
            "model": args.model, "max_batch": args.batch,
            "input_tokens": args.input_tokens,
            "output_tokens": args.output_tokens,
            "duration_s": args.duration,
            "stack": "gateway(flowControl+default scorers) -> engine server -> TpuEngine",
            "captured_at_round": 4,
            "rates": rows,
        }
    finally:
        await gw.stop()
        await server.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama3-3b")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--rates", default="2,5,10,20")
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--input-tokens", type=int, default=128)
    ap.add_argument("--output-tokens", type=int, default=64)
    ap.add_argument("--out", default=os.path.join(
        REPO, "benchmarks", "BENCH_ratesweep.json"))
    args = ap.parse_args(argv)

    artifact = asyncio.run(capture(args))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({"written": args.out,
                      "best": max(artifact["rates"],
                                  key=lambda r: r["output_tokens_per_sec"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
