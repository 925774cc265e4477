"""LongCat-Flash's double layer on the chip against its plain reference, at a
benchmark configuration's widths and the `reason-batch` cell's sizes.

    python scripts/compare_longcat_reference.py \
        --config-file chipbench/configs/longcat-flash-omni-cut.json --seeds 0,1,2,3,4

scripts/compare_mla_reference.py's comparison for the second block of
models/mla.py, under the sizes of the cell that serves it: whole prompts in the
four prefill buckets (128, 256, 512, 1,024: the two larger trace the grouped
expert matmuls, the two smaller the dense-over-held einsums), one window that
continues a cached prefix, and decode steps of all `--max-batch` lanes at once
through the engine's pool at `--max-batch` x `--max-model-len`. The program
side runs what `TpuEngine`'s step functions trace -- `models.mla.forward` /
`prefill_with_prefix` / `decode_step` with the MoE form `models.bind(...).model_for`
gives each shape, the decode attention the engine binds (the Pallas latent
kernel on a TPU, 64 heads), the page writes of `kvcache/pages.py`, the pool in
the `kvcache/state.Cache` that carries the counts -- jitted here to hand back
logits before the sampler and the router's choices:

1. *prefill*: lanes in turn take one of `--lengths` (a prompt shorter than its
   bucket, as the traffic's are; `a+b` is a prompt of `a` tokens prefilled
   whole and then a window of `b` that continues it through the pages); of
   one lane a length, logits at `--positions` positions of the prompt (of a
   window: at four, by shorter valid lengths);
2. *decode*: `--decode-steps` teacher-forced steps of all lanes at once
   (ragged: every length), logits of every lane;
3. *counts*: what the programs summed on the device (held here, zero-compute)
   against the same sums over the choices they handed back.

The reference (`chipbench/configs/reference_longcat_flash.py`, float32 under
`highest`, queries in blocks, only compared positions carried to the
vocabulary, given the same held range) runs once a distinct length, **held to
the outputs the program chose**, for compare_mla_reference.py's reason: with
random weights a near-tie that bf16 parts the other way moves that position's
logits and those of every later one by as much as the logits themselves. So
the routing is compared for what it is -- every choice the reference would not
have made has to be a near-tie in the reference's own scores (`shortfall`, in
the scores' own unit: a softmax score over 768 outputs is of the order of
1/768) -- and the logits along the program's own history, where what is left
is rounding.

Each line of output is one seed. Exit code 1 if any seed passes a limit below.
`--degrade cache8` rounds the cached rows to 8 bits (4 of exponent, 3 of
mantissa) after the prefill: the reading a lower precision gives, which has to
fail. (The held experts in 8 bits are no probe at this cut: with 16 of 512
experts held and gates of 6 x a softmax score, they carry a few thousandths of
the residual, under bf16's own rounding; PERF.md section 6, PR 39.)

On the CPU (`--model tiny-longcat --lengths 20,45,32+9 --max-model-len 128
--max-batch 6 --dtype float32 --decode-steps 4`) it rehearses the control flow
with the kernel interpreted; its numbers say nothing about the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.util
import json
import os
import sys
import time
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# ---- limits, each between two readings (PERF.md section 6, PR 39) -----------
# max |diff| of a stage's logits over its max |ref|, the reference held to the
# program's choices: bf16 products (2^-9 an operand) through four double layers
# (eight attention sublayers, eight dense FFNs). The correct program reads
# 0.0110-0.0161 over seeds 0-4 and every stage (decode 0.0136-0.0153); a cache
# rounded to 8 bits reads 0.0513-0.0566 in decode (seeds 0-2).
TIGHT = 0.03
# How far under the reference's own twelfth-best `s + b` a choice of the program
# may lie, in the scores' unit (a score is about 1/768 = 1.3e-3, the chosen ones
# 7e-3 to 1.6e-2; 15% of positions hold such a choice at all). bf16 activations
# move a router logit by about 1e-2 and a score by that share of itself: the
# correct program reads 0.000297-0.000400; with the 8-bit cache the decode steps
# read 0.000786-0.000852.
SHORTFALL = 0.00056


def _reference():
    path = os.path.join(REPO, "chipbench", "configs",
                        "reference_longcat_flash.py")
    spec = importlib.util.spec_from_file_location("reference_longcat_flash",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _pow2(n: int, least: int = 1) -> int:
    p = least
    while p < n:
        p *= 2
    return p


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="longcat-flash-omni-cut")
    ap.add_argument("--config-file", default="")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-model-len", type=int, default=2048)
    ap.add_argument("--lengths", default="113,250,500,1000,512+200")
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--positions", type=int, default=8)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--degrade", default="", choices=("", "cache8"))
    ap.add_argument("--shuffle-tables", action="store_true",
                    help="hand the lanes the pool's blocks in a shuffled "
                         "order (no runs of adjacent pages for the decode "
                         "kernels to fetch as one copy); default ascending, "
                         "all runs")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.engine.config import EngineConfig
    from llm_d_inference_scheduler_tpu.kvcache import pages, state
    from llm_d_inference_scheduler_tpu.models import bind, configs, mla
    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf
    from llm_d_inference_scheduler_tpu.utils.compile_cache import (
        configure_compile_cache)

    configure_compile_cache()
    if args.config_file:
        with open(args.config_file) as f:
            published = json.load(f)
        configs._REGISTRY[args.model] = config_from_hf(
            types.SimpleNamespace(**published), name=args.model)
    if args.dtype:
        configs._REGISTRY[args.model] = dataclasses.replace(
            configs.get_config(args.model), dtype=args.dtype)
    mcfg = configs.get_config(args.model)
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    cfg = EngineConfig(model=args.model, max_batch=args.max_batch,
                       max_model_len=args.max_model_len,
                       pallas_attention=True, pallas_interpret=not on_tpu)
    # The forms an engine on this device binds (models/binding.py), without
    # the engine.
    bound = bind(mcfg, platform=device.platform,
                 interpret=cfg.pallas_interpret)
    attend = functools.partial(pages.latent_decode_attention, kernel=True,
                               interpret=not on_tpu)
    geom = pages.PageGeometry.for_engine(mcfg, cfg.max_batch,
                                         cfg.max_model_len)
    block, B, K = geom.block, args.max_batch, args.decode_steps
    plans = [tuple(int(x) for x in spec.split("+"))
             for spec in args.lengths.split(",")]
    assert all(p[0] % block == 0 for p in plans if len(p) == 2), \
        "a continued prompt is whole pages"
    longest = max(sum(p) for p in plans)
    ref = _reference()
    sizes = dict(n_heads=mcfg.n_heads, kv_lora_rank=mcfg.kv_lora_rank,
                 qk_nope_head_dim=mcfg.qk_nope_head_dim,
                 qk_rope_head_dim=mcfg.qk_rope_head_dim,
                 rope_theta=mcfg.rope_theta, norm_eps=mcfg.norm_eps,
                 experts_per_token=mcfg.experts_per_token,
                 routed_scaling_factor=mcfg.routed_scaling_factor,
                 n_experts=mcfg.n_experts, first_expert=mcfg.experts_first,
                 scale_q=mcfg.mla_scale_q_lora,
                 scale_kv=mcfg.mla_scale_kv_lora)
    first, count = mcfg.held_experts

    # ---- the program's steps, logits and routes out ----
    @functools.partial(jax.jit, donate_argnums=(4,))
    def prefill(params, tokens, n, at, cache, row):
        logits, (fresh, _), routes = mla.forward(
            params, bound.model_for(tokens.size), tokens, want_kv=True,
            want_routes=True)
        cache, _ = pages.write_sequences(cache, None, fresh, None, row, n)
        return logits[0, at], routes, cache

    @functools.partial(jax.jit, donate_argnums=(4,), static_argnums=(6,))
    def window(params, tokens, n, written, cache, row, prior_blocks):
        # The engine's program: it hands back the last valid position alone,
        # so a shorter `n` looks at an earlier one.
        logits, cache, _, routes = mla.prefill_with_prefix(
            params, bound.model_for(tokens.size), tokens, n, written, cache,
            None, row, row[:, :prior_blocks], want_routes=True)
        return logits[0], routes, cache

    @functools.partial(jax.jit, donate_argnums=(3,))
    def decode(params, tokens, positions, cache, tables):
        logits, cache, _, routes = mla.decode_step(
            params, bound.model_for(tokens.size), tokens, positions, cache,
            None, tables, attention_fn=attend, want_routes=True)
        return logits, routes, cache

    def tally(chose):
        chose = np.asarray(chose)
        return [int(((chose >= first) & (chose < first + count)).sum()),
                int((chose >= mcfg.n_experts).sum())]

    lines, ok = [], True
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        params = jax.jit(lambda k: mla.init_params(mcfg, k))(
            jax.random.key(seed))
        cache, _ = pages.alloc(geom, device=device)
        seq = jax.random.randint(jax.random.key(seed + 1000),
                                 (longest + K,), 0, 257)
        per_seq = geom.max_blocks_per_seq
        tables = np.stack(
            [1 + lane * per_seq + np.arange(per_seq) for lane in range(B)]
        ).astype(np.int32)
        if args.shuffle_tables:
            # No two neighbours of a table adjacent in the pool: the decode
            # kernels fetch every group of entries a page at a time.
            tables = np.random.default_rng(seed).permutation(
                tables.reshape(-1)).reshape(tables.shape)
        tables = jnp.asarray(tables)
        plan_of = [plans[lane % len(plans)] for lane in range(B)]
        lens = [sum(p) for p in plan_of]
        counted, chosen_sum, choices = [0, 0], [0, 0], [0]

        def book(cache, chose):
            cache, held, zero, _ = state.take_counts(cache)
            for i, n in enumerate((held, zero)):
                counted[i] += int(n)
            for i, n in enumerate(tally(chose)):
                chosen_sum[i] += n
            choices[0] += int(np.asarray(chose).size)
            return cache

        # 1. prefill: every lane's prompt; the first lane of a plan is kept.
        looked, prefill_routes = {}, {}
        for lane in range(B):
            plan, row = plan_of[lane], tables[lane:lane + 1]
            keep = plans.index(plan) == lane
            n = plan[0]
            bucket = _pow2(n, block)
            toks = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(seq[:n])
            at = np.linspace(0, n - 1, args.positions).astype(int)
            got, routes, cache = prefill(
                params, toks, jnp.full((1,), n, jnp.int32), jnp.asarray(at),
                state.at_slots(cache, [lane]), row)
            cache = book(cache, routes)
            chose = [np.asarray(routes)[:, :n]]
            if keep:
                looked[f"prefill_{n}_in_{bucket}" + (
                    "_grouped" if bound.moe_grouped(bucket) else "")] = (
                        sum(plan), at, np.asarray(got))
            if len(plan) == 2:
                m = plan[1]
                wb = _pow2(m, block)
                wtoks = jnp.zeros((1, wb), jnp.int32).at[0, :m].set(
                    seq[n:n + m])
                short = [max(1, m // 4), m // 2, 3 * m // 4] if keep else []
                gots = []
                for valid in short + [m]:   # the whole window last
                    got, routes, cache = window(
                        params, wtoks, jnp.full((1,), valid, jnp.int32),
                        jnp.full((1,), n, jnp.int32),
                        state.at_slots(cache, [lane]), row,
                        _pow2(n // block))
                    cache = book(cache, routes)
                    gots.append(np.asarray(got))
                chose.append(np.asarray(routes)[:, :m])
                if keep:
                    looked[f"window_{wb}_p{_pow2(n // block)}"] = (
                        sum(plan), np.asarray([n + v - 1 for v in short + [m]]),
                        np.stack(gots))
            if keep:
                prefill_routes[sum(plan)] = np.concatenate(chose, axis=1)

        if args.degrade == "cache8":
            # reduce_precision, not a cast there and back: the TPU compiler
            # keeps excess precision and drops such a pair (chip run, PR 32).
            cache = dataclasses.replace(cache, k=jax.jit(
                lambda p: jax.lax.reduce_precision(p, exponent_bits=4,
                                                   mantissa_bits=3),
                donate_argnums=0)(cache.k))

        # 2. decode, teacher-forced, all lanes at once.
        steps, step_routes = [], []
        for k in range(K):
            positions = jnp.asarray([n + k for n in lens], jnp.int32)
            logits, routes, cache = decode(
                params, seq[positions], positions,
                state.at_slots(cache, np.arange(B)), tables)
            cache = book(cache, routes)
            steps.append(np.asarray(logits))            # [B, V]
            step_routes.append(np.asarray(routes))      # [L, B, k]
        steps = np.stack(steps, 1)                       # [B, K, V]
        step_routes = np.stack(step_routes, 2)           # [L, B, K, k]

        def judge(got, want):
            diff = float(np.abs(np.asarray(got, np.float32) - want).max())
            top = float(np.abs(want).max())
            return {"max_diff": diff, "max_ref": top, "rel": diff / top,
                    "positions": int(want.shape[0]),
                    "argmax_same": float((np.asarray(got).argmax(-1)
                                          == want.argmax(-1)).mean()),
                    "ok": diff <= TIGHT * top}

        # The reference, once a distinct length, held to the program's
        # choices.
        report, shortfalls, parted, decode_parts = {}, [], [], []
        for n in sorted(set(lens)):
            lanes = [i for i in range(B) if lens[i] == n]
            forced = np.concatenate(
                [prefill_routes[n], step_routes[:, lanes[0]]], axis=1)
            hidden, short = ref.hidden(params, seq[:n + K], **sizes,
                                       routes=jnp.asarray(forced))
            short = np.asarray(short)
            shortfalls.append(float(short.max()))
            parted.append(float((short > 0).mean()))
            want = np.asarray(ref.logits(params, hidden[n:n + K]))
            for lane in lanes:
                decode_parts.append(judge(steps[lane], want))
                decode_parts[-1]["same_routes_as_its_length"] = bool(
                    (step_routes[:, lane] == step_routes[:, lanes[0]]).all())
            for stage, (length, where, got) in looked.items():
                if length == n:
                    report[stage] = judge(got, np.asarray(
                        ref.logits(params, hidden[np.asarray(where)])))
        worst = max(decode_parts, key=lambda d: d["rel"])
        report["decode"] = {
            **worst, "positions": B * K,
            "argmax_same": float(np.mean([d["argmax_same"]
                                          for d in decode_parts])),
            "lanes_routed_like_their_length": float(np.mean(
                [d["same_routes_as_its_length"] for d in decode_parts])),
            "ok": all(d["ok"] for d in decode_parts)}
        line = {"seed": seed, "degrade": args.degrade or None,
                "tables": "shuffled" if args.shuffle_tables else "runs",
                "device": {"platform": device.platform,
                           "kind": device.device_kind},
                "model": mcfg.name, "n_layers": mcfg.n_layers,
                "kv_layers": geom.n_layers, "held_experts": [first, count],
                "lanes": B, "lane_tokens": sorted(set(lens)),
                "decode_steps": K, "pool_bytes": geom.pool_bytes,
                "memory": {k: v for k, v in (device.memory_stats() or {}).items()
                           if k in ("peak_bytes_in_use", "bytes_limit")},
                "routing": {"max_shortfall": max(shortfalls),
                            "choices_parted_share": float(np.mean(parted)),
                            "ok": max(shortfalls) <= SHORTFALL},
                "counts": {"held_zero_on_device": counted,
                           "held_zero_of_the_choices": chosen_sum,
                           "choices": choices[0],
                           "zero_share": chosen_sum[1] / choices[0],
                           "held_share_of_the_rest": chosen_sum[0] / (
                               choices[0] - chosen_sum[1]),
                           "ok": counted == chosen_sum},
                "stages": report,
                "seconds": round(time.monotonic() - t0, 1)}
        line["ok"] = bool(all(s["ok"] for s in report.values())
                          and line["routing"]["ok"] and line["counts"]["ok"])
        ok = ok and line["ok"]
        print(json.dumps(line), flush=True)
        lines.append(line)
        del params, cache, hidden
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
