"""DeepSeek-V3.2's block on the chip against its plain reference, at a
benchmark configuration's widths and the `longctx-reason` cell's sizes.

    python scripts/compare_dsa_reference.py \
        --config-file chipbench/configs/deepseek-v3.2-exp-cut.json --seeds 0,1,2,3,4

scripts/compare_longcat_reference.py's comparison for the block of
models/mla.py that selects the rows it attends to. Two lanes, one prompt a
lane (`--lengths`: one past 16k, one that crosses index_topk inside its third
window), each written as the engine writes it: a first window of `--window`
tokens (`models.mla.forward`), then windows that continue it through BOTH page
pools (`prefill_with_prefix`, prior tables in the engine's power-of-two
buckets: the indexer reads its keys through the block table, the selection
runs, the expanded form attends by the mask), then `--decode-steps`
teacher-forced decode steps of both lanes at once (`decode_step` with the
attention the engine binds: the indexer over the lanes' key pages, the
selection, the Pallas kernel over the selected rows of the latent pages). The
MoE form and the indexer's form are `models.bind`'s for this device (the MoE
form shape by shape), the pools ride in the `kvcache/state.Cache`.
Jitted here to hand back logits before the sampler, the router's choices and
every query's selected rows.

The reference (`chipbench/configs/reference_deepseek_v32.py`, float32 under
`highest`, no cache, dense scores and a selection mask; the weights upcast a
layer at a time from a host copy: 4.6 B parameters are 18.5 GB in f32) runs
once a lane, **held to the outputs the program's router chose and to the rows
the program selected**, for compare_mla_reference.py's reason: with random
weights a near-tie that bf16 parts the other way moves that position's logits
and those of every later one as a different model would (an index score says
nothing of a row's attention weight here). So the choices are judged for what
they are, and the logits along the program's own history:

- *logits*: at every window's last token and at every decode step, max |diff|
  over max |ref| of the stage;
- *selection*: a layer, the share of the reference's OWN S_t (its f32 scores
  along the same history) that the program also chose, over all queries; the
  least over the layers has a limit, and the first layer one of its own;
- *routing*: how far under what the reference's own choice asked a forced
  choice lies (0 where they agree), its groups and its outputs judged apart.

Each line of output is one seed. Exit code 1 if any seed passes a limit below.
`--degrade latent8` / `--degrade index8` round one of the two pools to 8 bits
(4 of exponent, 3 of mantissa) after every program, as a pool held in 8 bits
would be read: each has to fail.

On the CPU (`--model tiny-dsa --lengths 150,70 --window 32 --max-model-len 192
--dtype float32 --decode-steps 3`) it rehearses the control flow with both
kernels interpreted; its numbers say nothing about the chip.
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

# ---- limits, each between two readings (PERF.md section 6, PR 41) -----------
# my chip runs, PR 41, seeds 0-4 healthy, seeds 0-1 with one pool rounded to
# 8 bits (--degrade): each limit lies between what the program reads and what
# an 8-bit pool reads, and either pool fails at least one.
# max |diff| of a stage's logits over its max |ref|, the reference held to the
# program's routes and rows (bf16 products through five layers): 0.0192-0.0303
# healthy; 0.0661-0.1239 with the latent pool in 8 bits (the indexer's keys do
# not move it: the reference attends by the program's rows).
TIGHT = 0.045
# The least share, over the layers, of the reference's own selection that the
# program also holds: 0.98045-0.98114 healthy (it falls with depth, 0.9972 in
# the first layer, as the hidden states part), 0.97557-0.97581 with the key
# pool in 8 bits, 0.9106-0.9125 with the latent pool in 8 bits.
SHARED = 0.978
# The same in the FIRST layer, whose input is the reference's to the bit, so
# that only the keys' and the scores' rounding part the sets: 0.99720-0.99722
# healthy (0.99961 the short lane), 0.98562-0.98566 with the key pool in 8
# bits: the clean probe of that pool.
SHARED_FIRST = 0.992
# How far under what the reference's own choice asked a choice of the program
# may lie, in the scores' unit (reference_deepseek_v32._experts: the outputs
# inside the program's groups, the groups by their two-best sums), the worst
# of ~20k positions x 4 layers: 0.0258-0.0579 healthy (13.6-13.8% of positions
# part somewhere), 0.0954-0.1396 with the latent pool in 8 bits (41-42%).
SHORTFALL = 0.075


def _reference():
    path = os.path.join(REPO, "chipbench", "configs",
                        "reference_deepseek_v32.py")
    spec = importlib.util.spec_from_file_location("reference_deepseek_v32",
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
    ap.add_argument("--model", default="deepseek-v3.2-exp-cut")
    ap.add_argument("--config-file", default="")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--max-model-len", type=int, default=18432)
    ap.add_argument("--lengths", default="16640,3000")
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--decode-steps", type=int, default=4)
    ap.add_argument("--q-block", type=int, default=64)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--degrade", default="",
                    choices=("", "latent8", "index8"))
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
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    lens = [int(x) for x in args.lengths.split(",")]
    B, K, W = len(lens), args.decode_steps, args.window
    cfg = EngineConfig(model=args.model, max_batch=B,
                       max_model_len=args.max_model_len,
                       pallas_attention=True, pallas_interpret=not on_tpu)
    # The forms an engine on this device binds (models/binding.py), without
    # the engine.
    bound = bind(configs.get_config(args.model), platform=device.platform,
                 interpret=cfg.pallas_interpret)
    mcfg = bound.mcfg
    attend = functools.partial(pages.latent_decode_attention, kernel=True,
                               interpret=not on_tpu)
    geom = pages.PageGeometry.for_engine(mcfg, B, cfg.max_model_len)
    block, per_seq = geom.block, geom.max_blocks_per_seq
    assert W % block == 0 and max(lens) + K <= args.max_model_len
    ref = _reference()
    sizes = dict(n_heads=mcfg.n_heads, kv_lora_rank=mcfg.kv_lora_rank,
                 qk_nope_head_dim=mcfg.qk_nope_head_dim,
                 qk_rope_head_dim=mcfg.qk_rope_head_dim,
                 rope_theta=mcfg.rope_theta, rope_yarn=mcfg.rope_yarn,
                 norm_eps=mcfg.norm_eps,
                 experts_per_token=mcfg.experts_per_token,
                 routed_scaling_factor=mcfg.routed_scaling_factor,
                 n_group=mcfg.n_group, topk_group=mcfg.topk_group,
                 index_n_heads=mcfg.index_n_heads,
                 index_head_dim=mcfg.index_head_dim,
                 index_topk=mcfg.index_topk, first_expert=mcfg.experts_first,
                 q_block=args.q_block)

    # ---- the program's steps: logits, routes and selections out ----
    @functools.partial(jax.jit, donate_argnums=(3,))
    def prefill(params, tokens, n, cache, row):
        logits, (fresh, _), (routes, picked) = mla.forward(
            params, bound.model_for(tokens.size), tokens, want_kv=True,
            want_routes=True)
        cache, _ = pages.write_sequences(cache, None, fresh, None, row, n)
        return logits[0, n[0] - 1], routes, picked[:, 0], cache

    @functools.partial(jax.jit, donate_argnums=(4,), static_argnums=(6,))
    def window(params, tokens, n, written, cache, row, prior_blocks):
        logits, cache, _, (routes, picked) = mla.prefill_with_prefix(
            params, bound.model_for(tokens.size), tokens, n, written, cache,
            None, row, row[:, :prior_blocks], want_routes=True)
        return logits[0], routes, picked[:, 0], cache

    @functools.partial(jax.jit, donate_argnums=(3,))
    def decode(params, tokens, positions, cache, tables):
        logits, cache, _, (routes, picked) = mla.decode_step(
            params, bound.model_for(tokens.size), tokens, positions, cache,
            None, tables, attention_fn=attend, want_routes=True)
        return logits, routes, picked, cache

    @functools.partial(jax.jit, donate_argnums=(0,))
    def rounded(pool):
        # reduce_precision, not a cast there and back: the TPU compiler keeps
        # excess precision and drops such a pair (chip run, PR 32).
        return jax.lax.reduce_precision(pool, exponent_bits=4, mantissa_bits=3)

    def kept(cache):
        """The cache between two programs: the counts out, and with
        --degrade one pool as 8 bits would hold it."""
        cache, *_ = state.take_counts(cache)
        if args.degrade == "latent8":
            cache = dataclasses.replace(cache, k=rounded(cache.k))
        if args.degrade == "index8":
            cache = dataclasses.replace(cache, idx=rounded(cache.idx))
        return cache

    lines, ok = [], True
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        params = jax.jit(lambda k: mla.init_params(mcfg, k))(
            jax.random.key(seed))
        cache, _ = pages.alloc(geom, device=device)
        seq = np.asarray(jax.random.randint(
            jax.random.key(seed + 1000), (max(lens) + K,), 0, 257))
        tables = np.stack(
            [1 + lane * per_seq + np.arange(per_seq) for lane in range(B)]
        ).astype(np.int32)
        if args.shuffle_tables:
            # No two neighbours of a table adjacent in the pool: the decode
            # kernels fetch every group of entries a page at a time.
            tables = np.random.default_rng(seed).permutation(
                tables.reshape(-1)).reshape(tables.shape)
        tables = jnp.asarray(tables)
        looked = [[] for _ in lens]        # (position, logits) a lane
        routes_of = [[] for _ in lens]     # [Le, tokens, k] pieces a lane
        picked_of = [np.zeros((geom.n_layers, n + K, n + K), bool)
                     for n in lens]

        # 1. every lane's prompt, a window at a time.
        for lane, n in enumerate(lens):
            row = tables[lane:lane + 1]
            for lo in range(0, n, W):
                m = min(W, n - lo)
                bucket = _pow2(m, block)
                toks = jnp.zeros((1, bucket), jnp.int32).at[0, :m].set(
                    seq[lo:lo + m])
                held = state.at_slots(cache, [lane])
                if lo == 0:
                    got, routes, picked, cache = prefill(
                        params, toks, jnp.full((1,), m, jnp.int32), held, row)
                    prior = 0
                else:
                    prior = _pow2(lo // block)
                    got, routes, picked, cache = window(
                        params, toks, jnp.full((1,), m, jnp.int32),
                        jnp.full((1,), lo, jnp.int32), held, row, prior)
                cache = kept(cache)
                looked[lane].append((lo + m - 1, np.asarray(got)))
                routes_of[lane].append(np.asarray(routes)[:, :m])
                picked = np.asarray(picked)      # [L, bucket, prior x blk + bucket]
                T = prior * block
                mine = picked_of[lane]
                mine[:, lo:lo + m, :lo] = picked[:, :m, :lo]
                mine[:, lo:lo + m, lo:lo + m] = picked[:, :m, T:T + m]
        peak_program = (device.memory_stats() or {}).get("peak_bytes_in_use")

        # 2. decode, teacher-forced, both lanes at once.
        steps = []
        for k in range(K):
            positions = np.asarray([n + k for n in lens], np.int32)
            logits, routes, picked, cache = decode(
                params, jnp.asarray(seq[positions]), jnp.asarray(positions),
                state.at_slots(cache, np.arange(B)), tables)
            cache = kept(cache)
            steps.append(np.asarray(logits))                    # [B, V]
            routes, picked = np.asarray(routes), np.asarray(picked)
            for lane, t in enumerate(positions):
                routes_of[lane].append(routes[:, lane:lane + 1])
                picked_of[lane][:, t, :t] = picked[:, lane, :t]
                picked_of[lane][:, t, t] = picked[:, lane, -1]
        steps = np.stack(steps, 1)                               # [B, K, V]

        # 3. the reference, from a host copy of the weights (the device is
        # its own now), held to the program's routes and rows.
        host = jax.tree.map(np.asarray, params)
        del params, cache, held
        report, shared_by_layer, shortfalls, parted = {}, [], [], []

        def judge(got, want):
            diff = float(np.abs(np.asarray(got, np.float32) - want).max())
            top = float(np.abs(want).max())
            return {"max_diff": diff, "max_ref": top, "rel": diff / top,
                    "positions": int(want.shape[0]),
                    "argmax_same": float((np.asarray(got).argmax(-1)
                                          == want.argmax(-1)).mean()),
                    "ok": diff <= TIGHT * top}

        for lane, n in enumerate(lens):
            forced = np.concatenate(routes_of[lane], axis=1)
            mine = picked_of[lane]
            hidden, short, shared = ref.hidden(
                host, seq[:n + K], **sizes, routes=jnp.asarray(forced),
                picked=lambda layer, lo, hi: mine[layer, lo:hi])
            short = np.asarray(short)
            shortfalls.append(float(short.max()))
            parted.append(float((short > 0).mean()))
            shared_by_layer.append([round(s, 5) for s in shared])
            at = np.asarray([p for p, _ in looked[lane]])
            report[f"windows_{n}"] = judge(
                np.stack([g for _, g in looked[lane]]),
                np.asarray(ref.logits(host, hidden[at])))
            report[f"decode_{n}"] = judge(
                steps[lane], np.asarray(ref.logits(host, hidden[n:n + K])))
            selecting = int((np.arange(n + K) >= mcfg.index_topk).sum())
            report[f"windows_{n}"]["queries_that_select"] = selecting
        least_shared = min(min(s) for s in shared_by_layer)
        first_shared = min(s[0] for s in shared_by_layer)
        line = {"seed": seed, "degrade": args.degrade or None,
                "tables": "shuffled" if args.shuffle_tables else "runs",
                "device": {"platform": device.platform,
                           "kind": device.device_kind},
                "model": mcfg.name, "n_layers": mcfg.n_layers,
                "index_topk": mcfg.index_topk, "index_scores": mcfg.index_impl,
                "held_experts": list(mcfg.held_experts),
                "lane_tokens": lens, "window": W, "decode_steps": K,
                "pool_bytes": [geom.pool_bytes, geom.index_pool_bytes],
                "memory": {"peak_bytes_in_use_program": peak_program,
                           "bytes_limit": (device.memory_stats() or {}).get(
                               "bytes_limit")},
                "selection": {"shared_by_lane_and_layer": shared_by_layer,
                              "least": least_shared, "limit": SHARED,
                              "first_layer": first_shared,
                              "first_layer_limit": SHARED_FIRST,
                              "ok": (least_shared >= SHARED
                                     and first_shared >= SHARED_FIRST)},
                "routing": {"max_shortfall": max(shortfalls),
                            "choices_parted_share": float(np.mean(parted)),
                            "limit": SHORTFALL,
                            "ok": max(shortfalls) <= SHORTFALL},
                "logits_limit": TIGHT, "stages": report,
                "seconds": round(time.monotonic() - t0, 1)}
        line["ok"] = bool(all(s["ok"] for s in report.values())
                          and line["routing"]["ok"]
                          and line["selection"]["ok"])
        ok = ok and line["ok"]
        print(json.dumps(line), flush=True)
        lines.append(line)
        del host, hidden
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
