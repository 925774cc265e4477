"""The latent-attention family on the chip against its plain reference, at a
benchmark configuration's widths and a cell's sizes.

    python scripts/compare_mla_reference.py \
        --config-file chipbench/configs/kimi-vl-a3b-cut.json --seeds 0,1,2,3,4

What is compared. One sequence of random byte-range token ids, `--prompt-tokens`
long plus `--decode-steps`. The program side runs what `TpuEngine`'s step
functions trace — `models.mla.forward` / `prefill_with_prefix` / `decode_step`
with the MoE form `models.bind(...).model_for` gives each shape, the decode attention
the engine binds (the Pallas latent kernel on a TPU), the page writes of
`kvcache/pages.py`, the engine's pool at `--max-batch` x `--max-model-len` —
jitted here to hand back logits before the sampler and the experts each token
chose, where the engine's own programs hand back a sampled token:

1. *prefill*: the plain first window, then prefix-continuation windows, for
   `--max-batch` lanes whose prompts are the sequence's first 1, 2, .. windows
   in turn; of the longest lane, logits at `--positions` positions of the
   first window and at four of every later one (the program hands back a
   window's last valid position, so a shorter valid length looks earlier);
2. *absorbed against expanded on the same cache*: the longest lane's first
   decode step as a one-token prefix-continuation window, and as step 3's;
3. *decode*: `--decode-steps` teacher-forced steps of all lanes at once
   through the latent pages (ragged: four lengths), logits of every lane.

The reference (`chipbench/configs/reference_mla_moe.py`, float32 under
`highest`, queries in blocks, only compared positions carried to the
vocabulary) runs once for each distinct lane length, **held to the experts the
program chose**. With random weights one near-tie that bf16 parts the other way
moves that position's logits, and those of every later position that attends to
it, by as much as the logits themselves (first chip run, PR 32: 36-53% of
positions had such a layer, max |diff| 2.7-3.9 at max |ref| 5); so the routing
is compared for what it is — every choice the reference would not have made has
to be a near-tie in the reference's own scores (`shortfall`) — and the logits
are compared along the program's own history, where what is left is rounding.

Each line of output is one seed. Exit code 1 if any seed passes a limit below.
`--degrade cache8` rounds the cached rows to 8 bits (4 of exponent, 3 of
mantissa) after the
prefill: the reading a lower precision gives, which has to fail.

On the CPU (`--model tiny-mla --prompt-tokens 64 --window 16 --max-model-len
128 --max-batch 4 --dtype float32`) it rehearses the control flow with the
kernel interpreted; its numbers say nothing about the chip.
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

# ---- limits, each between two readings (PERF.md section 6, PR 32) -----------
# max |diff| of a stage's logits over its max |ref|, the reference held to the
# program's experts: bf16 products (2^-9 an operand) through nine residual
# blocks. The correct program reads 0.0168-0.0233 over seeds 0-4 and every
# stage; a cache rounded to 8 bits reads 0.087-0.090 in decode.
TIGHT = 0.05
# Absorbed against expanded on the same cache, two bf16 programs, over the
# positions both routed alike (8-14 of 16): reads 0.0201-0.0233. The 8-bit
# cache gives both sides the same rows and reads the same 0.023: this limit
# guards the two forms' mathematics, TIGHT the cache's precision.
FORMS = 0.05
# How far under the reference's own sixth-best `s + b` a choice of the program
# may lie (7% of choices lie under it at all). bf16 activations move a score by
# about 1e-3: the correct program reads 0.0115-0.0159; with the 8-bit cache
# the decode steps read 0.037-0.039.
SHORTFALL = 0.025


def _reference():
    path = os.path.join(REPO, "chipbench", "configs", "reference_mla_moe.py")
    spec = importlib.util.spec_from_file_location("reference_mla_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="kimi-vl-a3b-cut")
    ap.add_argument("--config-file", default="")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-model-len", type=int, default=8192)
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--prompt-tokens", type=int, default=4096)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--positions", type=int, default=16)
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
    from llm_d_inference_scheduler_tpu.kvcache import pages
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
                       prefill_chunk=args.window,
                       pallas_attention=True, pallas_interpret=not on_tpu)
    # The forms an engine on this device binds (models/binding.py), without
    # the engine.
    bound = bind(mcfg, platform=device.platform,
                 interpret=cfg.pallas_interpret)
    attend = functools.partial(pages.latent_decode_attention, kernel=True,
                               interpret=not on_tpu)
    geom = pages.PageGeometry.for_engine(mcfg, cfg.max_batch,
                                         cfg.max_model_len)
    block, win, B = geom.block, args.window, args.max_batch
    n_win = args.prompt_tokens // win
    assert args.prompt_tokens == n_win * win and win % block == 0
    ref = _reference()
    sizes = dict(n_heads=mcfg.n_heads, kv_lora_rank=mcfg.kv_lora_rank,
                 qk_nope_head_dim=mcfg.qk_nope_head_dim,
                 qk_rope_head_dim=mcfg.qk_rope_head_dim,
                 rope_theta=mcfg.rope_theta, norm_eps=mcfg.norm_eps,
                 experts_per_token=mcfg.experts_per_token,
                 routed_scaling_factor=mcfg.routed_scaling_factor)

    # ---- the program's steps, logits and routes out ----
    @functools.partial(jax.jit, donate_argnums=(3,))
    def first_window(params, tokens, at, pool, row):
        logits, (rows, _), routes = mla.forward(
            params, bound.model_for(tokens.size), tokens, want_kv=True,
            want_routes=True)
        pool, _ = pages.write_sequences(
            pool, None, rows, None, row,
            jnp.full((1,), tokens.shape[1], jnp.int32))
        return logits[0, at], routes, pool

    def next_window(prior_blocks):
        @functools.partial(jax.jit, donate_argnums=(4,))
        def step(params, tokens, n, written, pool, row):
            # The engine's program: it hands back the last valid position
            # alone, so a shorter `n` looks at an earlier one (the tail is
            # masked, as in a prompt's last window) and the whole window,
            # run last, leaves the rows it should.
            logits, pool, _, routes = mla.prefill_with_prefix(
                params, bound.model_for(tokens.size), tokens, n, written, pool,
                None, row, row[:, :prior_blocks], want_routes=True)
            return logits[0], routes, pool
        return step

    @functools.partial(jax.jit, donate_argnums=(3,))
    def decode(params, tokens, positions, pool, tables):
        logits, pool, _, routes = mla.decode_step(
            params, bound.model_for(tokens.size), tokens, positions, pool,
            None, tables, attention_fn=attend, want_routes=True)
        return logits, routes, pool

    def pow2(n):
        p = 1
        while p < n:
            p *= 2
        return p

    windows = {}

    def window_fn(prior_blocks):
        if prior_blocks not in windows:
            windows[prior_blocks] = next_window(prior_blocks)
        return windows[prior_blocks]

    lines, ok = [], True
    K = args.decode_steps
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        params = jax.jit(lambda k: mla.init_params(mcfg, k))(
            jax.random.key(seed))
        pool, _ = pages.alloc(geom, device=device)
        seq = jax.random.randint(jax.random.key(seed + 1000),
                                 (args.prompt_tokens + K,), 0, 257)
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
        lens = [win * (1 + lane % n_win) for lane in range(B)]
        longest = max(range(B), key=lambda i: lens[i])
        at = np.linspace(0, win - 1, args.positions).astype(int)

        # 1. prefill: every lane's windows; the longest lane's are kept.
        looked, chose = {}, []     # stage -> (positions, logits); routes
        for lane in range(B):
            row = tables[lane:lane + 1]
            keep = lane == longest
            for w in range(lens[lane] // win):
                toks = seq[None, w * win:(w + 1) * win]
                if w == 0:
                    got, routes, pool = first_window(
                        params, toks, jnp.asarray(at), pool, row)
                    if keep:
                        looked["prefill_plain"] = (at, np.asarray(got))
                        chose.append(np.asarray(routes))
                    continue
                prior = pow2(w * win // block)
                written = jnp.full((1,), w * win, jnp.int32)
                short = [win // 4, win // 2, 3 * win // 4] if keep else []
                gots = []
                for n in short + [win]:
                    got, routes, pool = window_fn(prior)(
                        params, toks, jnp.full((1,), n, jnp.int32), written,
                        pool, row)
                    gots.append(np.asarray(got))
                if keep:
                    looked[f"prefill_window{w}_p{prior}"] = (
                        np.asarray([w * win + n - 1 for n in short + [win]]),
                        np.stack(gots))
                    chose.append(np.asarray(routes))   # the whole window's
        prefill_routes = np.concatenate(chose, axis=1)  # [Le, prompt, k]

        if args.degrade == "cache8":
            # reduce_precision, not a cast there and back: the TPU compiler
            # keeps excess precision and drops such a pair (chip run, PR 32).
            pool = jax.jit(
                lambda p: jax.lax.reduce_precision(p, exponent_bits=4,
                                                   mantissa_bits=3),
                donate_argnums=0)(pool)

        # 2. decode, teacher-forced, all lanes at once.
        steps, step_routes = [], []
        for k in range(K):
            positions = jnp.asarray([n + k for n in lens], jnp.int32)
            logits, routes, pool = decode(params, seq[positions], positions,
                                          pool, tables)
            steps.append(np.asarray(logits))            # [B, V]
            step_routes.append(np.asarray(routes))      # [Le, B, k]
        steps = np.stack(steps, 1)                       # [B, K, V]
        step_routes = np.stack(step_routes, 2)           # [Le, B, K, k]

        # 3. absorbed against expanded on the same cache: the first four
        # steps of one lane a length again, each as a one-token window over
        # the rows the pages hold by now (later rows are masked by the
        # prefix length). Two bf16 programs part a near-tie differently now
        # and then; positions they routed alike are compared.
        @functools.partial(jax.jit, static_argnums=(5,))
        def expanded_step(params, tokens, written, pool, row, prior_blocks):
            logits, _, _, routes = mla.prefill_with_prefix(
                params, bound.model_for(tokens.size), tokens,
                jnp.ones((1,), jnp.int32), written, pool, None, row,
                row[:, :prior_blocks], want_routes=True)
            return logits[0], routes[:, 0]

        forms, alike, looked_at = 0.0, 0, 0
        forms_top = 1.0
        for n in sorted(set(lens)):
            lane = lens.index(n)
            for k in range(min(4, K)):
                one = jnp.zeros((1, 16), jnp.int32).at[0, 0].set(seq[n + k])
                got, routes = expanded_step(
                    params, one, jnp.full((1,), n + k, jnp.int32), pool,
                    tables[lane:lane + 1], pow2(-(-(n + k) // block)))
                looked_at += 1
                if (np.sort(np.asarray(routes), -1)
                        == np.sort(step_routes[:, lane, k], -1)).all():
                    alike += 1
                    diff = float(np.abs(steps[lane, k] - np.asarray(got)).max())
                    if diff > forms:
                        forms, forms_top = diff, float(np.abs(
                            np.asarray(got)).max())
        report = {"absorbed_vs_expanded": {
            "max_diff": forms, "max_ref": forms_top, "rel": forms / forms_top,
            "positions": looked_at, "routed_alike": alike,
            "ok": alike > 0 and forms <= FORMS * forms_top}}

        def judge(got, want):
            diff = float(np.abs(np.asarray(got, np.float32) - want).max())
            top = float(np.abs(want).max())
            return {"max_diff": diff, "max_ref": top, "rel": diff / top,
                    "positions": int(want.shape[0]),
                    "argmax_same": float((np.asarray(got).argmax(-1)
                                          == want.argmax(-1)).mean()),
                    "ok": diff <= TIGHT * top}

        # The reference, once a distinct length, held to the program's experts.
        shortfalls, parted, decode_parts = [], [], []
        for n in sorted(set(lens)):
            lanes = [i for i in range(B) if lens[i] == n]
            forced = np.concatenate(
                [prefill_routes[:, :n], step_routes[:, lanes[0]]], axis=1)
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
            if n == lens[longest]:
                for stage, (where, got) in looked.items():
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
                "lanes": B, "lane_tokens": sorted(set(lens)),
                "decode_steps": K, "pool_bytes": geom.pool_bytes,
                "memory": {k: v for k, v in (device.memory_stats() or {}).items()
                           if k in ("peak_bytes_in_use", "bytes_limit")},
                "routing": {"max_shortfall": max(shortfalls),
                            "choices_parted_share": float(np.mean(parted)),
                            "ok": max(shortfalls) <= SHORTFALL},
                "stages": report,
                "seconds": round(time.monotonic() - t0, 1)}
        line["ok"] = bool(all(s["ok"] for s in report.values())
                          and line["routing"]["ok"])
        ok = ok and line["ok"]
        print(json.dumps(line), flush=True)
        lines.append(line)
        del params, pool, hidden
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
