"""The nemotron_h family (models/hybrid.py) on the chip against its plain
reference, at a benchmark configuration's widths and a cell's sizes.

    python scripts/compare_ssm_reference.py \
        --config-file chipbench/configs/nemotron-3-super-cut.json --seeds 0,1,2,3,4

What is compared. One sequence of random byte-range token ids. The program
side runs what `TpuEngine`'s step functions trace — `models.hybrid.forward` /
`prefill_with_prefix` / `decode_step` with the MoE form `models.bind(...).model_for`
gives each shape, the decode attention the engine binds, the page writes of
`kvcache/pages.py` and the slot state of `kvcache/state.py` (a decode step's
states updated in place by ops/pallas_ssm.py's kernel where the engine's rule
says so: `state_update` in each line), the engine's
pools at `--max-batch` x `--max-model-len` — jitted here to hand back logits
before the sampler and the experts each token chose, where the engine's own
programs hand back a sampled token. `--max-batch` lanes, lane i in slot i,
take turns over `--lengths`:

1. *prefill*: a prompt of `n` tokens in its power-of-two bucket (padded where
   n is no power of two: the state must be the true last token's); `a+b` is a
   prompt whose first window of `a` tokens is a plain prefill and whose next
   `b` tokens are a continuation window that starts from the slot's state and
   the pages. Of the first lane of each length, logits at `--positions`
   positions of a plain prefill, and of a continuation window at four (the
   program hands back a window's last valid position, so a shorter valid
   length looks earlier; the slot's state is put back after each such run,
   because a state continued twice is not the state continued once, and the
   run has to route its tokens as the whole window does, since the
   reference follows the whole window's experts);
2. *decode*: `--decode-steps` teacher-forced steps of all lanes at once
   through state and pages (ragged), logits of every lane;
3. *state*: what the first lane of each length holds in its slot after the
   last step, against the reference's state (Frobenius norm of the
   difference over that of the reference): the worst state-space layer, and
   the FIRST one by itself, which has one layer's rounding upstream of it
   and so is the nearest this comparison comes to the state's own precision;
4. *state precision*: the share of those slots' state values that bf16 cannot
   hold (an f32 value drawn at random needs more than 8 bits of mantissa 255
   times in 256). The reference cannot see this: bf16 activations upstream
   move the state by 1% and more, and a state rounded to bf16 at every step
   moves it by less (PERF.md section 6, PR 34, has both readings), so the
   precision the configuration states for the state is probed directly.

The reference (`chipbench/configs/reference_nemotron_h.py`, float32 under
`highest`, the recurrence token by token, the experts by a loop over the held
range) runs once for each distinct length, **held to the experts the program
chose** (scripts/compare_mla_reference.py says why); every choice the
reference would not have made has to be a near-tie in the reference's own
scores (`shortfall`).

Each line of output is one seed. Exit code 1 if any seed passes a limit
below. Two controls, which have to fail: `--degrade state16` keeps the state
pool's values in bf16 (rounded after the prefill and after every decode
step; it fails the precision probe and nothing else), `--degrade norouted`
has the reference leave the routed experts out (it fails everything).

On the CPU (`--model tiny-hybrid --lengths 16,21,40,32+9 --max-model-len 128
--max-batch 6 --dtype float32 --decode-steps 4`) it rehearses the control
flow; its numbers say nothing about the chip.
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

# ---- limits, each between two readings (PERF.md section 6, PR 34; my chip
# runs, seeds 0-4, 64 lanes, 64 decode steps) ---------------------------------
# max |diff| of a stage's logits over its max |ref|, the reference held to the
# program's experts: bf16 products (2^-9 an operand) through eleven residual
# blocks. The correct program reads 0.0100-0.0139 over every stage and seed;
# without the routed experts the reference reads 0.77-0.95.
TIGHT = 0.03
# How far under the reference's own 22nd-best `s + b` a choice of the program
# may lie (14-15% of positions have such a choice in some layer: 22 of 512
# chosen leaves many near-ties). bf16 activations move a score by about 1e-3:
# the correct program reads 0.0072-0.0087; without the routed experts 0.74.
SHORTFALL = 0.02
# ||S - S_ref|| / ||S_ref|| of a slot's state after the last decode step, the
# first lane of each length. The worst state-space layer (bf16 activations
# through up to ten blocks upstream of it) reads 0.0181-0.0205, the first
# state-space layer alone (one block upstream) 0.0083-0.0104; without the
# routed experts 1.09 and 0.82. A state kept in bf16 reads 0.0199-0.0208 and
# 0.0098-0.0101: inside the correct program's own range, which is why the
# probe below exists.
STATE = 0.04
STATE_FIRST = 0.02
# Share of a slot's state values that bf16 cannot hold: an f32 state reads
# 0.99 and more, a state kept in bf16 reads 0.
STATE_BEYOND_BF16 = 0.9


def _reference():
    path = os.path.join(REPO, "chipbench", "configs", "reference_nemotron_h.py")
    spec = importlib.util.spec_from_file_location("reference_nemotron_h", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="nemotron-3-super-cut")
    ap.add_argument("--config-file", default="")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-model-len", type=int, default=2048)
    ap.add_argument("--lengths", default="128,200,400,512+300,1000")
    ap.add_argument("--decode-steps", type=int, default=64)
    ap.add_argument("--positions", type=int, default=16)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--degrade", default="",
                    choices=("", "state16", "norouted"))
    ap.add_argument("--state-update", default="",
                    choices=("", "gathered", "kernel", "kernel_interpret"),
                    help="how decode steps fetch the slots' states; default "
                         "what the engine's rule gives on this device")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.engine.config import EngineConfig
    from llm_d_inference_scheduler_tpu.kvcache import pages, state
    from llm_d_inference_scheduler_tpu.models import bind, configs, hybrid
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
    cfg = EngineConfig(model=args.model, max_batch=args.max_batch,
                       max_model_len=args.max_model_len)
    # The forms an engine on this device binds (models/binding.py), without
    # the engine; --state-update sets that one over the rule.
    bound = bind(mcfg, platform=device.platform,
                 interpret=cfg.pallas_interpret,
                 forced=({"ssm_impl": args.state_update}
                         if args.state_update else None))
    geom = pages.PageGeometry.for_engine(mcfg, cfg.max_batch,
                                         cfg.max_model_len)
    kernel = pages.use_kernel(geom.shape[-1], asked=None, interpret=False,
                              platform=device.platform, sharded=False)
    attend = functools.partial(pages.decode_attention, kernel=kernel)
    block, B, K = geom.block, args.max_batch, args.decode_steps
    ref = _reference()
    first_expert, _ = mcfg.held_experts
    sizes = dict(pattern=mcfg.layer_pattern, n_heads=mcfg.n_heads,
                 n_kv_heads=mcfg.n_kv_heads, head_dim=mcfg.head_dim,
                 ssm_heads=mcfg.ssm_heads, ssm_head_dim=mcfg.ssm_head_dim,
                 ssm_state=mcfg.ssm_state, ssm_groups=mcfg.ssm_groups,
                 norm_eps=mcfg.norm_eps,
                 experts_per_token=mcfg.experts_per_token,
                 routed_scaling_factor=mcfg.routed_scaling_factor,
                 first_expert=first_expert)

    def pow2(n, least=1):
        p = least
        while p < n:
            p *= 2
        return p

    # A length is (first window, continuation window or 0).
    kinds = [tuple(int(x) for x in (spec + "+0").split("+")[:2])
             for spec in args.lengths.split(",")]
    lens = [sum(kinds[lane % len(kinds)]) for lane in range(B)]
    assert max(lens) + K <= args.max_model_len

    # ---- the program's steps, logits and routes out ----
    @functools.partial(jax.jit, donate_argnums=(4,))
    def first_window(params, tokens, n, at, cache, row):
        logits, (fresh, _), routes = hybrid.forward(
            params, bound.model_for(tokens.size), tokens, want_kv=True,
            seq_len=n, want_routes=True)
        cache, _ = pages.write_sequences(cache, None, fresh, None, row, n)
        return logits[0, at], routes, cache

    def next_window(prior_blocks):
        @functools.partial(jax.jit, donate_argnums=(4,))
        def step(params, tokens, n, written, cache, row):
            logits, cache, _, routes = hybrid.prefill_with_prefix(
                params, bound.model_for(tokens.size), tokens, n, written,
                cache, None, row, row[:, :prior_blocks], want_routes=True)
            return logits[0], routes, cache
        return step

    @jax.jit
    def slot_rows(cache, lane):
        return cache.ssm[:, lane], cache.conv[:, lane]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def put_slot_rows(cache, lane, ssm, conv):
        return dataclasses.replace(cache, ssm=cache.ssm.at[:, lane].set(ssm),
                                   conv=cache.conv.at[:, lane].set(conv))

    @functools.partial(jax.jit, donate_argnums=(3,))
    def decode(params, tokens, positions, cache, tables):
        logits, cache, _, routes = hybrid.decode_step(
            params, bound.model_for(tokens.size), tokens, positions, cache,
            None, tables, attention_fn=attend, want_routes=True)
        return logits, routes, cache

    @functools.partial(jax.jit, donate_argnums=(0,))
    def state_in_bf16(cache):
        # reduce_precision, not a cast there and back: the TPU compiler keeps
        # excess precision and drops such a pair (chip run, PR 32).
        return dataclasses.replace(cache, ssm=jax.lax.reduce_precision(
            cache.ssm, exponent_bits=8, mantissa_bits=7))

    windows = {}

    def window_fn(prior_blocks):
        if prior_blocks not in windows:
            windows[prior_blocks] = next_window(prior_blocks)
        return windows[prior_blocks]

    def at_lanes(cache, slots):
        return state.at_slots(cache, np.asarray(slots, np.int32))

    lines, ok = [], True
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        params = jax.jit(lambda k: hybrid.init_params(mcfg, k))(
            jax.random.key(seed))
        cache, _ = pages.alloc(geom, device=device)
        seq = jax.random.randint(jax.random.key(seed + 1000),
                                 (max(lens) + K,), 0, 257)
        per_seq = geom.max_blocks_per_seq
        tables = jnp.asarray(np.stack(
            [1 + lane * per_seq + np.arange(per_seq) for lane in range(B)]
        ).astype(np.int32))

        # 1. prefill: every lane's windows; the first lane's of a kind kept.
        looked = {}        # stage -> (positions, logits)
        prefill_routes = {}   # length -> [Le, n, k]
        held = 0
        for lane in range(B):
            a, b = kinds[lane % len(kinds)]
            keep = lane < len(kinds)
            row = tables[lane:lane + 1]
            bucket = pow2(a, 16)
            toks = jnp.zeros((1, bucket), jnp.int32).at[0, :a].set(seq[:a])
            at = np.unique(np.linspace(0, a - 1, args.positions).astype(int))
            got, routes, cache = first_window(
                params, toks, jnp.full((1,), a, jnp.int32), jnp.asarray(at),
                at_lanes(cache, [lane]), row)
            cache, n_held, *_ = state.take_counts(cache)
            chose = [np.asarray(routes)[:, :a]]
            if keep:
                held += int(n_held)
                looked[f"prefill_b{bucket}_n{a}"] = (at, np.asarray(got))
            if b:
                wb, prior = pow2(b, 16), pow2(-(-a // block))
                toks = jnp.zeros((1, wb), jnp.int32).at[0, :b].set(
                    seq[a:a + b])
                written = jnp.full((1,), a, jnp.int32)
                short = [b // 4, b // 2, 3 * b // 4] if keep else []
                before = slot_rows(cache, lane)
                gots, short_routes = [], []
                for n in short + [b]:
                    got, routes, cache = window_fn(prior)(
                        params, toks, jnp.full((1,), n, jnp.int32), written,
                        at_lanes(cache, [lane]), row)
                    cache, *_ = state.take_counts(cache)
                    gots.append(np.asarray(got))
                    short_routes.append(np.asarray(routes)[:, :n])
                    if n != b:
                        cache = put_slot_rows(cache, lane, *before)
                chose.append(short_routes[-1])
                if keep:
                    # One program ran them all, so a shorter run routed its
                    # tokens as the whole window did.
                    assert all((r == short_routes[-1][:, :r.shape[1]]).all()
                               for r in short_routes)
                    looked[f"window_s{wb}_p{prior}_n{a}+{b}"] = (
                        np.asarray([a + n - 1 for n in short + [b]]),
                        np.stack(gots))
            if keep:
                prefill_routes[a + b] = np.concatenate(chose, axis=1)

        if args.degrade == "state16":
            cache = state_in_bf16(cache)

        # 2. decode, teacher-forced, all lanes at once.
        steps, step_routes = [], []
        for k in range(K):
            positions = jnp.asarray([n + k for n in lens], jnp.int32)
            logits, routes, cache = decode(
                params, seq[positions], positions,
                at_lanes(cache, np.arange(B)), tables)
            cache, *_ = state.take_counts(cache)
            if args.degrade == "state16":
                cache = state_in_bf16(cache)
            steps.append(np.asarray(logits))            # [B, V]
            step_routes.append(np.asarray(routes))      # [Le, B, k]
        steps = np.stack(steps, 1)                       # [B, K, V]
        step_routes = np.stack(step_routes, 2)           # [Le, B, K, k]
        slot_state = np.asarray(cache.ssm[:, :len(kinds)])  # [Lm, kinds, ...]
        as_bf16 = np.asarray(jnp.asarray(slot_state).astype(jnp.bfloat16)
                             .astype(jnp.float32))
        beyond_bf16 = float(np.mean(slot_state != as_bf16))

        def judge(got, want):
            diff = float(np.abs(np.asarray(got, np.float32) - want).max())
            top = float(np.abs(want).max())
            return {"max_diff": diff, "max_ref": top, "rel": diff / top,
                    "positions": int(want.shape[0]),
                    "argmax_same": float((np.asarray(got).argmax(-1)
                                          == want.argmax(-1)).mean()),
                    "ok": diff <= TIGHT * top}

        # The reference, once a distinct length, held to the program's experts.
        report, shortfalls, parted, decode_parts, state_parts = (
            {}, [], [], [], [])
        for kind, (a, b) in enumerate(kinds):
            n = a + b
            lanes = [i for i in range(B) if i % len(kinds) == kind]
            forced = np.concatenate(
                [prefill_routes[n], step_routes[:, lanes[0]]], axis=1)
            hidden, short, last = ref.hidden(
                params, seq[:n + K], **sizes, routes=jnp.asarray(forced),
                routed=args.degrade != "norouted", want_state=True)
            short = np.asarray(short)
            shortfalls.append(float(short.max()))
            parted.append(float((short > 0).mean()))
            want = np.asarray(ref.logits(params, hidden[n:n + K]))
            for lane in lanes:
                decode_parts.append(judge(steps[lane], want))
                decode_parts[-1]["same_routes_as_its_length"] = bool(
                    (step_routes[:, lane] == step_routes[:, lanes[0]]).all())
            last = np.asarray(last)
            state_parts.append([
                float(np.linalg.norm(slot_state[layer, kind] - last[layer])
                      / np.linalg.norm(last[layer]))
                for layer in range(last.shape[0])])
            for stage, (where, got) in looked.items():
                if stage.endswith(f"_n{a}+{b}" if b else f"_n{a}"):
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
        by_layer = np.max(np.asarray(state_parts), axis=0)   # over lengths
        report["state"] = {"rel": float(by_layer.max()),
                           "by_layer": by_layer.tolist(),
                           "ok": bool(by_layer.max() <= STATE)}
        report["state_first_layer"] = {"rel": float(by_layer[0]),
                                       "ok": bool(by_layer[0] <= STATE_FIRST)}
        report["state_precision"] = {
            "share_beyond_bf16": beyond_bf16,
            "ok": beyond_bf16 >= STATE_BEYOND_BF16}
        pairs = (sum(pow2(a, 16) for a, _ in kinds) * mcfg.experts_per_token
                 * mcfg.layer_pattern.count("E"))
        line = {"seed": seed, "degrade": args.degrade or None,
                "device": {"platform": device.platform,
                           "kind": device.device_kind},
                "model": mcfg.name, "n_layers": mcfg.n_layers,
                "lanes": B, "lane_tokens": sorted(set(lens)),
                "decode_steps": K, "attention_kernel": bool(kernel),
                "state_update": bound.mcfg.ssm_impl,
                "pool_bytes": geom.pool_bytes,
                "state_pool_bytes": geom.state.pool_bytes,
                "memory": {k: v for k, v in (device.memory_stats() or {}).items()
                           if k in ("peak_bytes_in_use", "bytes_limit")},
                "routing": {"max_shortfall": max(shortfalls),
                            "choices_parted_share": float(np.mean(parted)),
                            # Of every row, padded ones too, of the first
                            # window of each length (the device's count).
                            "held_pair_share_first_windows": held / pairs,
                            "ok": max(shortfalls) <= SHORTFALL},
                "stages": report,
                "seconds": round(time.monotonic() - t0, 1)}
        line["ok"] = bool(all(s["ok"] for s in report.values())
                          and line["routing"]["ok"])
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
