"""The lfm2_moe family (models/hybrid.py's gated short convolutions and
rotating attention, routed experts behind them) on the chip against its plain
reference, at a benchmark configuration's widths and a cell's sizes.

    python scripts/compare_lfm2_reference.py \
        --config-file chipbench/configs/lfm2-8b-a1b-cut.json --seeds 0,1,2

What is compared. One sequence of random byte-range token ids a seed. The
program side runs what `TpuEngine`'s step functions trace --
`models.hybrid.forward` / `prefill_with_prefix` / `decode_step` in the forms
`models.bind` gives this device (the experts' form a program, the paged walk
over pages of two 64-wide heads a row: `attention_kernel` and `page_shape` in
each line), the page writes of `kvcache/pages.py` and the slots' tails of
`kvcache/state.py`, the engine's pools at `--max-batch` x `--max-model-len`
-- jitted here to hand back logits before the sampler, and every expert
layer's choices. `--max-batch` lanes, lane i in slot i, take turns over
`--lengths`:

1. *prefill*: a prompt in windows of `--window` tokens: the first a plain
   prefill, every later one a continuation that starts from the slot's
   carried tail and reads the pages (the last one padded to its power-of-two
   bucket where it is no power of two, a one-token window among them: the
   padding rows must change nothing). Of the first lane of each length,
   logits at `--positions` positions of the first window and at the last
   position of every later one;
2. *decode*: `--decode-steps` teacher-forced steps of all lanes at once
   through the pages and the tails; logits of the first lane of each length
   at every step, and of every other lane its largest difference from that
   lane (the same tokens in another slot and other pages);
3. *tails*: what the first lane of each length holds in its slot after the
   last step, against the reference's last two rows of `B * u` (Frobenius
   norm of the difference over that of the reference, the worst layer and
   the FIRST one by itself), and EXACTLY against every other lane of its
   length (bit for bit: the same tokens leave the same tail in any slot);
4. *router*: the program's `route` (the function its expert layers call)
   alone on 4,096 random rows against an f32 `highest` router on the same
   bf16 inputs: the share of rows that choose the same 4 experts and the
   largest error of a gate where they do. The logits cannot see the
   router's own precision, because the reference is held to the program's
   choices.

The reference (`chipbench/configs/reference_lfm2_moe.py`, float32 under
`highest`, a layer's weights cast up a layer and an expert at a time, the
head through the embedding transposed) runs once for each distinct length,
HELD TO THE PROGRAM'S EXPERT CHOICES (`routes`: with random weights a token's
fourth and fifth scores lie a rounding apart somewhere in every prompt, and
a near-tie parted the other way is another function from there on); how far
under the reference's own threshold the weakest of those choices lies is
`shortfall`, in the scores' unit. Its logits are computed for the compared
positions alone.

Each line of output is one seed. Exit code 1 if any seed passes a limit
below. `--fault` plants one of eight faults, each of which has to FAIL:
`tail_dropped` (every continuation window starts from zeros, not the slot's
tail), `swap_bc` (the reference exchanges B and C), `no_qk_norm` (the
reference leaves the heads' norms out), `no_rotary` (the reference does not
rotate), `router_bf16` (the program's router rounds its scores to bf16),
`bias_in_gates` (the reference weighs the chosen experts by score + bias),
`halves_swapped` (the decode walk reads the OTHER head of every page row),
`experts_fp8` (the program's expert weights rounded to float8_e4m3).

On the CPU (`--model tiny-lfm2 --lengths 40+9,33 --window 16 --max-model-len
128 --max-batch 4 --dtype float32 --decode-steps 6`) it rehearses the control
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

# ---- limits, each between two readings (PERF.md section 6, PR 54; my chip
# runs, seeds 0-2 healthy and every fault on seed 0, 64 lanes, prompts of 2,600
# and 2,049 tokens in windows of 1,024, 256 decode steps) -----------------------
# The residual stream is bf16 and 32 branches deep (16 layers of a mixer and
# an FFN), and the reference is held to the program's expert choices: what is
# left is the rounding of the configuration's stated precision, read, and the
# limits stand between it and the nearest fault.
#
# max |diff| of a stage's logits over its max |ref|. Healthy 0.034-0.050 over
# every stage and seed; the weakest fault by this measure, `no_qk_norm`, reads
# 0.1008 on the first window (0.042-0.051 elsewhere: four layers of sixteen
# attend, and the norm's learned weight is 1 +- 0.1), `bias_in_gates`
# 0.078-0.108, `no_rotary` 0.089-0.261, `halves_swapped` 0.163-0.183 on the
# decode stages (the prefill stages read no page: healthy), `experts_fp8`
# 0.35-0.48, `tail_dropped` 1.13 on the one-token window and 1.27 on the decode
# steps behind it (a window of 552 rows reads 0.038: two rows of it see the
# zeros, and its last row is 550 behind them), `swap_bc` 1.22-1.43.
TIGHT = 0.07
# The same as a root mean square over a stage's logits, over the
# reference's: what a systematic difference moves and a few unlucky logits do
# not. Healthy 0.037-0.046; `no_qk_norm` 0.0639 on the first window,
# `bias_in_gates` 0.075-0.096, `no_rotary` 0.092-0.172, `halves_swapped`
# 0.163-0.174, `experts_fp8` 0.40-0.42.
RMS = 0.052
# max |diff| between two lanes that hold the same tokens, over max |ref|:
# the same arithmetic in other slots and other pages reads 0.0 exactly on
# every seed; a lane that read another's page or slot would read as a fault
# does.
LANES = 0.005
# ||tail - tail_ref|| / ||tail_ref|| of a slot's two carried rows after the
# last decode step. It grows layer by layer with the residual stream's
# rounding: healthy 0.0037 in the first convolution layer (bf16 rounding of B,
# u and their product alone) to 0.054-0.058 in the last; `bias_in_gates`
# 0.122, `no_rotary` 0.131, `halves_swapped` 0.225, `experts_fp8` 0.53,
# `swap_bc` 1.48 (and 1.44 in the first layer, where nothing else is
# upstream). `no_qk_norm` reads 0.066 here and is the logits' to catch.
TAIL = 0.09
TAIL_FIRST = 0.008
# How far under the reference's own threshold a choice of the program may
# lie, in the scores' unit (a sigmoid's: 0 to 1): healthy 0.031-0.036 (the
# hidden states' bf16 rounding moves a score by that much, and the weakest of
# 14 x 2,856 x 4 choices is the one reported); `no_qk_norm` 0.076,
# `bias_in_gates` 0.098, `halves_swapped` 0.177, `no_rotary` 0.233,
# `experts_fp8` 0.39, `tail_dropped` 0.76, `swap_bc` 0.97. (`router_bf16`
# reads 0.047: the probe below is what sees it.)
SHORTFALL = 0.06
# The router's own precision, probed alone: the share of 4,096 random rows
# for which the program's `route` chooses the 4 experts an f32 `highest`
# router chooses from the same bf16 inputs, and the largest error of a gate
# where they agree. Healthy 1.0 and 0.0 on every seed (f32 accumulation of
# the same products); with the scores rounded to bf16 0.9968 and 3.1e-4.
ROUTER_SAME = 0.9985
GATE_ERROR = 5e-5

FAULTS = ("tail_dropped", "swap_bc", "no_qk_norm", "no_rotary", "router_bf16",
          "bias_in_gates", "halves_swapped", "experts_fp8")
_SWITCHES = {"swap_bc": dict(swap_bc=True), "no_qk_norm": dict(qk_norm=False),
             "no_rotary": dict(rotary=False),
             "bias_in_gates": dict(bias_in_gates=True)}
_KINDS = {"C": "conv", "Q": "full_attention"}


def _reference():
    path = os.path.join(REPO, "chipbench", "configs", "reference_lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("reference_lfm2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="lfm2-8b-a1b-cut")
    ap.add_argument("--config-file", default="")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-model-len", type=int, default=4608)
    ap.add_argument("--lengths", default="2600,2048+1",
                    help="prompt lengths; a+b is a prompt of a + b tokens "
                         "(the windows are cut from its whole length)")
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--decode-steps", type=int, default=256)
    ap.add_argument("--positions", type=int, default=16)
    ap.add_argument("--q-block", type=int, default=512)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--fault", default="", choices=("",) + FAULTS)
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
    plain_route = hybrid.route
    if args.fault == "router_bf16":
        def rounded_route(cfg, lp, h):
            # The product's result in bf16, as a router without
            # preferred_element_type=f32 would leave it.
            wide = jnp.dot(h, lp["router"]).astype(jnp.bfloat16)
            return plain_route(cfg, {**lp, "router": jnp.eye(
                wide.shape[-1], dtype=jnp.bfloat16)}, wide)

        hybrid.route = rounded_route
    device = jax.devices()[0]
    cfg = EngineConfig(model=args.model, max_batch=args.max_batch,
                       max_model_len=args.max_model_len)
    # The forms an engine on this device binds (models/binding.py), without
    # the engine.
    bound = bind(mcfg, platform=device.platform,
                 interpret=cfg.pallas_interpret)
    geom = pages.PageGeometry.for_engine(mcfg, cfg.max_batch,
                                         cfg.max_model_len)
    kernel = pages.use_kernel(geom.shape[-1], asked=None, interpret=False,
                              platform=device.platform, sharded=False)
    attend = functools.partial(pages.decode_attention, kernel=kernel)
    if args.fault == "halves_swapped":
        side, plain_attend = mcfg.kv_heads_a_row, attend

        def attend(q, k_pages, v_pages, layer, tables, lens, cur_k, cur_v):
            # Every query head as a head of the row's OTHER KV head (its own
            # current K/V moved there with it): the cached rows it reads are
            # the other head's.
            def across(t, heads):
                return t.reshape(t.shape[0], -1, side, heads // side,
                                 t.shape[-1])[:, :, ::-1].reshape(t.shape)

            per = q.shape[1] // cur_k.shape[1] * side
            return across(plain_attend(
                across(q, per), k_pages, v_pages, layer, tables, lens,
                across(cur_k, side), across(cur_v, side)), per)
    block, B, K, W = geom.block, args.max_batch, args.decode_steps, args.window
    ref = _reference()
    sizes = dict(layer_types=tuple(_KINDS[c] for c in mcfg.layer_pattern),
                 num_dense_layers=mcfg.first_k_dense, n_heads=mcfg.n_heads,
                 n_kv_heads=mcfg.n_kv_heads, head_dim=mcfg.head_dim,
                 top_k=mcfg.experts_per_token, rope_theta=mcfg.rope_theta,
                 norm_eps=mcfg.norm_eps, scaling=mcfg.routed_scaling_factor,
                 q_block=args.q_block, **_SWITCHES.get(args.fault, {}))

    def pow2(n, least=16):
        p = least
        while p < n:
            p *= 2
        return p

    kinds = [sum(int(x) for x in spec.split("+"))
             for spec in args.lengths.split(",")]
    lens = [kinds[lane % len(kinds)] for lane in range(B)]
    assert max(lens) + K <= args.max_model_len

    # ---- the program's steps, logits and routes out ----
    @functools.partial(jax.jit, donate_argnums=(4,))
    def first_window(params, tokens, n, at, cache, row):
        logits, (fresh, _), routes = hybrid.forward(
            params, bound.model_for(tokens.size), tokens, want_kv=True,
            seq_len=n, want_routes=True)
        cache, _ = pages.write_sequences(cache, None, fresh, None, row, n)
        return logits[0, at], routes, cache

    @functools.lru_cache(maxsize=None)
    def next_window(prior_blocks):
        @functools.partial(jax.jit, donate_argnums=(4,))
        def step(params, tokens, n, written, cache, row):
            logits, cache, _, routes = hybrid.prefill_with_prefix(
                params, bound.model_for(tokens.size), tokens, n, written,
                cache, None, row, row[:, :prior_blocks], want_routes=True)
            return logits[0], routes, cache
        return step

    @functools.partial(jax.jit, donate_argnums=(3,))
    def decode(params, tokens, positions, cache, tables, firsts):
        logits, cache, _, routes = hybrid.decode_step(
            params, bound.model_for(tokens.size), tokens, positions, cache,
            None, tables, attention_fn=attend, want_routes=True)
        # Every lane against the first lane of its length.
        apart = jnp.max(jnp.abs(logits - logits[firsts]))
        return (logits[:len(kinds)], routes[:, :len(kinds)], apart, cache)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def without_tail(cache, slot):
        return dataclasses.replace(cache, conv=cache.conv.at[:, slot].set(0))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def experts_in_fp8(params):
        # reduce_precision, not a cast there and back: the TPU compiler keeps
        # excess precision and drops such a pair (chip run, PR 32).
        return {**params, "experts": {
            n: (jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)
                if n in ("w1", "w3", "w2") else a)
            for n, a in params["experts"].items()}}

    @jax.jit
    def router_probe(stack, h):
        lp = {n: stack[n][0] for n in ("router", "router_bias")}
        idx, gates = hybrid.route(mcfg, lp, h)
        with jax.default_matmul_precision("highest"):
            scores = jax.nn.sigmoid(h.astype(jnp.float32)
                                    @ lp["router"].astype(jnp.float32))
        _, want = jax.lax.top_k(scores + lp["router_bias"],
                                mcfg.experts_per_token)
        same = jnp.all(jnp.sort(idx, -1) == jnp.sort(want, -1), axis=-1)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        exact = (chosen / jnp.sum(chosen, -1, keepdims=True)
                 * mcfg.routed_scaling_factor)
        return (jnp.mean(same.astype(jnp.float32)),
                jnp.max(jnp.where(same[:, None], jnp.abs(gates - exact), 0)))

    def at_lanes(cache, slots):
        return state.at_slots(cache, np.asarray(slots, np.int32))

    def init(seed):
        return jax.jit(lambda k: hybrid.init_params(mcfg, k))(
            jax.random.key(seed))

    lines, ok = [], True
    firsts = jnp.asarray([lane % len(kinds) for lane in range(B)], jnp.int32)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        params = init(seed)
        if args.fault == "experts_fp8":
            served_params, params = experts_in_fp8(params), None
        else:
            served_params = params
        cache, _ = pages.alloc(geom, device=device)
        seq = jax.random.randint(jax.random.key(seed + 1000),
                                 (max(lens) + K,), 0, 257)
        per_seq = geom.max_blocks_per_seq
        tables = jnp.asarray(np.stack(
            [1 + lane * per_seq + np.arange(per_seq) for lane in range(B)]
        ).astype(np.int32))

        # 1. prefill: every lane's windows; the first lane's of a kind kept.
        looked = {}        # stage -> (kind, positions, logits)
        routes_of = [[] for _ in kinds]   # [expert layers, tokens, k] pieces
        for lane in range(B):
            n, kind = lens[lane], lane % len(kinds)
            keep = lane < len(kinds)
            row = tables[lane:lane + 1]
            a = min(n, W)
            toks = jnp.zeros((1, pow2(a)), jnp.int32).at[0, :a].set(seq[:a])
            at = np.unique(np.linspace(0, a - 1, args.positions).astype(int))
            got, routes, cache = first_window(
                served_params, toks, jnp.full((1,), a, jnp.int32),
                jnp.asarray(at), at_lanes(cache, [lane]), row)
            cache = state.take_counts(cache)[0]
            if keep:
                looked[f"prefill_b{pow2(a)}_n{a}@{n}"] = (
                    kind, at, np.asarray(got))
                routes_of[kind].append(np.asarray(routes)[:, :a])
            for start in range(W, n, W):
                b = min(n - start, W)
                wb, prior = pow2(b), pow2(-(-start // block), 1)
                toks = jnp.zeros((1, wb), jnp.int32).at[0, :b].set(
                    seq[start:start + b])
                if args.fault == "tail_dropped":
                    cache = without_tail(cache, lane)
                got, routes, cache = next_window(prior)(
                    served_params, toks, jnp.full((1,), b, jnp.int32),
                    jnp.full((1,), start, jnp.int32),
                    at_lanes(cache, [lane]), row)
                cache = state.take_counts(cache)[0]
                if keep:
                    looked[f"window_s{wb}_p{prior}_n{start}+{b}@{n}"] = (
                        kind, np.asarray([start + b - 1]),
                        np.asarray(got)[None])
                    routes_of[kind].append(np.asarray(routes)[:, :b])

        # 2. decode, teacher-forced, all lanes at once.
        steps, apart, chosen = [], [], []
        for k in range(K):
            positions = jnp.asarray([n + k for n in lens], jnp.int32)
            logits, routes, d, cache = decode(
                served_params, seq[positions], positions,
                at_lanes(cache, np.arange(B)), tables, firsts)
            cache = state.take_counts(cache)[0]
            steps.append(logits), apart.append(d), chosen.append(routes)
        steps = np.asarray(jnp.stack(steps, 1))           # [kinds, K, V]
        apart = float(jnp.max(jnp.stack(apart)))
        chosen = np.asarray(jnp.stack(chosen, 2))          # [Le, kinds, K, k]
        tails = np.asarray(cache.conv[:, :B].astype(jnp.float32))  # [Lc, B, ..]
        tail_dtype = str(cache.conv.dtype)
        same_tails = all(
            np.array_equal(tails[:, lane], tails[:, lane % len(kinds)])
            for lane in range(B))
        probe = jax.random.normal(
            jax.random.key(seed + 2000), (4096, mcfg.d_model),
            jnp.float32).astype(served_params["embed"].dtype)
        router_same, gate_error = (float(x) for x in router_probe(
            served_params["experts"], probe))
        peak = {k: v for k, v in (device.memory_stats() or {}).items()
                if k in ("peak_bytes_in_use", "bytes_limit")}
        del cache
        if params is None:
            # The reference computes with the weights as the seed gives
            # them, not with what the fault made of them.
            served_params = None
            params = init(seed)

        def judge(got, want):
            apart = np.asarray(got, np.float32) - want
            diff, top = float(np.abs(apart).max()), float(np.abs(want).max())
            rms = float(np.sqrt(np.mean(np.square(apart))
                                / np.mean(np.square(want))))
            return {"max_diff": diff, "max_ref": top, "rel": diff / top,
                    "rms": rms, "positions": int(want.shape[0]),
                    "argmax_same": float((np.asarray(got).argmax(-1)
                                          == want.argmax(-1)).mean()),
                    "ok": diff <= TIGHT * top and rms <= RMS}

        # The reference, once a distinct length, held to the program's routes.
        report, tail_parts, tops, shortfall = {}, [], [], 0.0
        for kind, n in enumerate(kinds):
            forced = jnp.asarray(np.concatenate(
                routes_of[kind] + [chosen[:, kind]], axis=1))
            hidden, last, short = ref.hidden(
                params, seq[:n + K], **sizes, routes=forced, want_tail=True,
                want_shortfall=True)
            shortfall = max(shortfall, float(short))
            want = np.asarray(ref.logits(params, hidden[n:n + K]))
            tops.append(float(np.abs(want).max()))
            report[f"decode@{n}"] = judge(steps[kind], want)
            last = np.asarray(last).reshape(last.shape[0], -1)   # [Lc, 2 H]
            tail_parts.append([
                float(np.linalg.norm(tails[layer, kind] - last[layer])
                      / np.linalg.norm(last[layer]))
                for layer in range(last.shape[0])])
            for stage, (of, where, got) in looked.items():
                if of == kind:
                    report[stage] = judge(got, np.asarray(
                        ref.logits(params, hidden[np.asarray(where)])))
            del hidden
        report["lanes_apart"] = {"max_diff": apart, "rel": apart / min(tops),
                                 "ok": apart <= LANES * min(tops)}
        by_layer = np.max(np.asarray(tail_parts), axis=0)   # over lengths
        report["tails"] = {"rel": float(by_layer.max()),
                           "by_layer": by_layer.tolist(),
                           "dtype": tail_dtype,
                           "same_in_every_slot": bool(same_tails),
                           "ok": bool(by_layer.max() <= TAIL and same_tails)}
        report["tail_first_layer"] = {"rel": float(by_layer[0]),
                                      "ok": bool(by_layer[0] <= TAIL_FIRST)}
        report["shortfall"] = {"scores": shortfall,
                               "ok": shortfall <= SHORTFALL}
        report["router"] = {"same": router_same, "gate_error": gate_error,
                            "ok": (router_same >= ROUTER_SAME
                                   and gate_error <= GATE_ERROR)}
        line = {"seed": seed, "fault": args.fault or None,
                "device": {"platform": device.platform,
                           "kind": device.device_kind},
                "model": mcfg.name, "n_layers": mcfg.n_layers,
                "lanes": B, "lane_tokens": sorted(set(lens)),
                "window": W, "decode_steps": K,
                "attention_kernel": bool(kernel),
                "page_shape": list(geom.shape),
                "experts_form": {str(t): bound.model_for(t).moe_impl
                                 for t in (B, W)},
                "pool_bytes": geom.pool_bytes,
                "state_pool_bytes": geom.state.pool_bytes,
                "memory": peak,
                "worst_rel": max(s["rel"] for n, s in report.items()
                                 if "max_ref" in s),
                "worst_rms": max(s["rms"] for s in report.values()
                                 if "rms" in s),
                "stages": report,
                "seconds": round(time.monotonic() - t0, 1)}
        line["ok"] = bool(all(s["ok"] for s in report.values()))
        line["failed"] = sorted(n for n, s in report.items() if not s["ok"])
        ok = ok and line["ok"]
        print(json.dumps(line), flush=True)
        lines.append(line)
        # (The next seed's weights need the room.)
        params = served_params = None
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
