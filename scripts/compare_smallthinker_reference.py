"""SmallThinker's block on the chip against its plain reference, at the
benchmark configuration's widths and the `longctx-16k` cell's sizes.

    python scripts/compare_smallthinker_reference.py \
        --config-file chipbench/configs/smallthinker-21b-a3b-cut.json --seeds 0,1

scripts/compare_dots3_reference.py's comparison for a model of models/llama.py
whose layers are of two kinds. Two lanes, one prompt a lane (`--lengths`: one
that ends past 12k tokens, one that crosses the window's 4,096 while it
decodes), each written as the engine writes it: a first window of `--window`
tokens (`models.llama.forward`), then windows that continue it through BOTH
pool pairs (`prefill_with_prefix`: the full layers read the whole prefix
through the block table; the window layers read the pages of their own pools
that end where the window starts), then `--decode-steps` teacher-forced decode
steps of both lanes at once (`decode_step`: the full layers' Pallas walk over
the whole context, the window layers' from the window's first page). The
window layers' pages come from the engine's own owner
(`engine/blocks.WindowedAllocator.slide`, step by step): a lane gives pages
back while it decodes, takes as many again, and its window slides across
both. The MoE form and the kernels' forms are `models.bind`'s for this device.

The reference (`chipbench/configs/reference_smallthinker.py`, float32 under
`highest`, no cache) runs once a lane, **held to the experts the program's
routers chose** (compare_dsa_reference.py's reason: with random weights a
near-tie that a bf16 program parts the other way moves that position's logits
as a different model would), so the choices are judged for what they are and
the logits along the program's own history:

- *logits*: at every window's last token and at every decode step, max |diff|
  over max |ref| of the stage, and the error's root mean square over the
  reference's;
- *the window's edge*: the short lane's decode logits past the window's length
  are closer (root mean square) to the reference at `sliding_window_size` than
  to the reference one token narrower and to the one one token wider, by the
  margin below: a window off by one row moves the logits far less than bf16
  rounding does, but it moves them in ITS direction, and over hundreds of
  steps x 151,936 logits the rounding is orthogonal to it;
- *routing*: how far under what the reference's own choice asked a forced
  choice lies; and, probed alone, the share of 4,096 random tokens for which
  the program's own `_route` chooses what an f32 router chooses from the same
  inputs, with the largest gate error where they agree (the hidden states'
  rounding hides the router's from every statistic above: this one sees the
  router's alone).

Each line of output is one seed. Exit code 1 if any seed fails a limit below.
`--fault` plants one of five faults in the PROGRAM's side, and each has to
fail: `router_bf16` (the router's logits rounded to bf16 where f32 is
stated), `experts_fp8` (the experts' weights rounded to float8_e4m3, the
nearest precision below the bf16 the configuration states), `window_4095`
(the window layers and their owner one row short), `rope_on_full` (the full
layers rotated), `router_reads_ffn` (the router fed the FFN's normed input).

On the CPU (`--model tiny-swa-kv --lengths 150,70 --window 32 --max-model-len
256 --dtype float32 --decode-steps 40`) it rehearses the control flow with the
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

# ---- limits, each between two readings (PERF.md section 6, PR 48) -----------
# my chip runs, PR 48: seeds 0-2 healthy, seed 0 with each fault planted
# (--fault); every fault's reading fails at least one limit, no healthy one
# fails any.
# max |diff| of a stage's logits over its max |ref| (bf16 products through
# eight layers, the reference held to the program's routes): 0.0067-0.0094
# healthy; 0.042-0.047 with the experts' weights in float8_e4m3, 0.39-0.47
# with the full layers rotated, 0.70-0.84 with the router fed the FFN's
# input (the gross faults' limit; the fine ones are the next three's).
TIGHT = 0.02
# The root mean square of a stage's error over its reference's: 0.0075-0.0083
# healthy (rounding, the same at every stage and seed); 0.044-0.048 with
# float8 experts, 0.39-0.43 and 0.71-0.93 with the two gross faults.
TIGHT_RMS = 0.018
# How much closer (root mean square) the short lane's decode logits past the
# window's length (352 steps) lie to the reference at the stated window than
# to either neighbour, as a ratio: 1.0034-1.0040 to the narrower and
# 1.0027-1.0047 to the wider healthy; 0.9971 to the narrower one with a
# window of 4,095 in the program (1.0041 to the wider).
EDGE = 1.001
# How far under what the reference's own choice asked a choice of the program
# may lie, in the router's logits' unit: 0.045-0.047 healthy (4.1-4.7% of
# positions part somewhere: the hidden states' own rounding sets it, so it
# cannot see the router's, 0.051 with bf16 logits: the probe below does);
# 0.35 with float8 experts, 4.0 with the full layers rotated.
SHORTFALL = 0.12
# The router's own precision, probed alone on the chip: the share of 4,096
# random tokens for which the program's `_route` chooses the 6 experts an f32
# `highest` router chooses from the same bf16 inputs, and the largest error
# of a gate where they agree. 1.0 and 0.0 healthy (seeds 0-2: f32
# accumulation of the same products); 0.9167 and 3.4e-3 with the router's
# logits rounded to bf16.
ROUTER_SAME = 0.97
GATE_ERROR = 5e-5

FAULTS = ("", "router_bf16", "experts_fp8", "window_4095", "rope_on_full",
          "router_reads_ffn")


def _reference():
    path = os.path.join(REPO, "chipbench", "configs",
                        "reference_smallthinker.py")
    spec = importlib.util.spec_from_file_location("reference_smallthinker",
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
    ap.add_argument("--model", default="smallthinker-21b-a3b-cut")
    ap.add_argument("--config-file", default="")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--max-model-len", type=int, default=16384)
    ap.add_argument("--lengths", default="11776,3840")
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--decode-steps", type=int, default=608)
    ap.add_argument("--q-block", type=int, default=256)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--fault", default="", choices=FAULTS)
    ap.add_argument("--shuffle-tables", action="store_true",
                    help="put both pools' pages at shuffled physical ids "
                         "under the owner's tables (no runs of adjacent "
                         "pages for the decode kernels to fetch as one "
                         "copy); default as the owner hands them out: "
                         "ascending tables, the window pool in stretches")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.engine.blocks import allocator_for
    from llm_d_inference_scheduler_tpu.engine.config import EngineConfig
    from llm_d_inference_scheduler_tpu.kvcache import pages, state
    from llm_d_inference_scheduler_tpu.models import bind, configs, llama
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
    stated = configs.get_config(args.model)      # what the reference computes
    served = stated                               # what the program computes
    if args.fault == "window_4095":
        served = dataclasses.replace(served, kv_window=served.kv_window - 1)
    if args.fault == "rope_on_full":
        served = dataclasses.replace(served, full_nope=False)
    if args.fault == "router_reads_ffn":
        plain_ffn = llama._ffn

        def late_ffn(cfg, lp, h, route=None):
            # The experts the router chooses from the FFN's own normed
            # input, whatever it chose ahead of the attention.
            return plain_ffn(cfg, lp, h, llama._route(cfg, lp, h))

        llama._ffn = late_ffn
    if args.fault == "router_bf16":
        plain_route = llama._route

        def rounded_route(cfg, lp, h):
            # The product's result in the operands' dtype, as a router
            # without preferred_element_type=f32 would leave it.
            wide = jnp.dot(h, lp["router"]).astype(jnp.bfloat16)
            return plain_route(cfg, {**lp, "router": jnp.eye(
                wide.shape[-1], dtype=jnp.bfloat16)}, wide)

        llama._route = rounded_route
    configs._REGISTRY[args.model] = served
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    lens = [int(x) for x in args.lengths.split(",")]
    B, K, W = len(lens), args.decode_steps, args.window
    cfg = EngineConfig(model=args.model, max_batch=B,
                       max_model_len=args.max_model_len,
                       pallas_attention=True, pallas_interpret=not on_tpu)
    bound = bind(served, platform=device.platform,
                 interpret=cfg.pallas_interpret)
    mcfg = bound.mcfg
    attend = functools.partial(pages.decode_attention, kernel=True,
                               interpret=not on_tpu)
    geom = pages.PageGeometry.for_engine(mcfg, B, cfg.max_model_len)
    block, per_seq = geom.block, geom.max_blocks_per_seq
    assert W % block == 0 and max(lens) + K <= args.max_model_len
    ref = _reference()
    window_layers = [int(ch == "W") for ch in stated.layer_pattern]
    sizes = dict(
        n_heads=stated.n_heads, n_kv_heads=stated.n_kv_heads,
        head_dim=stated.head_dim, rope_theta=stated.rope_theta,
        norm_eps=stated.norm_eps, experts_per_token=stated.experts_per_token,
        rope_layout=[w or int(not stated.full_nope) for w in window_layers],
        sliding_window_layout=window_layers,
        sliding_window_size=stated.kv_window, q_block=args.q_block)

    own_routes = args.fault == "router_reads_ffn"

    def routed(routes, rows):
        """A program's routes [L, B, S, k] for its first lane's ``rows``; a
        program whose FFN routes itself (a planted fault) tells what it did
        not use, and the reference keeps its own."""
        return None if own_routes else np.asarray(routes)[:, 0, :rows]

    # ---- the program's steps: logits and routes out ----
    @functools.partial(jax.jit, donate_argnums=(3,))
    def prefill(params, tokens, n, cache, row):
        logits, (fresh, _), routes = llama.forward(
            params, bound.model_for(tokens.size), tokens, want_kv=True,
            want_routes=True)
        cache, _ = pages.write_sequences(cache, None, fresh, None, row, n)
        return logits[0, n[0] - 1], routes, cache

    @functools.partial(jax.jit, donate_argnums=(4,), static_argnums=(6,))
    def window(params, tokens, n, written, cache, row, prior_blocks):
        logits, cache, _, routes = llama.prefill_with_prefix(
            params, bound.model_for(tokens.size), tokens, n, written, cache,
            None, row, row[:, :prior_blocks], want_routes=True)
        return logits[0], routes, cache

    @functools.partial(jax.jit, donate_argnums=(3,))
    def decode(params, tokens, positions, cache, tables):
        logits, cache, _, routes = llama.decode_step(
            params, bound.model_for(tokens.size), tokens, positions, cache,
            None, tables, attention_fn=attend, want_routes=True)
        return logits, routes, cache

    def router_probe(params, seed, tokens=4096):
        """(share of tokens whose chosen experts are the f32 router's, the
        largest gate error among those): the program's own `_route` on the
        first layer's router against the same arithmetic in float32 under
        `highest`, on unit-variance inputs in the model's dtype."""
        lp = {"router": params["layers"]["router"][0]}
        h = jax.random.normal(jax.random.key(seed + 2000),
                              (tokens, stated.d_model), jnp.float32
                              ).astype(lp["router"].dtype)
        idx, gates = jax.jit(lambda lp, h: llama._route(mcfg, lp, h))(lp, h)
        with jax.default_matmul_precision("highest"):
            want, want_gates, _ = ref.route(
                h.astype(jnp.float32), lp["router"].astype(jnp.float32),
                stated.experts_per_token)
        idx, gates, want, want_gates = (np.asarray(a) for a in (
            idx, gates, want, want_gates))
        same = (idx == want).all(-1)
        return float(same.mean()), float(
            np.abs(gates - want_gates)[same].max(initial=0.0))

    lines, ok = [], True
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        # (The weights are the stated model's whatever fault the program
        # reads them with.)
        params = jax.jit(lambda k: llama.init_params(stated, k))(
            jax.random.key(seed))
        host = jax.tree.map(np.asarray, params)
        if args.fault == "experts_fp8":
            params = dict(params, layers={
                k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                    if k in ("w1", "w2", "w3") else v)
                for k, v in params["layers"].items()})
        router_same, gate_error = router_probe(params, seed)
        cache, _ = pages.alloc(geom, device=device)
        owner = allocator_for(geom, False)
        seq = np.asarray(jax.random.randint(
            jax.random.key(seed + 1000), (max(lens) + K,), 0, 257))
        held = [owner.alloc(per_seq) for _ in lens]
        tables = np.zeros((B, per_seq), np.int32)
        for lane, blocks in enumerate(held):
            tables[lane, :len(blocks)] = blocks
        # Where each pool's page ids lie: as the owner names them, or (the
        # trash page apart) shuffled, one fixed bijection a pool a seed, so
        # that no two neighbours of a table are adjacent in the pool.
        place, place_w = (
            np.concatenate([[0], 1 + (np.random.default_rng(
                seed + i).permutation(n - 1) if args.shuffle_tables
                else np.arange(n - 1))]).astype(np.int32)
            for i, n in enumerate((geom.n_blocks, geom.window.n_blocks)))
        tables = place[tables]
        looked = [[] for _ in lens]        # (position, logits) a lane
        routes_of = [[] for _ in lens]     # [L, tokens, k] pieces a lane
        given_back = 0

        # 1. every lane's prompt, a window at a time.
        for lane, n in enumerate(lens):
            row = tables[lane:lane + 1]
            for lo in range(0, n, W):
                m = min(W, n - lo)
                bucket = _pow2(m, block)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :m] = seq[lo:lo + m]
                wt = np.zeros((1, per_seq), np.int32)
                owner.slide(held[lane], lo, lo + m, wt[0], True)
                at = state.at_slots(cache, [lane], place_w[wt])
                if lo == 0:
                    got, routes, cache = prefill(
                        params, toks, np.full((1,), m, np.int32), at, row)
                else:
                    got, routes, cache = window(
                        params, toks, np.full((1,), m, np.int32),
                        np.full((1,), lo, np.int32), at, row,
                        _pow2(lo // block))
                cache, *_ = state.take_counts(cache)
                looked[lane].append((lo + m - 1, np.asarray(got)))
                routes_of[lane].append(routed(routes, m))
        peak_program = (device.memory_stats() or {}).get("peak_bytes_in_use")

        # 2. decode, teacher-forced, both lanes at once, a step a call; the
        # owner slides every lane's window pages ahead of each.
        steps = []
        for k in range(K):
            positions = np.asarray([n + k for n in lens], np.int32)
            wt = np.zeros((B, per_seq), np.int32)
            for lane, t in enumerate(positions):
                before = held[lane].first
                owner.slide(held[lane], int(t), int(t) + 1, wt[lane])
                given_back += held[lane].first - before
            logits, routes, cache = decode(
                params, seq[positions], positions,
                state.at_slots(cache, np.arange(B), place_w[wt]), tables)
            cache, *_ = state.take_counts(cache)
            steps.append(np.asarray(logits))                    # [B, V]
            for lane in range(B):
                routes_of[lane].append(
                    None if own_routes
                    else np.asarray(routes)[:, lane])            # [L, 1, k]
        steps = np.stack(steps, 1)                               # [B, K, V]
        most_pages = max(len(t.window) for t in held)

        # 3. the reference, from a host copy of the (stated) weights, held to
        # the program's routes.
        del params, cache, at
        report, shortfalls, parted = {}, [], []

        def rms(a):
            return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))

        def judge(got, want):
            got = np.asarray(got, np.float32)
            diff = float(np.abs(got - want).max())
            top = float(np.abs(want).max())
            err = rms(got - want) / rms(want)
            return {"max_diff": diff, "max_ref": top, "rel": diff / top,
                    "rms_rel": err, "positions": int(want.shape[0]),
                    "argmax_same": float((got.argmax(-1)
                                          == want.argmax(-1)).mean()),
                    "ok": diff <= TIGHT * top and err <= TIGHT_RMS}

        edge = None
        for lane, n in enumerate(lens):
            forced = (None if routes_of[lane][0] is None else jnp.asarray(
                np.concatenate(routes_of[lane], axis=1)))
            hidden, _, short = ref.hidden(host, seq[:n + K], **sizes,
                                          routes=forced)
            short = np.asarray(short)
            shortfalls.append(float(short.max()))
            parted.append(float((short > 0).mean()))
            at = np.asarray([p for p, _ in looked[lane]])
            report[f"windows_{n}"] = judge(
                np.stack([g for _, g in looked[lane]]),
                np.asarray(ref.logits(host, hidden[at])))
            want = np.asarray(ref.logits(host, hidden[n:n + K]))
            report[f"decode_{n}"] = judge(steps[lane], want)
            if lane == B - 1:
                # The window's edge, on the short lane, over the steps whose
                # context is past the window: the reference one token
                # narrower and one wider, along the same history.
                past = np.arange(n, n + K) >= stated.kv_window
                here = rms(steps[lane][past] - want[past])
                edge = {"steps_past_the_window": int(past.sum()),
                        "at_stated_window": here / rms(want[past])}
                for name, size in (("narrower", -1), ("wider", 1)):
                    other, _, _ = ref.hidden(
                        host, seq[:n + K], **{
                            **sizes, "sliding_window_size":
                            sizes["sliding_window_size"] + size},
                        routes=forced)
                    there = rms(steps[lane][past] - np.asarray(
                        ref.logits(host, other[n:n + K]))[past])
                    edge[name] = there / rms(want[past])
                    edge[f"{name}_over_stated"] = there / here
                edge["limit"] = EDGE
                edge["ok"] = bool(past.any() and min(
                    edge["narrower_over_stated"],
                    edge["wider_over_stated"]) > EDGE)
        line = {"seed": seed, "fault": args.fault or None,
                "device": {"platform": device.platform,
                           "kind": device.device_kind},
                "model": mcfg.name, "n_layers": mcfg.n_layers,
                "layer_pattern": mcfg.layer_pattern,
                "window": mcfg.kv_window, "full_nope": mcfg.full_nope,
                "router_input": mcfg.router_input,
                "expert_activation": mcfg.expert_act,
                "window_attention": mcfg.swa_impl,
                "moe_form": {str(rows): bound.model_for(rows).moe_impl
                             for rows in (B, W)},
                "lane_tokens": lens, "prefill_window": W, "decode_steps": K,
                "tables": "shuffled" if args.shuffle_tables else "owner's",
                "window_pages": {"given_back_in_decode": given_back,
                                 "most_held_by_a_lane": most_pages,
                                 "pool": geom.window.n_blocks - 1},
                "pool_bytes": [geom.pool_bytes, geom.window.pool_bytes],
                "memory": {"peak_bytes_in_use_program": peak_program,
                           "bytes_limit": (device.memory_stats() or {}).get(
                               "bytes_limit")},
                "edge": edge,
                "routing": {"max_shortfall": max(shortfalls),
                            "choices_parted_share": float(np.mean(parted)),
                            "limit": SHORTFALL,
                            "router_same_as_f32": router_same,
                            "router_same_limit": ROUTER_SAME,
                            "gate_error_where_same": gate_error,
                            "gate_error_limit": GATE_ERROR,
                            "ok": (max(shortfalls) <= SHORTFALL
                                   and router_same >= ROUTER_SAME
                                   and gate_error <= GATE_ERROR)},
                "logits_limit": TIGHT, "logits_rms_limit": TIGHT_RMS,
                "stages": report,
                "seconds": round(time.monotonic() - t0, 1)}
        line["ok"] = bool(all(s["ok"] for s in report.values())
                          and line["routing"]["ok"] and edge["ok"])
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
