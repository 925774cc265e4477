"""dots3-note-prev's block on the chip against its plain reference, at the
benchmark configuration's widths and the `longctx-wide` cell's sizes.

    python scripts/compare_dots3_reference.py \
        --config-file chipbench/configs/dots3-note-prev-cut.json --seeds 0,1

scripts/compare_dsa_reference.py's comparison for a model of models/mla.py
whose layers are of two kinds. Two lanes, one prompt a lane (`--lengths`: one
past 4,096 tokens, one that crosses index_topk inside its third window), each
written as the engine writes it: a first window of `--window` tokens
(`models.mla.forward`), then windows that continue it through BOTH kinds of
pool (`prefill_with_prefix`: the full layers read their rows and keys through
the block table and select; the window layers read the pages of their own pool
that end where the window starts), then `--decode-steps` teacher-forced decode
steps of both lanes at once (`decode_step`: the indexer, the selection and the
Pallas walk over the selected rows in the full layers, the walk from the
window's first page in the window layers). The window layers' pages come from
the engine's own owner (`engine/blocks.WindowedAllocator.slide`, step by
step): with 600 steps and more a lane gives back ~38 pages while it decodes,
takes as many again, and its window slides across both. The MoE form and the
kernels' forms are `models.bind`'s for this device.

The reference (`chipbench/configs/reference_dots3_note.py`, float32 under
`highest`, no cache) runs once a lane, **held to the outputs the program's
router chose and to the rows the program's full layers selected**
(compare_dsa_reference.py's reason), so the choices are judged for what they
are and the logits along the program's own history:

- *logits*: at every window's last token and at every decode step, max |diff|
  over max |ref| of the stage, and the error's root mean square over the
  reference's;
- *the window's edge*: the short lane's decode logits are closer (root mean
  square) to the reference at `sliding_window_size` than to the reference one
  token narrower and to the one one token wider, by the margins below: a
  window off by one row moves the logits far less than bf16 rounding does, but
  it moves them in ITS direction, and over 600 steps x 19,008 logits the
  rounding is orthogonal to it;
- *selection*: a full layer, the share of the reference's OWN S_t that the
  program also chose; the least over the layers, and the first layer's;
- *routing*: how far under what the reference's own choice asked a forced
  choice lies; and, probed alone, the share of 4,096 random tokens for which
  the program's own `route` chooses what an f32 router chooses from the same
  inputs (the hidden states' rounding hides the router's from every statistic
  above: this one sees the router's alone).

Each line of output is one seed. Exit code 1 if any seed passes a limit below.
`--fault` plants one of four faults in the PROGRAM's side, and each has to
fail: `router_bf16` (the router's scores rounded to bf16), `no_gate` (both
gates dropped), `window_512` (the window layers and their owner one token
short), `stale_page` (at every decode step a lane's first window page is read
where another request is writing: what a page given back too early shows).

On the CPU (`--model tiny-swa --lengths 150,70 --window 32 --max-model-len 256
--dtype float32 --decode-steps 40`) it rehearses the control flow with the
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

# ---- limits, each between two readings (PERF.md section 6, PR 45) -----------
# my chip runs, PR 45: seeds 0-2 healthy, seed 0 (seed 2 for the router) with
# each fault planted (--fault); every fault's run exits 1, every healthy one 0.
# max |diff| of a stage's logits over its max |ref| (bf16 products through
# five layers, the reference held to the program's routes and rows):
# 0.0167-0.0219 healthy; 0.89-1.04 without the gates, 0.0259-0.0287 at the
# decode stages with a stale page (the gross faults' limit; the fine ones are
# the next two's).
TIGHT = 0.035
# The root mean square of a stage's error over its reference's: 0.0178-0.0191
# healthy (rounding, the same at every stage and seed); 0.0238-0.0242 at the
# decode stages with a stale page read a step, 0.94-0.97 without the gates.
TIGHT_RMS = 0.0215
# How much closer (root mean square) the short lane's decode logits lie to the
# reference at the stated window than to either neighbour, as a ratio: 1.0153-
# 1.0169 both ways healthy; 0.9847 to the narrower one with a window of 512 in
# the program (1.0153 to the wider), 0.9907 / 1.0097 with a stale page.
EDGE = 1.005
# The least share, over the full layers, of the reference's own selection that
# the program also holds, and the first layer's: 0.99596-0.99597 and 0.9988
# healthy (two full layers; deepseek's five read 0.9805 and 0.9972: its limits
# are kept, an 8-bit key pool reads 0.9756 / 0.9856 there); 0.807 without the
# gates.
SHARED = 0.978
SHARED_FIRST = 0.992
# How far under what the reference's own choice asked a choice of the program
# may lie, in the scores' unit: 0.0132-0.0136 healthy (10.6% of positions part
# somewhere); 0.77 without the gates. The hidden states' own rounding sets it,
# so it cannot see the router's: the probe below does.
SHORTFALL = 0.04
# The router's own precision, probed alone on the chip: the share of 4,096
# random tokens for which the program's `route` (the function its expert
# layers call) chooses the 8 experts an f32 `highest` router chooses from the
# same bf16 inputs, and the largest error of a gate where they agree.
# 1.0 and 0.0 healthy (seeds 0-2: f32 accumulation of the same products);
# 0.9111 and 1.45e-4 with the router's scores rounded to bf16 (seed 2).
ROUTER_SAME = 0.97
GATE_ERROR = 5e-5


def _reference():
    path = os.path.join(REPO, "chipbench", "configs",
                        "reference_dots3_note.py")
    spec = importlib.util.spec_from_file_location("reference_dots3_note", path)
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
    ap.add_argument("--model", default="dots3-note-prev-cut")
    ap.add_argument("--config-file", default="")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--max-model-len", type=int, default=18432)
    ap.add_argument("--lengths", default="4608,2304")
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--decode-steps", type=int, default=608)
    ap.add_argument("--q-block", type=int, default=256)
    ap.add_argument("--dtype", default="")
    ap.add_argument("--fault", default="", choices=(
        "", "router_bf16", "no_gate", "window_512", "stale_page"))
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
    stated = configs.get_config(args.model)      # what the reference computes
    served = stated                               # what the program computes
    if args.fault == "no_gate":
        served = dataclasses.replace(
            served, attn_gate=False, window_attn=dataclasses.replace(
                served.window_attn, gate=False))
    if args.fault == "window_512":
        served = dataclasses.replace(
            served, window_attn=dataclasses.replace(
                served.window_attn, window=served.window_attn.window - 1))
    if args.fault == "router_bf16":
        plain_route = mla.route

        def rounded_route(cfg, lp, h):
            # The product's result in the operands' dtype, as a router
            # without preferred_element_type=f32 would leave it.
            wide = jnp.dot(h, lp["router"]).astype(jnp.bfloat16)
            return plain_route(cfg, {**lp, "router": jnp.eye(
                wide.shape[-1], dtype=jnp.bfloat16)}, wide)

        mla.route = rounded_route
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
    attend = functools.partial(pages.latent_decode_attention, kernel=True,
                               interpret=not on_tpu)
    geom = pages.PageGeometry.for_engine(mcfg, B, cfg.max_model_len)
    block, per_seq = geom.block, geom.max_blocks_per_seq
    assert W % block == 0 and max(lens) + K <= args.max_model_len
    ref = _reference()

    def widths(kind):
        return dict(n_heads=kind.n_heads, kv_lora_rank=kind.kv_lora_rank,
                    qk_nope_head_dim=kind.qk_nope_head_dim,
                    qk_rope_head_dim=kind.qk_rope_head_dim,
                    rope_theta=kind.rope_theta)

    names = {"*": "full_attention", "W": "sliding_attention"}
    sizes = dict(
        layer_types=[names[ch] for ch in stated.layer_pattern],
        full=widths(stated), window=widths(stated.window_attn),
        sliding_window_size=stated.window_attn.window,
        norm_eps=stated.norm_eps, rescale=stated.mla_scale_q_lora,
        gate=stated.attn_gate, window_gate=stated.window_attn.gate,
        experts_per_token=stated.experts_per_token,
        routed_scaling_factor=stated.routed_scaling_factor,
        index_n_heads=stated.index_n_heads,
        index_head_dim=stated.index_head_dim, index_topk=stated.index_topk,
        first_expert=stated.experts_first, q_block=args.q_block)

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

    def router_probe(params, seed, tokens=4096):
        """(share of tokens whose chosen experts are the f32 router's, the
        largest gate error among those): the program's own `route` on the
        first expert layer's router against the same arithmetic in float32
        under `highest`, on unit-variance inputs in the model's dtype."""
        lp = {k: params["layers"][k][0] for k in ("router", "router_bias")}
        h = jax.random.normal(jax.random.key(seed + 2000),
                              (tokens, stated.d_model), jnp.float32
                              ).astype(lp["router"].dtype)
        idx, gates = jax.jit(lambda lp, h: mla.route(mcfg, lp, h))(lp, h)
        with jax.default_matmul_precision("highest"):
            scores = jax.nn.sigmoid(h.astype(jnp.float32)
                                    @ lp["router"].astype(jnp.float32))
            _, want = jax.lax.top_k(scores + lp["router_bias"],
                                    stated.experts_per_token)
            chosen = jnp.take_along_axis(scores, want, axis=-1)
            want_gates = (chosen / chosen.sum(-1, keepdims=True)
                          * stated.routed_scaling_factor)
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
        params = jax.jit(lambda k: mla.init_params(stated, k))(
            jax.random.key(seed))
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
        routes_of = [[] for _ in lens]     # [Le, tokens, k] pieces a lane
        picked_of = [np.zeros((geom.n_layers, n + K, n + K), bool)
                     for n in lens]
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
                    got, routes, picked, cache = prefill(
                        params, toks, np.full((1,), m, np.int32), at, row)
                    prior = 0
                else:
                    prior = _pow2(lo // block)
                    got, routes, picked, cache = window(
                        params, toks, np.full((1,), m, np.int32),
                        np.full((1,), lo, np.int32), at, row, prior)
                cache, *_ = state.take_counts(cache)
                looked[lane].append((lo + m - 1, np.asarray(got)))
                routes_of[lane].append(np.asarray(routes)[:, :m])
                picked = np.asarray(picked)  # [Lf, bucket, prior x blk + bucket]
                T = prior * block
                mine = picked_of[lane]
                mine[:, lo:lo + m, :lo] = picked[:, :m, :lo]
                mine[:, lo:lo + m, lo:lo + m] = picked[:, :m, T:T + m]
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
            if args.fault == "stale_page":
                reach = mcfg.window_attn.window - 1
                for lane, t in enumerate(positions):
                    other = held[(lane + 1) % B]
                    wt[lane, max(int(t) - reach, 0) // block] = \
                        other.window[-1]
            logits, routes, picked, cache = decode(
                params, seq[positions], positions,
                state.at_slots(cache, np.arange(B), place_w[wt]), tables)
            cache, *_ = state.take_counts(cache)
            steps.append(np.asarray(logits))                    # [B, V]
            routes, picked = np.asarray(routes), np.asarray(picked)
            for lane, t in enumerate(positions):
                routes_of[lane].append(routes[:, lane:lane + 1])
                picked_of[lane][:, t, :t] = picked[:, lane, :t]
                picked_of[lane][:, t, t] = picked[:, lane, -1]
        steps = np.stack(steps, 1)                               # [B, K, V]
        most_pages = max(len(t.window) for t in held)

        # 3. the reference, from a host copy of the weights, held to the
        # program's routes and rows.
        host = jax.tree.map(np.asarray, params)
        del params, cache, at
        report, shared_by_layer, shortfalls, parted = {}, [], [], []

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
            forced = jnp.asarray(np.concatenate(routes_of[lane], axis=1))
            mine = picked_of[lane]
            held_to = dict(routes=forced,
                           picked=lambda layer, lo, hi: mine[layer, lo:hi])
            hidden, short, shared = ref.hidden(host, seq[:n + K], **sizes,
                                               **held_to)
            short = np.asarray(short)
            shortfalls.append(float(short.max()))
            parted.append(float((short > 0).mean()))
            shared_by_layer.append([round(s, 5) for s in shared])
            at = np.asarray([p for p, _ in looked[lane]])
            report[f"windows_{n}"] = judge(
                np.stack([g for _, g in looked[lane]]),
                np.asarray(ref.logits(host, hidden[at])))
            want = np.asarray(ref.logits(host, hidden[n:n + K]))
            report[f"decode_{n}"] = judge(steps[lane], want)
            report[f"windows_{n}"]["queries_that_select"] = int(
                (np.arange(n + K) >= mcfg.index_topk).sum())
            if lane == B - 1:
                # The window's edge, on the short lane: the reference one
                # token narrower and one wider, along the same history.
                here = rms(steps[lane] - want)
                edge = {"at_stated_window": here / rms(want)}
                for name, size in (("narrower", -1), ("wider", 1)):
                    other, _, _ = ref.hidden(
                        host, seq[:n + K], **{
                            **sizes, "sliding_window_size":
                            sizes["sliding_window_size"] + size}, **held_to)
                    there = rms(steps[lane] - np.asarray(
                        ref.logits(host, other[n:n + K])))
                    edge[name] = there / rms(want)
                    edge[f"{name}_over_stated"] = there / here
                edge["limit"] = EDGE
                edge["ok"] = bool(min(edge["narrower_over_stated"],
                                      edge["wider_over_stated"]) > EDGE)
        least_shared = min(min(s) for s in shared_by_layer)
        first_shared = min(s[0] for s in shared_by_layer)
        line = {"seed": seed, "fault": args.fault or None,
                "device": {"platform": device.platform,
                           "kind": device.device_kind},
                "model": mcfg.name, "n_layers": mcfg.n_layers,
                "layer_pattern": mcfg.layer_pattern,
                "window": mcfg.window_attn.window,
                "index_topk": mcfg.index_topk, "index_scores": mcfg.index_impl,
                "window_attention": mcfg.swa_impl,
                "held_experts": list(mcfg.held_experts),
                "lane_tokens": lens, "prefill_window": W, "decode_steps": K,
                "tables": "shuffled" if args.shuffle_tables else "owner's",
                "window_pages": {"given_back_in_decode": given_back,
                                 "most_held_by_a_lane": most_pages,
                                 "pool": geom.window.n_blocks - 1},
                "pool_bytes": [geom.pool_bytes, geom.index_pool_bytes,
                               geom.window.pool_bytes],
                "memory": {"peak_bytes_in_use_program": peak_program,
                           "bytes_limit": (device.memory_stats() or {}).get(
                               "bytes_limit")},
                "edge": edge,
                "selection": {"shared_by_lane_and_layer": shared_by_layer,
                              "least": least_shared, "limit": SHARED,
                              "first_layer": first_shared,
                              "first_layer_limit": SHARED_FIRST,
                              "ok": (least_shared >= SHARED
                                     and first_shared >= SHARED_FIRST)},
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
                          and line["routing"]["ok"]
                          and line["selection"]["ok"] and edge["ok"])
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
