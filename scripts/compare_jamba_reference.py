"""The jamba family (models/hybrid.py's Mamba-1 and attention layers with a
dense FFN each) on the chip against its plain reference, at a benchmark
configuration's widths and a cell's sizes.

    python scripts/compare_jamba_reference.py \
        --config-file chipbench/configs/ai21-jamba2-3b.json --seeds 0,1,2

What is compared. One sequence of random byte-range token ids a seed. The
program side runs what `TpuEngine`'s step functions trace --
`models.hybrid.forward` / `prefill_with_prefix` / `decode_step` in the forms
`models.bind` gives this device (`state_update`, `state_scan` and
`attention_kernel` in each line), the page writes of `kvcache/pages.py` and
the slot state of `kvcache/state.py`, the engine's pools at `--max-batch` x
`--max-model-len` -- jitted here to hand back logits before the sampler.
`--max-batch` lanes, lane i in slot i, take turns over `--lengths`:

1. *prefill*: a prompt in windows of `--window` tokens: the first a plain
   prefill, every later one a continuation that starts from the slot's
   carried state and tail and reads the pages (the last one padded to its
   power-of-two bucket where it is no power of two: the padding rows must
   change nothing). Of the first lane of each length, logits at
   `--positions` positions of the first window and at the last position of
   every later one;
2. *decode*: `--decode-steps` teacher-forced steps of all lanes at once
   through the pool and the pages; logits of the first lane of each length at
   every step, and of every other lane its largest difference from that lane
   (the same tokens in another slot and other pages);
3. *state*: what the first lane of each length holds in its slot after the
   last step, against the reference's state (Frobenius norm of the
   difference over that of the reference): the worst Mamba layer, and the
   FIRST one by itself, which has one layer's rounding upstream of it;
4. *state precision*: the share of those slots' state values that bf16 cannot
   hold (an f32 value drawn at random needs more than 8 bits of mantissa 255
   times in 256). The reference cannot see this: bf16 activations upstream
   move the state by more than a state rounded to bf16 at every step does
   (PERF.md section 6, PR 34, has both readings for the other state family),
   so the precision the configuration states for the state is probed
   directly.

The reference (`chipbench/configs/reference_jamba.py`, float32 under
`highest`, the recurrence token by token, the head through the embedding
transposed) runs once for each distinct length, its logits computed for the
compared positions alone.

Each line of output is one seed. Exit code 1 if any seed passes a limit
below. `--fault` plants one of five faults, each of which has to FAIL:
`state_bf16` (the state pool's values rounded to bf16 after the prefill and
after every decode step), `one_decay` (the program computes with the mean of
`A` over a channel's state values: one decay a channel, Mamba-2's form),
`no_norms` (the reference leaves the RMSNorms on dt, B and C out),
`rope_on_attention` (the reference rotates q and k), `conv3` (the program's
convolution loses its oldest tap).

On the CPU (`--model tiny-jamba --lengths 40+9,37 --window 16 --max-model-len
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

# ---- limits, each between two readings (PERF.md section 6, PR 52; my chip
# runs, seeds 0-2, 64 lanes, prompts of 2,600 and 2,049 tokens in windows of
# 1,024, 1,024 decode steps) ---------------------------------------------------
# The residual stream is bf16 and 56 branches deep (28 layers of a mixer and
# an FFN), and a Mamba-1 state integrates its inputs' rounding over hundreds
# of tokens: the healthy program's state parts from the reference's by 0.006
# in the first Mamba layer and 0.09 in the last but one, growing layer by
# layer alike on every seed, and its logits by a twentieth. That is the
# precision the configuration states (bf16 weights and activations, f32
# state), read, and the limits stand between it and the nearest fault.
#
# max |diff| of a stage's logits over its max |ref|. Healthy 0.048-0.069 over
# every stage and seed; `rope_on_attention` reads 0.19 on the first window
# (short contexts, where the rotation changes most) and 0.070-0.084 elsewhere,
# `one_decay` 0.88-1.12, `no_norms` 1.12-1.22, `conv3` 1.31-1.40.
TIGHT = 0.12
# The same as a root mean square over a stage's logits, over the
# reference's: what a systematic difference moves and a few unlucky logits
# do not. Healthy 0.049-0.056; `rope_on_attention` 0.072-0.112 (two layers
# of 28 attend: the weakest fault), `state_bf16` 0.064 on the decode stages,
# `one_decay` 0.91-0.99.
RMS = 0.064
# max |diff| between two lanes that hold the same tokens, over max |ref|:
# the same arithmetic on other slots and other pages reads 0.0 exactly on
# every seed; a lane that read another's page or slot would read as a fault
# does (0.9 and more).
LANES = 0.005
# ||S - S_ref|| / ||S_ref|| of a slot's state after the last decode step. The
# worst Mamba layer: healthy 0.090-0.095, `state_bf16` 0.111-0.127,
# `rope_on_attention` 0.112-0.126, the others 1.3-2.8. The first Mamba layer
# alone (one block upstream of it): healthy 0.0053-0.0061, `state_bf16`
# 0.0104-0.0123, `one_decay` 0.41-0.43, `no_norms` 2.0, `conv3` 0.82.
STATE = 0.103
STATE_FIRST = 0.008
# Share of a slot's state values that bf16 cannot hold: an f32 state reads
# 0.99996, a state kept in bf16 reads 0.
STATE_BEYOND_BF16 = 0.9

FAULTS = ("state_bf16", "one_decay", "no_norms", "rope_on_attention", "conv3")


def _reference():
    path = os.path.join(REPO, "chipbench", "configs", "reference_jamba.py")
    spec = importlib.util.spec_from_file_location("reference_jamba", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="ai21-jamba2-3b")
    ap.add_argument("--config-file", default="")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-model-len", type=int, default=5120)
    ap.add_argument("--lengths", default="2600,2048+1",
                    help="prompt lengths; a+b is a prompt of a + b tokens "
                         "(the windows are cut from its whole length)")
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--decode-steps", type=int, default=1024)
    ap.add_argument("--positions", type=int, default=16)
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
    device = jax.devices()[0]
    cfg = EngineConfig(model=args.model, max_batch=args.max_batch,
                       max_model_len=args.max_model_len)
    # The forms an engine on this device binds (models/binding.py), without
    # the engine.
    bound = bind(mcfg, platform=device.platform,
                 interpret=cfg.pallas_interpret)
    served = bound.mcfg
    geom = pages.PageGeometry.for_engine(mcfg, cfg.max_batch,
                                         cfg.max_model_len)
    kernel = pages.use_kernel(geom.shape[-1], asked=None, interpret=False,
                              platform=device.platform, sharded=False)
    attend = functools.partial(pages.decode_attention, kernel=kernel)
    block, B, K, W = geom.block, args.max_batch, args.decode_steps, args.window
    ref = _reference()
    # One compile for the 26 Mamba layers of a length, not one a layer (the
    # file itself stays plain: eager jax.numpy).
    ref._mamba = jax.jit(ref._mamba, static_argnames=(
        "state", "dt_rank", "norm_eps", "norms"))
    attn_at = [i for i, c in enumerate(mcfg.layer_pattern) if c == "A"]
    period = (attn_at[1] - attn_at[0] if len(attn_at) > 1
              else mcfg.n_layers)
    sizes = dict(n_layers=mcfg.n_layers, attn_period=period,
                 attn_offset=attn_at[0], n_heads=mcfg.n_heads,
                 n_kv_heads=mcfg.n_kv_heads, head_dim=mcfg.head_dim,
                 ssm_state=mcfg.ssm_state, ssm_dt_rank=mcfg.ssm_dt_rank,
                 norm_eps=mcfg.norm_eps,
                 norms=args.fault != "no_norms",
                 rotary=args.fault == "rope_on_attention")

    def pow2(n, least=16):
        p = least
        while p < n:
            p *= 2
        return p

    kinds = [sum(int(x) for x in spec.split("+"))
             for spec in args.lengths.split(",")]
    lens = [kinds[lane % len(kinds)] for lane in range(B)]
    assert max(lens) + K <= args.max_model_len

    # ---- the program's steps, logits out ----
    @functools.partial(jax.jit, donate_argnums=(4,))
    def first_window(params, tokens, n, at, cache, row):
        logits, (fresh, _) = hybrid.forward(
            params, bound.model_for(tokens.size), tokens, want_kv=True,
            seq_len=n)
        cache, _ = pages.write_sequences(cache, None, fresh, None, row, n)
        return logits[0, at], cache

    @functools.lru_cache(maxsize=None)
    def next_window(prior_blocks):
        @functools.partial(jax.jit, donate_argnums=(4,))
        def step(params, tokens, n, written, cache, row):
            logits, cache, _ = hybrid.prefill_with_prefix(
                params, bound.model_for(tokens.size), tokens, n, written,
                cache, None, row, row[:, :prior_blocks])
            return logits[0], cache
        return step

    @functools.partial(jax.jit, donate_argnums=(3,))
    def decode(params, tokens, positions, cache, tables, firsts):
        logits, cache, _ = hybrid.decode_step(
            params, bound.model_for(tokens.size), tokens, positions, cache,
            None, tables, attention_fn=attend)
        # Every lane against the first lane of its length.
        apart = jnp.max(jnp.abs(logits - logits[firsts]))
        return logits[:len(kinds)], apart, cache

    @functools.partial(jax.jit, donate_argnums=(0,))
    def state_in_bf16(cache):
        # reduce_precision, not a cast there and back: the TPU compiler keeps
        # excess precision and drops such a pair (chip run, PR 32).
        return dataclasses.replace(cache, ssm=jax.lax.reduce_precision(
            cache.ssm, exponent_bits=8, mantissa_bits=7))

    def at_lanes(cache, slots):
        return state.at_slots(cache, np.asarray(slots, np.int32))

    def faulty(params):
        """The parameters the PROGRAM computes with under ``--fault``."""
        ssm1 = dict(params["ssm1"])
        if args.fault == "one_decay":
            ssm1["A_log"] = jnp.broadcast_to(
                jnp.log(jnp.mean(jnp.exp(ssm1["A_log"]), axis=1,
                                 keepdims=True)), ssm1["A_log"].shape)
        if args.fault == "conv3":
            ssm1["conv_w"] = ssm1["conv_w"].at[:, 0].set(0)
        return {**params, "ssm1": ssm1}

    lines, ok = [], True
    firsts = jnp.asarray([lane % len(kinds) for lane in range(B)], jnp.int32)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        params = jax.jit(lambda k: hybrid.init_params(mcfg, k))(
            jax.random.key(seed))
        served_params = faulty(params)
        cache, _ = pages.alloc(geom, device=device)
        seq = jax.random.randint(jax.random.key(seed + 1000),
                                 (max(lens) + K,), 0, 257)
        per_seq = geom.max_blocks_per_seq
        tables = jnp.asarray(np.stack(
            [1 + lane * per_seq + np.arange(per_seq) for lane in range(B)]
        ).astype(np.int32))

        # 1. prefill: every lane's windows; the first lane's of a kind kept.
        looked = {}        # stage -> (kind, positions, logits)
        for lane in range(B):
            n, kind = lens[lane], lane % len(kinds)
            keep = lane < len(kinds)
            row = tables[lane:lane + 1]
            a = min(n, W)
            toks = jnp.zeros((1, pow2(a)), jnp.int32).at[0, :a].set(seq[:a])
            at = np.unique(np.linspace(0, a - 1, args.positions).astype(int))
            got, cache = first_window(
                served_params, toks, jnp.full((1,), a, jnp.int32),
                jnp.asarray(at), at_lanes(cache, [lane]), row)
            cache = state.take_counts(cache)[0]
            if keep:
                looked[f"prefill_b{pow2(a)}_n{a}@{n}"] = (
                    kind, at, np.asarray(got))
            for start in range(W, n, W):
                b = min(n - start, W)
                wb, prior = pow2(b), pow2(-(-start // block), 1)
                toks = jnp.zeros((1, wb), jnp.int32).at[0, :b].set(
                    seq[start:start + b])
                got, cache = next_window(prior)(
                    served_params, toks, jnp.full((1,), b, jnp.int32),
                    jnp.full((1,), start, jnp.int32),
                    at_lanes(cache, [lane]), row)
                cache = state.take_counts(cache)[0]
                if keep:
                    looked[f"window_s{wb}_p{prior}_n{start}+{b}@{n}"] = (
                        kind, np.asarray([start + b - 1]),
                        np.asarray(got)[None])
        if args.fault == "state_bf16":
            cache = state_in_bf16(cache)

        # 2. decode, teacher-forced, all lanes at once.
        steps, apart = [], []
        for k in range(K):
            positions = jnp.asarray([n + k for n in lens], jnp.int32)
            logits, d, cache = decode(
                served_params, seq[positions], positions,
                at_lanes(cache, np.arange(B)), tables, firsts)
            cache = state.take_counts(cache)[0]
            if args.fault == "state_bf16":
                cache = state_in_bf16(cache)
            steps.append(logits)
            apart.append(d)
        steps = np.asarray(jnp.stack(steps, 1))           # [kinds, K, V]
        apart = float(jnp.max(jnp.stack(apart)))
        slot_state = np.asarray(cache.ssm[:, :len(kinds)])  # [Ls, kinds, N, C]
        as_bf16 = np.asarray(jnp.asarray(slot_state).astype(jnp.bfloat16)
                             .astype(jnp.float32))
        beyond_bf16 = float(np.mean(slot_state != as_bf16))

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

        # The reference, once a distinct length.
        report, state_parts, tops = {}, [], []
        for kind, n in enumerate(kinds):
            hidden, last = ref.hidden(params, seq[:n + K], **sizes,
                                      want_state=True)
            want = np.asarray(ref.logits(params, hidden[n:n + K]))
            tops.append(float(np.abs(want).max()))
            report[f"decode@{n}"] = judge(steps[kind], want)
            last = np.swapaxes(np.asarray(last), 1, 2)     # [Ls, N, C]
            state_parts.append([
                float(np.linalg.norm(slot_state[layer, kind] - last[layer])
                      / np.linalg.norm(last[layer]))
                for layer in range(last.shape[0])])
            for stage, (of, where, got) in looked.items():
                if of == kind:
                    report[stage] = judge(got, np.asarray(
                        ref.logits(params, hidden[np.asarray(where)])))
            del hidden
        report["lanes_apart"] = {"max_diff": apart, "rel": apart / min(tops),
                                 "ok": apart <= LANES * min(tops)}
        by_layer = np.max(np.asarray(state_parts), axis=0)   # over lengths
        report["state"] = {"rel": float(by_layer.max()),
                           "by_layer": by_layer.tolist(),
                           "ok": bool(by_layer.max() <= STATE)}
        report["state_first_layer"] = {"rel": float(by_layer[0]),
                                       "ok": bool(by_layer[0] <= STATE_FIRST)}
        report["state_precision"] = {
            "share_beyond_bf16": beyond_bf16,
            "ok": beyond_bf16 >= STATE_BEYOND_BF16}
        line = {"seed": seed, "fault": args.fault or None,
                "device": {"platform": device.platform,
                           "kind": device.device_kind},
                "model": mcfg.name, "n_layers": mcfg.n_layers,
                "lanes": B, "lane_tokens": sorted(set(lens)),
                "window": W, "decode_steps": K,
                "attention_kernel": bool(kernel),
                "state_update": served.ssm_impl,
                "state_scan": served.ssm_scan_impl,
                "pool_bytes": geom.pool_bytes,
                "state_pool_bytes": geom.state.pool_bytes,
                "memory": {k: v for k, v in (device.memory_stats() or {}).items()
                           if k in ("peak_bytes_in_use", "bytes_limit")},
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
        del params, served_params, cache
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
