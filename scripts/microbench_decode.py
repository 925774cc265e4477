"""Decode-step component microbenchmark on the real chip.

Times the pieces of the fused decode step in isolation — forward (layers +
lm head) without KV writes, the paged-attention kernel, the current-token KV
scatter, and the sampler — at several points (lanes x context tokens a lane),
so a regression in one component is visible without reading a profiler trace.
Prints one JSON line per (component, point). The kernel's line also gives ms
a call (one layer), us a page fetched, and the share of the chip's peak
bandwidth that the bytes the call needs (chipbench/kernels.py) come to.
Everything runs in this one process (one process per chip). With ``--tables``
the kernel's line is printed for block tables whose groups of RUN_PAGES
entries name adjacent pages never (``shuffled``), as often as the allocator
leaves them under a closed loop's churn (``churn``) and always (``runs``, the
default), and with ``--kv-window N`` the window layers' walk
(``swa_paged_decode_attention``) is timed beside it over window tables of the
same kinds, the churned ones as engine/blocks.WindowedAllocator leaves them
(``window_churn``). ``--config-file`` takes the shapes from a benchmark
configuration and ``--attn-only`` leaves out the components that need the
model's weights: SmallThinker's two walks are
``--config-file chipbench/configs/smallthinker-21b-a3b-cut.json --attn-only
--points 32x8100 --max-model-len 16384 --tables shuffled,churn,runs
--kv-window 4096``.

``--moe`` times one MoE FFN layer at prefill shapes instead (Mixtral's widths,
T tokens of one prompt): the dense-over-experts einsums against the grouped
form the engine serves above the ridge (ops/pallas_moe.py) and, with
``--moe-candidates``, against the grouped matmuls jax ships (megablox ``gmm``,
``lax.ragged_dot``) behind the same sort. FLOPs and bytes are reckoned here
from the shapes: useful work is T*k rows, work done counts the padded rows
too. Then it checks the mathematics (``--moe-check-seeds``): the layer's
output, grouped and dense against a plain float32 FFN, and the last token's
logits through the four-layer model, grouped against dense.

``--moe-glue`` books one grouped expert layer's device time op by op (the
XLA side around the two kernels: names, microseconds, XLA's reckoned bytes;
``--moe-interpret --moe-glue-shapes tiny --moe-glue-tokens 64
--moe-glue-iters 1`` rehearses it here, without a table).

``--moe-decode`` times the held experts of one expert layer at DECODE shapes
instead (a chip that holds a range of the experts its router scores, 2-128
rows, the widths of the benchmark's four such configurations): the dense
einsums over every held expert against the form that reads the chosen
experts alone (ops/pallas_moe.chosen_experts; ``--moe-candidates``: and a
loop of ``lax.cond`` over the held experts in plain XLA), ms and the GB/s of
the weights each has to read; it is what CHOSEN_MAX_ROWS_PER_EXPERT was set
from, and its last lines run the two routings that would halt a chip on a
block index of -1 (no held expert chosen; a lane whose choices do not count).

``--ssm`` times the state-space layers' decode kernel alone instead (one
step of the recurrence on the state pool in place, ops/pallas_ssm.py) at a
model's widths and the cell's lanes, every state layer in one program on a
donated pool, against the gathered form the CPU keeps (gather by slot, the
plain update, scatter back): ms a call (one layer), the GB/s and the share of
the chip's peak that the bytes the call needs (chipbench/kernels_ssm.py) come
to, and how far the kernel's new state and y lie from the plain form's.

``--latent`` times the latent family's paged decode walks alone instead
(ops/pallas_latent_attention.py; for a block that selects rows, the two
decode kernels of ops/pallas_dsa.py as well) at a benchmark configuration's
widths and a point (lanes x context), over block tables whose groups of R
entries name adjacent pages never (a shuffled pool), as often as the
allocator leaves them under the cell's churn (``churned_tables``), and always
(run share 0 / the churn's / 1), for R of ``--latent-groups``: ms a call, ns
a page, the GB/s of the bytes the call needs, the least time by
chipbench/kernels_dsa.py / kernels_mla.py, and how far the kernel lies from
its plain form on that table. It is what RUN_PAGES was chosen from.

``--window`` times the latent family's expanded attention alone instead
(models/mla.expanded_attention: a 1,024-token window's queries against a
prior table's bucket of cached rows and its own, keys and values carried out
a head) at a benchmark configuration's widths over prior buckets of
``--window-priors`` blocks, in both forms: the scores whole in memory (the
CPU's form) and a tile at a time in VMEM (ops/pallas_dsa.py, the form a TPU
engine binds): ms a call (one layer), the bytes of scores the form puts into
memory, and how far the tiled form lies from the whole one.

``--kv-prefill`` times ONE continuation window's attention alone of a K/V
model with window and full layers (models/llama.py ``kv_window``; the
smallthinker configuration's widths and pool sizes): 1,024 queries against a
prior table of ``--kv-prefill-priors`` blocks filled to ``--kv-prefill-live``
of the bucket and the window's own rows, a full layer and a window layer, in
both forms: the rows gathered whole and banded in XLA (the scan over the
kind's layers closes over the stacked pools, as the step program's does, so
the compiler's once-a-program re-layout of the V pool is in the time) and the
tiled kernel that walks the pages (ops/pallas_paged_attention.
kv_window_prefill_attention): ms a layer, the share of the MXU's peak the
rows a query sees make of it, and how far the kernel lies from the plain form.

Usage: python scripts/microbench_decode.py [--model qwen3-4b]
           [--points 16x1000,16x300,8x300] [--tables shuffled,churn,runs]
           [--kv-window 4096] [--config-file FILE] [--attn-only]
       python scripts/microbench_decode.py --moe [--moe-tokens 256,512,1024]
           [--moe-candidates] [--moe-check-seeds 0,1]
       python scripts/microbench_decode.py --moe-glue
           [--moe-glue-shapes lfm2,kimi,longcat,mixtral]
       python scripts/microbench_decode.py --moe-decode [--moe-candidates]
           [--moe-decode-configs longcat-flash-omni-cut,...]
           [--moe-decode-rows 2,8,16,32,64,128]
       python scripts/microbench_decode.py --ssm [--ssm-lanes 64,16,2]
           [--ssm-head-blocks 128,32]
       python scripts/microbench_decode.py --ssm1 [--ssm-lanes 64,8]
           [--ssm1-channel-blocks ,1280] [--ssm1-rows 1024,128]
           [--ssm1-time-blocks 128,256]
       python scripts/microbench_decode.py --latent
           [--latent-config deepseek-v3.2-exp-cut] [--latent-points 32x9700]
           [--latent-groups 4,8,16] [--latent-tables shuffled,churn,runs]
       python scripts/microbench_decode.py --window
           [--window-config kimi-vl-a3b-cut] [--window-priors 0,64,128,256,512]
           [--window-live 1.0]
       python scripts/microbench_decode.py --kv-prefill
           [--kv-prefill-priors 64,128,256,512,1024] [--kv-prefill-live 0.75]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, *args, iters=20):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def _chipbench_kernels():
    """chipbench/kernels.py, the benchmark's yardstick: the chip's peaks and
    the bytes a call of the attention kernel needs."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench"))
    import kernels

    return kernels


def moe_main(args):
    """One MoE FFN layer at prefill shapes: time, shares of the peaks, and
    the check of the mathematics."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.models import bind, llama
    from llm_d_inference_scheduler_tpu.models.configs import get_config
    from llm_d_inference_scheduler_tpu.ops import pallas_moe

    mcfg = dataclasses.replace(get_config(args.moe_model), n_layers=1,
                               vocab_size=256)
    E, k, D, F = (mcfg.n_experts, mcfg.experts_per_token, mcfg.d_model,
                  mcfg.d_ff)
    tm = pallas_moe.ROW_TILE
    weight_bytes = 3 * E * D * F * 2
    # Interpreted on the CPU the times mean nothing: no share of a peak then.
    peak = (None if args.moe_interpret else
            _chipbench_kernels().peaks(jax.devices()[0].device_kind))
    init = jax.jit(lambda key, c: llama.init_params(c, key), static_argnums=1)
    lp = jax.tree.map(lambda a: a[0], init(jax.random.key(0), mcfg)["layers"])

    def line(**kv):
        print(json.dumps(kv), flush=True)

    def routed(fn):
        """An FFN that sorts (token, expert) rows by expert and hands the
        three grouped matmuls to ``fn(lhs, rhs, group_sizes)``."""
        def ffn(lp, x):
            T = x.shape[1]
            xt = x.reshape(T, D)
            logits = (xt @ lp["router"]).astype(jnp.float32)
            top, idx = jax.lax.top_k(logits, k)
            gates = jax.nn.softmax(top, axis=-1).astype(x.dtype)
            flat = idx.reshape(-1)
            order = jnp.argsort(flat, stable=True)
            sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
            xs = xt[order // k]
            h = (jax.nn.silu(fn(xs, lp["w1"], sizes))
                 * fn(xs, lp["w3"], sizes)).astype(x.dtype)
            out = fn(h, lp["w2"], sizes).astype(x.dtype)
            back = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
            return (out[back].reshape(T, k, D) * gates[..., None]).sum(1)[None]
        return ffn

    forms = {
        "dense": lambda lp, x: llama._moe_ffn(mcfg, lp, x),
        "grouped": lambda lp, x: pallas_moe.moe_ffn_grouped(
            lp, x, E, k, interpret=args.moe_interpret),
    }
    if args.moe_candidates:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        for tiling in ((256, 1024, 1024), (512, 1024, 1024)):
            forms["megablox_gmm_%dx%dx%d" % tiling] = routed(
                functools.partial(gmm, preferred_element_type=jnp.bfloat16,
                                  tiling=tiling,
                                  interpret=args.moe_interpret))
        forms["lax_ragged_dot"] = routed(functools.partial(
            jax.lax.ragged_dot, preferred_element_type=jnp.bfloat16))
        for name, tiles in (("grouped_up4096x512_down2048x1024",
                             ((4096, 512), (2048, 1024))),
                            ("grouped_up2048x1024_down1024x2048",
                             ((2048, 1024), (1024, 2048)))):
            forms[name] = functools.partial(
                lambda lp, x, tiles: pallas_moe.moe_ffn_grouped(
                    lp, x, E, k, tiles_up=tiles[0], tiles_down=tiles[1],
                    interpret=args.moe_interpret),
                tiles=tiles)

    for T in [int(t) for t in args.moe_tokens.split(",")]:
        x = jax.random.normal(jax.random.key(T), (1, T, D), jnp.bfloat16)
        # Rows the grouped layout works on: every expert's group padded to tm.
        idx = jax.lax.top_k((x[0] @ lp["router"]).astype(jnp.float32), k)[1]
        counts = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
        live_rows = int((-(-counts // tm) * tm).sum())
        for name, fn in forms.items():
            try:
                ms = timeit(jax.jit(fn), lp, x, iters=10)
            except Exception as e:  # a candidate the compiler refuses
                line(component="moe_ffn", form=name, T=T,
                     error=f"{type(e).__name__}: {str(e)[:300]}")
                continue
            rows = (T * E if name == "dense" else
                    live_rows if name.startswith("grouped") else T * k)
            share = lambda need, of: round(
                100 * need / (ms * 1e-3) / peak[of], 2)
            line(component="moe_ffn", form=name, T=T, ms=round(ms, 3),
                 rows_useful=T * k, rows_done=rows,
                 expert_counts=counts.tolist(),
                 **({} if peak is None else dict(
                     useful_flops_share_pct=share(T * k * 6 * D * F,
                                                  "flops_per_s"),
                     done_flops_share_pct=share(rows * 6 * D * F,
                                                "flops_per_s"),
                     weight_bytes_share_pct=share(weight_bytes,
                                                  "bytes_per_s"))))

    # The mathematics: one layer against the plain float32 FFN (the lines of
    # chipbench/configs/reference_llama_family.py's forward, an expert at a
    # time), then the last token's logits through four layers.
    def reference_ffn(lp, x):
        with jax.default_matmul_precision("highest"):
            h = x[0].astype(jnp.float32)
            top, idx = jax.lax.top_k(h @ lp["router"].astype(jnp.float32), k)
            gate = jax.nn.softmax(top, axis=-1)
            y = jnp.zeros_like(h)
            for e in range(E):
                w1, w3, w2 = (lp[n][e].astype(jnp.float32)
                              for n in ("w1", "w3", "w2"))
                out = (jax.nn.silu(h @ w1) * (h @ w3)) @ w2
                y = y + out * jnp.sum(jnp.where(idx == e, gate, 0.0),
                                      axis=-1)[:, None]
            return y[None], jnp.sort(idx, axis=-1)

    def program_choice(lp, x):  # the experts a bf16-rounded logit picks
        return jnp.sort(jax.lax.top_k(
            (x[0] @ lp["router"]).astype(jnp.float32), k)[1], axis=-1)

    seeds = [int(s) for s in args.moe_check_seeds.split(",") if s]

    def diff(a, b, rows=None):
        d = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
        return float((d if rows is None else d[0][rows]).max())

    for seed in seeds:
        for T in (512, 1024):
            x = jax.random.normal(jax.random.key(seed), (1, T, D),
                                  jnp.bfloat16)
            ref, ref_choice = jax.jit(reference_ffn)(lp, x)
            dense, grouped = (jax.jit(forms[n])(lp, x)
                              for n in ("dense", "grouped"))
            # A token whose second and third logits lie within bf16 rounding
            # may go to another expert in the program than in the reference
            # (float32 logits there, a bf16 product here): the comparison
            # with the reference leaves such tokens out and counts them. The
            # two forms of the program are compared over every token — on a
            # TPU both route on the product's f32 accumulator.
            same = np.asarray((ref_choice == program_choice(lp, x)).all(-1))
            line(check="moe_layer", seed=seed, T=T,
                 max_abs_ref=float(jnp.abs(ref).max()),
                 tokens_routed_as_the_reference=int(same.sum()),
                 grouped_vs_ref=diff(grouped, ref, same),
                 dense_vs_ref=diff(dense, ref, same),
                 grouped_vs_dense=diff(grouped, dense))
    del lp
    model = dataclasses.replace(get_config(args.moe_model), n_layers=4)
    # Both forms of the model as an engine here binds them.
    bound = bind(model, platform=jax.devices()[0].platform,
                 interpret=args.moe_interpret)
    for seed in seeds:
        params = init(jax.random.key(seed), model)
        for T in (512, 1024):
            tokens = jax.random.randint(jax.random.key(seed + T), (1, T), 0,
                                        model.vocab_size)
            last = {}
            for impl, cfg in (("dense", bound.mcfg),
                              ("grouped", bound.grouped)):
                last[impl] = jax.jit(
                    lambda p, t, cfg=cfg: llama.forward(p, cfg, t)[0][0, -1])(
                        params, tokens)
            line(check="moe_last_token_logits", seed=seed, T=T, layers=4,
                 max_abs_dense=float(jnp.abs(last["dense"]).max()),
                 grouped_vs_dense=diff(last["grouped"], last["dense"]),
                 same_argmax=bool(last["grouped"].argmax()
                                  == last["dense"].argmax()))
        del params


def moe_glue_main(args):
    """The grouped experts' XLA side, op by op: one expert layer of 1,024
    rows at the widths of ``--moe-glue-shapes`` through
    ops/pallas_moe.grouped_experts under the profiler, every executed op
    booked by its name (microseconds a layer, XLA's reckoned bytes a layer,
    whether it stands in the ``ffn.experts.glue`` scope), then the sums: the
    glue, the two kernels, the whole layer. Run it in the parent's checkout
    too (copy this file there): the function's name and arguments are the
    parent's."""
    import glob
    import tempfile

    import jax
    import jax.numpy as jnp

    from llm_d_inference_scheduler_tpu.ops import pallas_moe

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench"))
    import trace_scopes

    T, iters = args.moe_glue_tokens, args.moe_glue_iters
    for name in args.moe_glue_shapes.split(","):
        # Experts held, choices a token, widths, the router's outputs a
        # choice is drawn from (more than held: a held range of them).
        E, k, D, F, routed_over = _GLUE_SHAPES[name]
        keys = jax.random.split(jax.random.key(args.seed), 6)
        init = lambda key, shape: jax.random.normal(
            key, shape, jnp.bfloat16) * shape[-2] ** -0.5
        lp = {"w1": init(keys[0], (E, D, F)), "w3": init(keys[1], (E, D, F)),
              "w2": init(keys[2], (E, F, D))}
        x = jax.random.normal(keys[3], (T, D), jnp.bfloat16)
        top, idx = jax.lax.top_k(
            jax.random.normal(keys[4], (T, routed_over), jnp.float32), k)
        gates = jax.nn.softmax(top, axis=-1)
        held = routed_over != E
        first = routed_over // 2 if held else None
        live = int(((idx >= (first or 0)) & (idx < (first or 0) + E)).sum())
        fn = jax.jit(lambda lp, x, idx, gates: pallas_moe.grouped_experts(
            lp, x, idx, gates, E, first=first,
            interpret=args.moe_interpret))
        ms = timeit(fn, lp, x, idx, gates, iters=iters)
        out = dict(component="moe_glue", shape=name, T=T, E=E, k=k, D=D, F=F,
                   routed_over=routed_over, live_rows=live,
                   buffer_rows=T * k + E * pallas_moe.ROW_TILE,
                   wall_ms=round(ms, 4))
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                for _ in range(iters):
                    y = fn(lp, x, idx, gates)
                jax.block_until_ready(y)
            paths = sorted(glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
            planes = [p for p in trace_scopes.read_planes(paths[-1])
                      if p["ops"]] if paths else []
        if not planes:      # the CPU: no device plane, nothing to book
            print(json.dumps(dict(out, ops=None)), flush=True)
            continue
        plane = planes[0]
        ops = {}
        for mid, _, duration in plane["ops"]:
            md = plane["op_metadata"][str(mid)]
            if trace_scopes._is_control_flow(md.get("name", "")):
                continue
            op, _, text = md.get("name", "").partition(" = ")
            op = op.lstrip("%")
            kind = ("kernel" if "moe_grouped" in op else
                    "glue" if trace_scopes.scope_of(md.get("tf_op") or "")
                    == "ffn.experts.glue" else "other")
            row = ops.setdefault(op, [kind, 0, 0.0, 0, text.split("{")[0]])
            row[1] += 1
            row[2] += duration / 1e6                # ps -> us
            row[3] += md.get("bytes_accessed") or 0
        sums = {kind: [0, 0.0, 0] for kind in ("glue", "kernel", "other")}
        for op, (kind, calls, us, nbytes, result) in sorted(
                ops.items(), key=lambda kv: -kv[1][2]):
            print(json.dumps(dict(
                component="moe_glue_op", shape=name, op=op, kind=kind,
                result=result,
                calls_a_layer=round(calls / iters, 2),
                us_a_layer=round(us / iters, 2),
                xla_mb_a_layer=round(nbytes / iters / 1e6, 2))), flush=True)
            for i, v in enumerate((calls, us, nbytes)):
                sums[kind][i] += v / iters
        print(json.dumps(dict(
            out,
            glue_ops=round(sums["glue"][0], 1),
            glue_us=round(sums["glue"][1], 1),
            glue_xla_mb=round(sums["glue"][2] / 1e6, 1),
            kernels_us=round(sums["kernel"][1], 1),
            other_us=round(sums["other"][1], 1),
            layer_us=round(sum(v[1] for v in sums.values()), 1))), flush=True)
        del lp


# --moe-glue's shapes: (experts held, choices a token, d_model, an expert's
# width, router outputs a choice is drawn from) of the four cells whose
# glue differs most: 32 and 64 narrow groups all held (lfm2-8b-a1b-cut,
# kimi-vl-a3b-cut), 16 of LongCat's 768 outputs held at its width (about 2%
# of the rows live), Mixtral's 8 wide groups; and two more held ranges.
_GLUE_SHAPES = {
    "lfm2": (32, 4, 2048, 1792, 32),
    "kimi": (64, 6, 2048, 1408, 64),
    "longcat": (16, 12, 6144, 2048, 768),
    "mixtral": (8, 2, 4096, 14336, 8),
    "dots3": (32, 8, 5120, 1536, 256),
    "deepseek": (16, 8, 7168, 2048, 256),
    "tiny": (4, 2, 128, 256, 8),      # the CPU rehearsal's
}


def moe_decode_main(args):
    """The held experts' part of one expert layer at decode shapes (a row a
    lane, a chip that holds a range of the experts its router scores), at the
    widths of ``--moe-decode-configs``: the dense einsums over every held
    expert as the models have them, against the form that reads the chosen
    experts alone (ops/pallas_moe.chosen_experts) and, with
    ``--moe-candidates``, a loop over the held experts in plain XLA with a
    ``lax.cond`` around one expert's products. Choices are drawn evenly over
    the router's outputs, ``--moe-decode-draws`` routings a point; GB/s is of
    the weights a form has to read (dense: every held expert's; the others:
    the live ones'). The last lines run the form at two rows with no held
    expert chosen, and with a row whose choices do not count: on the chip
    either would halt the core if a block index went to -1."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf
    from llm_d_inference_scheduler_tpu.ops import pallas_moe

    interpret = args.moe_interpret
    device = jax.devices()[0]
    peak = (None if interpret else
            _chipbench_kernels().peaks(device.device_kind)["bytes_per_s"])
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    L, layer = 2, jnp.asarray(1, jnp.int32)   # stacked, the second layer read

    def line(**kv):
        print(json.dumps({**kv, "device": device.device_kind}), flush=True)

    def weights_of(local, gates, E, dtype):
        return jnp.einsum("tke,tk->te", jax.nn.one_hot(local, E, dtype=dtype),
                          gates.astype(dtype))

    for name in args.moe_decode_configs.split(","):
        with open(os.path.join(root, "chipbench", "configs",
                               name + ".json")) as f:
            m = config_from_hf(types.SimpleNamespace(**json.load(f)),
                               name=name)
        first, E = m.held_experts
        k, outputs = m.experts_per_token, m.router_width
        gated = not m.moe_latent_dim          # models/hybrid.py: relu squared
        D, F = m.moe_latent_dim or m.d_model, m.moe_d_ff or m.d_ff
        if interpret:                         # the control flow alone
            D, F = 256, 128
        dt = jnp.dtype(m.dtype)
        expert_bytes = (3 if gated else 2) * D * F * dt.itemsize
        keys = iter(jax.random.split(jax.random.key(0), 8))

        def w(shape, fan_in):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * fan_in ** -0.5).astype(dt)

        stack = {"w1": w((L, E, D, F), D), "w2": w((L, E, F, D), F),
                 **({"w3": w((L, E, D, F), D)} if gated else {})}

        def dense(stack, x, local, gates):
            lp = {n: a[layer] for n, a in stack.items()}
            weights = weights_of(local, gates, E, x.dtype)
            up = jnp.einsum("td,edf->tef", x, lp["w1"])
            if not gated:     # the gate ahead of the one sum (models/hybrid)
                act = jnp.square(jax.nn.relu(up)) * weights[..., None]
                return jnp.einsum("tef,efz->tz", act, lp["w2"])
            act = jax.nn.silu(up) * jnp.einsum("td,edf->tef", x, lp["w3"])
            return jnp.einsum("ted,te->td",
                              jnp.einsum("tef,efd->ted", act, lp["w2"]),
                              weights)

        def chosen(stack, x, local, gates):
            return pallas_moe.chosen_experts(
                stack, x, local, gates, E, layer=layer, gated=gated,
                interpret=interpret)[0]

        def xla_cond(stack, x, local, gates):
            weights = weights_of(local, gates, E, x.dtype)
            live = jnp.any(local[..., None] == jnp.arange(E), axis=(0, 1))

            def expert(e, y):
                def run(y):
                    up = x @ stack["w1"][layer, e]
                    act = (jax.nn.silu(up) * (x @ stack["w3"][layer, e])
                           if gated else jnp.square(jax.nn.relu(up)))
                    out = (act @ stack["w2"][layer, e]).astype(jnp.float32)
                    return y + out * weights[:, e, None]
                return jax.lax.cond(live[e], run, lambda y: y, y)

            y = jax.lax.fori_loop(0, E, expert,
                                  jnp.zeros(x.shape, jnp.float32))
            return y.astype(x.dtype)

        forms = {"dense": dense, "chosen": chosen}
        if args.moe_candidates:
            forms["xla_cond"] = xla_cond
        forms = {n: jax.jit(fn) for n, fn in forms.items()}
        rng = np.random.default_rng(0)

        def draw(T):
            idx = np.argsort(rng.random((T, outputs)), axis=1)[:, :k]
            local = np.where((idx >= first) & (idx < first + E),
                             idx - first, -1).astype(np.int32)
            return jnp.asarray(local), jnp.asarray(
                rng.random((T, k), np.float32))

        for T in [int(t) for t in args.moe_decode_rows.split(",")]:
            x = jax.random.normal(jax.random.key(T), (T, D), dt)
            draws = [draw(T) for _ in range(args.moe_decode_draws)]
            live = [len(set(np.asarray(loc).ravel()) - {-1})
                    for loc, _ in draws]
            ms, err = {}, 0.0
            for form, fn in forms.items():
                try:
                    ms[form] = float(np.mean(
                        [timeit(fn, stack, x, *d, iters=args.moe_decode_iters)
                         for d in draws]))
                except Exception as e:   # a form the compiler refuses
                    line(component="moe_decode", config=name, form=form, T=T,
                         error=f"{type(e).__name__}: {str(e)[:300]}")
            want = forms["dense"](stack, x, *draws[0]).astype(jnp.float32)
            if "chosen" in ms:
                got = forms["chosen"](stack, x, *draws[0]).astype(jnp.float32)
                err = float(jnp.abs(got - want).max() / jnp.abs(want).max())

            def gbps(form, experts):
                return (None if peak is None or form not in ms else round(
                    experts * expert_bytes / (ms[form] * 1e-3) / 1e9, 1))

            line(component="moe_decode", config=name, T=T, held=E, D=D, F=F,
                 rows_per_expert=round(T * k / outputs, 3),
                 live_mean=round(float(np.mean(live)), 2),
                 unread_share_pct=round(100 * (1 - np.mean(live) / E), 1),
                 ms={n: round(v, 4) for n, v in ms.items()},
                 read_GBps={n: gbps(n, E if n == "dense" else
                                    max(float(np.mean(live)), 1.0))
                            for n in ms},
                 chosen_vs_dense=(round(ms["dense"] / ms["chosen"], 3)
                                  if "chosen" in ms else None),
                 chosen_err_vs_dense=err)

        # What halts a chip and not the interpreter: no tile live at all, and
        # a lane whose choices do not count.
        x = jax.random.normal(jax.random.key(2), (2, D), dt)
        local, gates = draw(2)
        local = local.at[0, 0].set(0)              # a held expert, chosen ...
        nobody = jnp.full_like(local, -1)
        one = local.at[1].set(-1)                  # ... by row 0 alone
        none = forms["chosen"](stack, x, nobody, gates)
        got = forms["chosen"](stack, x, one, gates).astype(jnp.float32)
        want = forms["dense"](stack, x, one, gates).astype(jnp.float32)
        line(check="moe_decode_edges", config=name,
             none_chosen_max_abs=float(jnp.abs(none.astype(jnp.float32)).max()),
             one_row_err_vs_dense=float(jnp.abs(got - want).max()
                                        / jnp.abs(want).max()),
             one_row_second_row_max_abs=float(jnp.abs(got[1]).max()))
        del stack


def ssm_main(args):
    """The state update of every state layer of ``--ssm-model`` at ``lanes``
    lanes: the kernel (a head block each of ``--ssm-head-blocks``; the
    default is the one the shapes choose) and the gathered form."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.models.configs import get_config
    from llm_d_inference_scheduler_tpu.ops import pallas_ssm

    kernels = _chipbench_kernels()      # puts chipbench/ on the path
    import kernels_ssm

    m = get_config(args.ssm_model)
    L, H, P, N, G = (m.n_state_layers, m.ssm_heads, m.ssm_head_dim,
                     m.ssm_state, m.ssm_groups)
    device = jax.devices()[0]
    peak = (None if args.ssm_interpret
            else kernels.peaks(device.device_kind)["bytes_per_s"])

    def gathered(ssm, layer, slots, *small):
        rows, y = pallas_ssm.update_rows(ssm[layer, slots], *small)
        return ssm.at[layer, slots].set(rows), y

    def every_layer(update, ssm, slots, *small):
        ys = []
        for layer in range(L):
            ssm, y = update(ssm, jnp.asarray(layer, jnp.int32), slots, *small)
            ys.append(y)
        return ssm, jnp.stack(ys)

    for lanes in map(int, args.ssm_lanes.split(",")):
        keys = jax.random.split(jax.random.key(lanes), 6)
        slots = jax.random.permutation(keys[0], lanes).astype(jnp.int32)
        small = (jax.random.uniform(keys[1], (lanes, H), jnp.float32, .5, 1.),
                 jax.random.normal(keys[2], (lanes, H, P), jnp.float32),
                 jax.random.normal(keys[3], (lanes, G, N), jnp.float32),
                 jax.random.normal(keys[4], (lanes, G, N), jnp.float32))

        def pool():
            return jax.random.normal(keys[5], (L, lanes + 1, H, P, N),
                                     jnp.float32)

        need = kernels_ssm.ssm_state_update(lanes, H, P, N, G)
        forms = {"gathered": gathered}
        for hb in (args.ssm_head_blocks.split(",")
                   if args.ssm_head_blocks else [None]):
            forms[f"kernel hb={hb or pallas_ssm.pick_head_block(H, P, N)}"] = (
                functools.partial(pallas_ssm.update_in_place,
                                  head_block=hb and int(hb),
                                  interpret=args.ssm_interpret))
        want = None
        for name, update in forms.items():
            fn = jax.jit(functools.partial(every_layer, update),
                         donate_argnums=(0,))
            ssm, y = fn(pool(), slots, *small)
            got = (np.asarray(ssm[:, slots[:2]]), np.asarray(y))
            want = want or got
            t0 = time.perf_counter()
            for _ in range(args.ssm_iters):
                ssm, y = fn(ssm, slots, *small)
            jax.block_until_ready(ssm)
            call_s = (time.perf_counter() - t0) / args.ssm_iters / L
            del ssm
            print(json.dumps({
                "component": f"ssm_state_update {name}", "lanes": lanes,
                "layers": L, "ms_per_call": round(call_s * 1e3, 4),
                "ms_per_step": round(call_s * L * 1e3, 3),
                "needed_GBps": round(need["bytes"] / call_s / 1e9, 1),
                "share_of_peak_pct": (
                    None if peak is None else
                    round(100 * need["bytes"] / call_s / peak, 1)),
                "state_vs_gathered": float(
                    np.abs(got[0] - want[0]).max() / np.abs(want[0]).max()),
                "y_vs_gathered": float(
                    np.abs(got[1] - want[1]).max() / np.abs(want[1]).max()),
                "device": device.device_kind}), flush=True)


def ssm1_main(args):
    """Both kernels of a Mamba-1 layer alone, at ``--ssm1-config``'s widths:
    the decode step's update of every state layer at ``--ssm-lanes`` lanes
    (the kernel at each of ``--ssm1-channel-blocks``, and the gathered form)
    with the share of the chip's peak its needed bytes come to
    (chipbench/kernels_ssm1.py), and one prompt window's scan of one layer at
    ``--ssm1-rows`` rows (the kernel at each of
    ``--ssm1-scan-channel-blocks`` x ``--ssm1-time-blocks``, against
    ``lax.scan`` over positions)."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf
    from llm_d_inference_scheduler_tpu.ops import pallas_ssm

    kernels = _chipbench_kernels()      # puts chipbench/ on the path
    import kernels_ssm1

    with open(args.ssm1_config) as f:
        m = config_from_hf(types.SimpleNamespace(**json.load(f)), "ssm1")
    L, (N, C) = m.n_state_layers, m.ssm_row
    device = jax.devices()[0]
    interpret = args.ssm_interpret
    peak = (None if interpret
            else kernels.peaks(device.device_kind)["bytes_per_s"])
    blocks = [int(b) if b else None
              for b in args.ssm1_channel_blocks.split(",")]

    def operands(key, lead):
        k = jax.random.split(key, 6)
        return (jax.random.uniform(k[0], (*lead, C), jnp.float32, 1e-3, .1),
                jax.random.normal(k[1], (*lead, C), jnp.float32),
                jax.random.normal(k[2], (*lead, N), jnp.float32),
                jax.random.normal(k[3], (*lead, N), jnp.float32),
                -jax.random.uniform(k[4], (N, C), jnp.float32, 1., 16.),
                jax.random.normal(k[5], (C,), jnp.float32))

    def relative(got, want):
        return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                     / np.abs(np.asarray(want)).max())

    def gathered(ssm, layer, slots, *small):
        rows, y = pallas_ssm.update1_rows(ssm[layer, slots], *small)
        return ssm.at[layer, slots].set(rows), y

    def every_layer(update, ssm, slots, *small):
        ys = []
        for layer in range(L):
            ssm, y = update(ssm, jnp.asarray(layer, jnp.int32), slots, *small)
            ys.append(y)
        return ssm, jnp.stack(ys)

    for lanes in map(int, args.ssm_lanes.split(",")):
        slots = jax.random.permutation(jax.random.key(lanes), lanes).astype(
            jnp.int32)
        small = operands(jax.random.key(lanes + 1), (lanes,))
        need = kernels_ssm1.ssm1_state_update(lanes, C, N)
        forms = {"gathered": gathered}
        for cb in blocks:
            forms[f"kernel cb={cb or C}"] = functools.partial(
                pallas_ssm.update1_in_place, channel_block=cb,
                interpret=interpret)
        want = None
        for name, update in forms.items():
            fn = jax.jit(functools.partial(every_layer, update),
                         donate_argnums=(0,))
            ssm, y = fn(jax.random.normal(jax.random.key(7),
                                          (L, lanes + 1, N, C), jnp.float32),
                        slots, *small)
            got = (np.asarray(ssm[:, slots[:2]]), np.asarray(y))
            want = want or got
            t0 = time.perf_counter()
            for _ in range(args.ssm_iters):
                ssm, y = fn(ssm, slots, *small)
            jax.block_until_ready(ssm)
            call_s = (time.perf_counter() - t0) / args.ssm_iters / L
            del ssm
            print(json.dumps({
                "component": f"ssm1_state_update {name}", "lanes": lanes,
                "layers": L, "ms_per_call": round(call_s * 1e3, 4),
                "ms_per_step": round(call_s * L * 1e3, 3),
                "needed_GBps": round(need["bytes"] / call_s / 1e9, 1),
                "share_of_peak_pct": (
                    None if peak is None else
                    round(100 * need["bytes"] / call_s / peak, 1)),
                "state_vs_gathered": relative(got[0], want[0]),
                "y_vs_gathered": relative(got[1], want[1]),
                "device": device.device_kind}), flush=True)

    for rows in map(int, args.ssm1_rows.split(",")):
        small = operands(jax.random.key(rows), (1, rows))
        s0 = jax.random.normal(jax.random.key(rows + 1), (1, N, C), jnp.float32)
        forms = {"lax.scan": pallas_ssm.scan_rows}
        for cb in map(int, args.ssm1_scan_channel_blocks.split(",")):
            for tb in map(int, args.ssm1_time_blocks.split(",")):
                if rows % tb == 0:
                    forms[f"kernel cb={cb} tb={tb}"] = functools.partial(
                        pallas_ssm.selective_scan, channel_block=cb,
                        time_block=tb, interpret=interpret)
        want = None
        for name, scan in forms.items():
            fn = jax.jit(scan)
            got = fn(*small, s0)
            want = want or got
            jax.block_until_ready(got)
            t0 = time.perf_counter()
            for _ in range(args.ssm_iters):
                out = fn(*small, s0)
            jax.block_until_ready(out)
            call_s = (time.perf_counter() - t0) / args.ssm_iters
            print(json.dumps({
                "component": f"ssm1_selective_scan {name}", "rows": rows,
                "ms_per_layer_window": round(call_s * 1e3, 4),
                "ms_per_window": round(call_s * L * 1e3, 2),
                "us_per_row": round(call_s / rows * 1e6, 3),
                "y_vs_scan": relative(got[0], want[0]),
                "state_vs_scan": relative(got[1], want[1]),
                "device": device.device_kind}), flush=True)


def churned_tables(lanes: int, max_model_len: int, prompts=(4096, 16384),
                   outputs=(512, 1536), *, block: int = 16,
                   turnovers: int = 500, warm_up: int = 100, seed: int = 0,
                   allocator=None) -> list[list[int]]:
    """The block tables an engine's allocator hands out under a closed
    loop's churn, newest last: ``lanes`` requests in flight, each reserving
    prompt + output at admission (prompts log-uniform, outputs uniform, as
    the batch cells draw them; the defaults are longctx-reason's), every
    complete prompt block hash-committed as the engine commits it, a random
    lane ending and its successor admitted ``warm_up + turnovers`` times; the
    tables of the last ``turnovers`` admissions. A count of what the
    allocator does, not a measurement. The pool goes on fragmenting as it
    ages (PERF.md section 7 (63)): how many of a table's groups are runs
    depends on how many turnovers it has seen. ``allocator``: the class to
    drive (default: the engine's, with the pool the engine would give it)."""
    import math
    import random

    from llm_d_inference_scheduler_tpu.engine.blocks import (
        PrefixCachingAllocator,
    )

    rng = random.Random(seed)
    alloc = (allocator or PrefixCachingAllocator)(
        1 + lanes * -(-max_model_len // block), block)
    hashes = iter(range(1 << 62))

    def admit():
        prompt = int(math.exp(rng.uniform(*map(math.log, prompts))))
        table = alloc.alloc(alloc.blocks_for_tokens(
            prompt + rng.randint(*outputs)))
        whole = table[:prompt // block]
        alloc.commit_hashes(whole, [next(hashes) for _ in whole])
        return table

    live = [admit() for _ in range(lanes)]
    handed = []
    for turn in range(warm_up + turnovers):
        lane = rng.randrange(lanes)
        alloc.release(live[lane])
        live[lane] = admit()
        if turn >= warm_up:
            handed.append(live[lane])
    return handed


def window_churn(geom, lanes: int, prompts=(4096, 12288),
                 outputs=(512, 1536), *,
                 prefill: int = 1024, chunk: int = 8, chunks: int = 300,
                 seed: int = 0):
    """The window tables engine/blocks.WindowedAllocator leaves under a
    closed loop's churn (``geom``: the engine's kvcache/pages.PageGeometry;
    the defaults are longctx-16k's): ``lanes`` requests in flight, prompts
    log-uniform and outputs uniform, a prompt written in windows of
    ``prefill`` tokens ahead of the decode chunks (at most 4,096 tokens of
    prompt a step, oldest request first, as the engine's loop does), a decode
    chunk of ``chunk`` steps a decoding lane a turn, a lane that ends freed
    and its successor admitted at the next turn. Yields, ``chunks`` times,
    (the allocator, [(table, window row, position)] of the lanes that decoded
    this turn), and frees what is live at the end. A count of what the
    allocator does, not a measurement."""
    import math
    import random

    import numpy as np

    from llm_d_inference_scheduler_tpu.engine.blocks import allocator_for

    rng = random.Random(seed)
    owner = allocator_for(geom, False)
    width, block = geom.max_blocks_per_seq, geom.block
    slots = [None] * lanes
    for turn in range(chunks):
        for i, s in enumerate(slots):
            if s is None:
                prompt = int(math.exp(rng.uniform(*map(math.log, prompts))))
                prompt = max(1, min(prompt, width * block - outputs[1]))
                end = prompt + rng.randint(*outputs)
                slots[i] = dict(table=owner.alloc(-(-end // block)),
                                prompt=prompt, end=end, written=0, at=turn)
        budget = max(1, 4096 // prefill)
        for _, i in sorted((s["at"], i) for i, s in enumerate(slots)
                           if s["written"] < s["prompt"]):
            s = slots[i]
            while budget and s["written"] < s["prompt"]:
                hi = min(s["written"] + prefill, s["prompt"])
                owner.slide(s["table"], s["written"], hi,
                            np.zeros(width, np.int32), True)
                s["written"], budget = hi, budget - 1
                s["pos"] = hi
        decoded = []
        for i, s in enumerate(slots):
            if s["written"] < s["prompt"]:
                continue
            row = np.zeros(width, np.int32)
            owner.slide(s["table"], s["pos"], s["pos"] + chunk, row)
            decoded.append((s["table"], row, s["pos"]))
            s["pos"] += chunk
        yield owner, decoded
        for i, s in enumerate(slots):
            if s["written"] == s["prompt"] and s["pos"] >= s["end"]:
                owner.free(s["table"])
                slots[i] = None
    for s in filter(None, slots):
        owner.free(s["table"])


def latent_main(args):
    """The latent family's paged decode walks at ``--latent-config``'s
    widths, over tables with runs of adjacent pages and without."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.kvcache import pages
    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf
    from llm_d_inference_scheduler_tpu.ops import (pallas_dsa,
                                                   pallas_latent_attention,
                                                   sparse_attention)
    from llm_d_inference_scheduler_tpu.ops.attention import (
        latent_paged_decode_attention,
    )

    kernels = _chipbench_kernels()      # puts chipbench/ on the path
    import kernels_dsa
    import kernels_mla

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           args.latent_config + ".json")) as f:
        m = config_from_hf(types.SimpleNamespace(**json.load(f)),
                           name=args.latent_config)
    interpret = args.latent_interpret
    device = jax.devices()[0]
    dt = jnp.dtype(m.dtype)
    block, max_len = m.kv_block_size, args.latent_max_model_len
    width = -(-max_len // block)
    H, Dk, value = m.n_heads, m.latent_dim, m.kv_lora_rank
    Hi, Di = m.index_n_heads, m.index_dim
    kw = dict(value_dim=value, scale=0.1)
    churn = [tuple(map(int, r.split("-")))
             for r in (args.latent_prompts, args.latent_outputs)]
    latent = pallas_latent_attention.latent_paged_decode_attention_pallas
    masked = pallas_dsa.sparse_latent_paged_decode_attention_pallas
    scores = pallas_dsa.index_scores_paged_pallas

    def table_of(kind, lanes, n_pages):
        ids = 1 + np.arange(lanes * width)
        if kind == "runs":
            return ids.reshape(lanes, width)
        if kind == "shuffled":
            return np.random.default_rng(args.latent_seed).permutation(
                ids).reshape(lanes, width)
        # The churn's newest tables that reach the point's context (not in
        # flight together: two lanes may read one page, which a walk cannot
        # feel).
        reach = [t for t in churned_tables(lanes, max_len, *churn, block=block,
                                           seed=args.latent_seed)
                 if len(t) >= n_pages][-lanes:]
        if len(reach) < lanes:
            raise SystemExit(
                f"the churn handed out too few tables of {n_pages} pages")
        table = np.zeros((lanes, width), np.int32)
        for row, t in zip(table, reach):
            row[:len(t)] = t
        return table

    def timed(fn, *operands):
        """Seconds a call of ``fn(layer, *operands)``, the calls chained in
        one program over the pool's two layers in turn."""
        n = args.latent_iters

        def chain(*operands):
            def body(acc, layer):
                out = fn(layer, *operands)
                return acc + out.astype(jnp.float32).sum(), None

            return jax.lax.scan(body, jnp.float32(0),
                                jnp.arange(n, dtype=jnp.int32) % 2)[0]

        return timeit(jax.jit(chain), *operands, iters=3) / n * 1e-3

    def rel_err(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        return float(np.abs(got - want).max() / np.abs(want).max())

    served_with = pallas_latent_attention.RUN_PAGES
    try:
        for lanes, ctx in [map(int, pt.split("x"))
                           for pt in args.latent_points.split(",")]:
            keys = jax.random.split(jax.random.key(args.latent_seed), 7)
            n_pages = -(-ctx // block)
            n_blocks = 1 + lanes * width
            pool = jax.random.normal(
                keys[0], (2, n_blocks, block, -(-Dk // 128) * 128), dt)
            pool = pool.at[..., Dk:].set(0)
            q = jax.random.normal(keys[1], (lanes, H, Dk), dt)
            cur = jax.random.normal(keys[2], (lanes, Dk), dt)
            seq_lens = jnp.full((lanes,), ctx + 1, jnp.int32)
            if m.index_topk:
                keys_pool = jax.random.normal(
                    keys[3], (2, n_blocks, block, Di), dt)
                q_idx = jax.random.normal(keys[4], (lanes, Hi, Di), dt)
                w_idx = jax.random.normal(keys[5], (lanes, Hi), jnp.float32)
                # A selection as the indexer leaves it: index_topk rows of
                # the context, anywhere.
                chosen = jax.vmap(lambda k: jax.random.permutation(k, ctx))(
                    jax.random.split(keys[6], lanes))[:, :m.index_topk]
                keep = jnp.zeros((lanes, width * block), bool).at[
                    jnp.arange(lanes)[:, None], chosen].set(True)
                cur_keep = jnp.ones((lanes,), bool)

            # name -> (the jitted kernel, a call of it at a layer, the
            # operands the chained program takes, what a call needs, its
            # plain form at a layer and a table)
            walks = {"mla_paged_decode_attention": (
                latent,
                lambda layer, q, pool, bt, cur: latent(
                    q, pool, layer, bt, seq_lens, cur, interpret=interpret,
                    **kw),
                lambda bt: (q, pool, bt, cur),
                kernels_mla.latent_attention_decode(
                    lanes * ctx, lanes, H, Dk, value),
                lambda bt: latent_paged_decode_attention(
                    q, pool, 1, bt, seq_lens, cur, **kw))}
            if m.index_topk:
                walks["dsa_paged_decode_attention"] = (
                    masked,
                    lambda layer, q, pool, bt, cur: masked(
                        q, pool, layer, bt, seq_lens, cur, keep, cur_keep,
                        interpret=interpret, **kw),
                    lambda bt: (q, pool, bt, cur),
                    kernels_dsa.selected_attention_decode(
                        lanes * ctx, lanes, m.index_topk, H, Dk, value),
                    lambda bt: (
                        sparse_attention.sparse_latent_paged_decode_attention(
                            q, pool, jnp.asarray(1), bt, seq_lens, cur, keep,
                            cur_keep, **kw)))
                walks["dsa_index_scores_decode"] = (
                    scores,
                    lambda layer, q_idx, w_idx, keys_pool, bt: scores(
                        q_idx, w_idx, keys_pool, layer, bt, seq_lens,
                        interpret=interpret)[:, :ctx],
                    lambda bt: (q_idx, w_idx, keys_pool, bt),
                    kernels_dsa.indexer_decode(lanes * ctx, lanes, Hi, Di),
                    lambda bt: sparse_attention.index_scores(
                        q_idx[:, None], w_idx[:, None],
                        pages.read_rows(keys_pool, 1, bt))[:, 0, :ctx])

            for kind in args.latent_tables.split(","):
                bt = jnp.asarray(table_of(kind, lanes, n_pages), jnp.int32)
                plain = {name: walk[4](bt) for name, walk in walks.items()}
                for R in map(int, args.latent_groups.split(",")):
                    pallas_latent_attention.RUN_PAGES = R
                    share = np.asarray(pallas_latent_attention.table_runs(
                        bt, seq_lens, block, R))[:, :n_pages // R].mean()
                    for name, (kernel, call, operands, need, _) in (
                            walks.items()):
                        kernel.clear_cache()    # R is read at the trace
                        call_s = timed(call, *operands(bt))
                        least, bound = (
                            (None, None) if interpret else
                            kernels.roofline_seconds(need, device.device_kind))
                        print(json.dumps({
                            "component": name, "R": R, "table": kind,
                            "run_share_pct": round(100 * float(share), 1),
                            "lanes": lanes, "ctx": ctx,
                            "ms_per_call": round(call_s * 1e3, 4),
                            "ns_per_page": round(
                                call_s * 1e9 / (lanes * n_pages), 2),
                            "needed_GBps": round(
                                need["bytes"] / call_s / 1e9, 1),
                            "least_ms": least and round(least * 1e3, 4),
                            "bound": bound,
                            "roofline_pct": least and round(
                                100 * least / call_s, 2),
                            "max_err_vs_plain": rel_err(
                                call(1, *operands(bt)), plain[name]),
                            "device": device.device_kind}), flush=True)
    finally:
        pallas_latent_attention.RUN_PAGES = served_with


def window_main(args):
    """The latent family's expanded attention at ``--window-config``'s
    widths, whole and tiled, over prior buckets."""
    import dataclasses
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.models import mla
    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           args.window_config + ".json")) as f:
        m = config_from_hf(types.SimpleNamespace(**json.load(f)),
                           name=args.window_config)
    tiled = "kernel_interpret" if args.window_interpret else "kernel"
    dt = jnp.dtype(m.dtype)
    S, H, block = args.window_tokens, m.n_heads, m.kv_block_size
    keys = jax.random.split(jax.random.key(0), 4)
    lp = {"wkvb": (jax.random.normal(
        keys[0], (m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)))
        * m.kv_lora_rank ** -0.5).astype(dt)}
    q_nope = jax.random.normal(keys[1], (1, S, H, m.qk_nope_head_dim), dt)
    q_rope = jax.random.normal(keys[2], (1, S, H, m.qk_rope_head_dim), dt)
    for prior in map(int, args.window_priors.split(",")):
        # models/mla.prefill_with_prefix's mask: the prior bucket's rows up
        # to the prefix, then the window's own under the diagonal (a first
        # window, ``forward``: its own alone).
        T = prior * block
        prefix = int(T * args.window_live)
        pos = prefix + np.arange(S)[None]
        kv_pos = np.concatenate([np.arange(T)[None], pos], axis=1)
        valid = np.concatenate([np.arange(T)[None] < prefix,
                                np.ones((1, S), bool)], axis=1)
        mask = jnp.asarray((pos[:, :, None] >= kv_pos[:, None, :])
                           & valid[:, None])
        rows = jax.random.normal(keys[3], (1, T + S, m.latent_dim), dt)
        got = {}
        for form in ("xla", tiled):
            fn = jax.jit(functools.partial(
                mla.expanded_attention,
                dataclasses.replace(m, expanded_impl=form)))
            ms = timeit(fn, lp, q_nope, q_rope, rows, mask,
                        iters=args.window_iters)
            got[form] = np.asarray(fn(lp, q_nope, q_rope, rows, mask),
                                   np.float32)
            print(json.dumps({
                "component": "mla_window_attention", "form": form,
                "heads": H, "window": S, "prior_blocks": prior,
                "rows": T + S, "live_rows": prefix + S,
                "ms_per_call": round(ms, 3),
                # What the form writes to memory of the scores: [H, S, rows]
                # in f32 whole, nothing where a tile stays in VMEM.
                "score_bytes": H * S * (T + S) * 4 if form == "xla" else 0,
                "max_err_vs_plain": (None if form == "xla" else float(
                    np.abs(got[form] - got["xla"]).max())),
            }), flush=True)


def kv_prefill_main(args):
    """One continuation window's attention of a K/V model with two kinds of
    layer at ``--kv-prefill-config``'s widths, a full and a window layer, the
    plain form and the kernel, over prior buckets."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.kvcache import pages
    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf
    from llm_d_inference_scheduler_tpu.ops import pallas_paged_attention as paged

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           args.kv_prefill_config + ".json")) as f:
        m = config_from_hf(types.SimpleNamespace(**json.load(f)),
                           name=args.kv_prefill_config)
    interpret = args.kv_prefill_interpret
    peak = None if interpret else _chipbench_kernels().peaks(
        jax.devices()[0].device_kind)["flops_per_s"]
    dt = jnp.dtype(m.dtype)
    S, H, block, band = (args.kv_prefill_tokens, m.n_heads, m.kv_block_size,
                         m.kv_window)
    geom = pages.PageGeometry.for_engine(m, args.kv_prefill_lanes,
                                         args.max_model_len)
    keys = jax.random.split(jax.random.key(args.seed), 7)
    q = jax.random.normal(keys[0], (1, S, H, m.head_dim), dt)
    k_new, v_new = (jax.random.normal(k, (1, S, m.n_kv_heads, m.head_dim), dt)
                    for k in keys[1:3])
    suffix_len = jnp.asarray([S], jnp.int32)
    tiles = [int(t) for t in args.kv_prefill_query_tiles.split(",") if t] \
        or [paged.QUERY_TILE]

    def attend(impl, pools, near_pools, is_near, at, prefix_len):
        # models/llama._mixed_prefill_with_prefix's call, in either form:
        # the engine's tables ascend (blocks.py; block 0 is the trash), a
        # window layer's are the pages that end where the window starts.
        near_table, pos = pages.window_prefix_pages(table, prefix_len, block,
                                                    band)
        return pages.prefill_attention(
            q, k_new, v_new, pools, near_pools, is_near, at, table,
            near_table, pos[:, 0], prefix_len, suffix_len, window=band,
            impl=impl)

    def chain(impl, pools, near_pools, is_near, prefix_len):
        # Every layer of the kind, the scan closing over the stacked pools.
        def body(acc, at):
            out = attend(impl, pools, near_pools, is_near, at, prefix_len)
            return acc + out.astype(jnp.float32).sum(), None

        return jax.lax.scan(body, jnp.float32(0), jnp.arange(
            (near_pools if is_near else pools)[0].shape[0],
            dtype=jnp.int32))[0]

    served_tile = paged.QUERY_TILE
    tiled = "kernel_interpret" if interpret else "kernel"
    pools, near_pools = (
        tuple(jax.random.normal(k, shape, dt) for k in ks)
        for ks, shape in ((keys[3:5], geom.shape),
                          (keys[5:7], geom.window.shape)))
    for kind in ("full", "window"):
        is_near = kind == "window"
        layers = (near_pools if is_near else pools)[0].shape[0]
        for prior in map(int, args.kv_prefill_priors.split(",")):
            prefix = int(prior * block * args.kv_prefill_live) // block * block
            # The kernel takes the sequence's whole table, the plain form
            # the prior bucket of it.
            widths = {"xla": prior, "kernel": geom.max_blocks_per_seq}
            prefix_len = jnp.asarray([prefix], jnp.int32)
            # (Query, row) pairs a query sees: every row up to itself, or
            # its band of them.
            at = prefix + np.arange(S)
            seen = int(np.sum(np.minimum(at + 1, band) if is_near
                              else at + 1))
            got = {}
            for name, impl, tile in [("xla", "xla", None)] + [
                    ("kernel", tiled, t) for t in tiles]:
                if tile:    # read where the kernel's wrapper is traced
                    paged.QUERY_TILE = tile
                    jax.clear_caches()
                table = jnp.asarray(
                    1 + np.arange(widths[name])[None] % (geom.n_blocks - 1),
                    jnp.int32)
                operands = (pools, near_pools, is_near, prefix_len)
                ms = timeit(jax.jit(functools.partial(chain, impl),
                                    static_argnums=2), *operands,
                            iters=args.kv_prefill_iters) / layers
                got[name] = np.asarray(jax.jit(
                    functools.partial(attend, impl), static_argnums=2)(
                    *operands[:3], jnp.int32(layers - 1), prefix_len),
                    np.float32)
                print(json.dumps({
                    "component": "kv_window_prefill_attention", "form": name,
                    "layer": kind, "heads": H, "window": S,
                    "prior_blocks": prior, "live_rows": prefix,
                    "table_width": widths[name] if kind == "full" else -(
                        -(band - 1) // block), "query_tile": tile,
                    "ms_per_layer": round(ms, 4),
                    "mxu_peak_share_pct": peak and round(
                        100 * 4.0 * H * m.head_dim * seen
                        / (ms * 1e-3) / peak, 2),
                    "max_err_vs_plain": (None if name == "xla" else float(
                        np.abs(got[name] - got["xla"]).max())),
                }), flush=True)
    paged.QUERY_TILE = served_tile
    jax.clear_caches()

def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen3-4b")
    ap.add_argument("--moe", action="store_true",
                    help="time one MoE FFN layer at prefill shapes instead")
    ap.add_argument("--moe-model", default="mixtral-8x7b")
    ap.add_argument("--moe-tokens", default="256,512,1024")
    ap.add_argument("--moe-candidates", action="store_true",
                    help="also time megablox gmm, lax.ragged_dot and other "
                         "tiles of the repo's kernel")
    ap.add_argument("--moe-check-seeds", default="0,1")
    ap.add_argument("--moe-interpret", action="store_true",
                    help="interpret the kernels: rehearses the control flow "
                         "on the CPU; its times mean nothing")
    ap.add_argument("--moe-glue", action="store_true",
                    help="book one grouped expert layer's device time op "
                         "by op instead: the XLA side, the kernels, the "
                         "whole (from a profiler trace)")
    ap.add_argument("--moe-glue-shapes", default="lfm2,kimi,longcat,mixtral")
    ap.add_argument("--moe-glue-tokens", type=int, default=1024)
    ap.add_argument("--moe-glue-iters", type=int, default=20)
    ap.add_argument("--moe-decode", action="store_true",
                    help="time the held experts of one expert layer at "
                         "decode shapes instead: dense over all of them "
                         "against the chosen ones alone")
    ap.add_argument("--moe-decode-configs",
                    default="longcat-flash-omni-cut,deepseek-v3.2-exp-cut,"
                            "dots3-note-prev-cut,nemotron-3-super-cut",
                    help="files of chipbench/configs: the widths")
    ap.add_argument("--moe-decode-rows", default="2,8,16,32,64,128")
    ap.add_argument("--moe-decode-draws", type=int, default=4)
    ap.add_argument("--moe-decode-iters", type=int, default=20)
    ap.add_argument("--ssm", action="store_true",
                    help="time the state-space layers' decode kernel alone "
                         "instead")
    ap.add_argument("--ssm1", action="store_true",
                    help="time a Mamba-1 layer's two kernels alone "
                         "(ops/pallas_ssm.py: the decode step's update in "
                         "place and a prompt window's scan); --ssm-lanes, "
                         "--ssm-iters and --ssm-interpret are shared")
    ap.add_argument("--ssm1-config",
                    default=os.path.join(
                        os.path.dirname(os.path.dirname(
                            os.path.abspath(__file__))),
                        "chipbench", "configs", "ai21-jamba2-3b.json"))
    ap.add_argument("--ssm1-channel-blocks", default="",
                    help="comma-separated channel blocks of the update to "
                         "try (an empty one: a lane's whole tile, which is "
                         "what is served and the default)")
    ap.add_argument("--ssm1-scan-channel-blocks", default="1024",
                    help="the scan kernel's (its tile is carried in vector "
                         "registers; 16 x 5,120 does not fit its VMEM)")
    ap.add_argument("--ssm1-time-blocks", default="128")
    ap.add_argument("--ssm1-rows", default="1024,128")
    ap.add_argument("--ssm-model", default="nemotron-3-super-cut")
    ap.add_argument("--ssm-lanes", default="64,16,2")
    ap.add_argument("--ssm-head-blocks", default="",
                    help="head blocks to time, comma-separated; default the "
                         "one the shapes choose")
    ap.add_argument("--ssm-iters", type=int, default=20)
    ap.add_argument("--ssm-interpret", action="store_true",
                    help="interpret the kernel: rehearses the control flow "
                         "on the CPU; its times mean nothing")
    ap.add_argument("--latent", action="store_true",
                    help="time the latent family's paged decode walks alone "
                         "instead, over tables with runs and without")
    ap.add_argument("--latent-config", default="deepseek-v3.2-exp-cut",
                    help="a file of chipbench/configs: the widths")
    ap.add_argument("--latent-points", default="32x9700",
                    help="lanes x context tokens a lane, comma-separated")
    ap.add_argument("--latent-max-model-len", type=int, default=18432)
    ap.add_argument("--latent-groups", default="4,8,16",
                    help="values of RUN_PAGES to time")
    ap.add_argument("--latent-tables", default="shuffled,churn,runs",
                    help="block tables: a shuffled pool (no runs), the "
                         "allocator's under the cell's churn, ascending")
    ap.add_argument("--latent-prompts", default="4096-16384",
                    help="the churn's prompts (log-uniform)")
    ap.add_argument("--latent-outputs", default="512-1536",
                    help="the churn's outputs (uniform)")
    ap.add_argument("--latent-iters", type=int, default=20)
    ap.add_argument("--latent-seed", type=int, default=0)
    ap.add_argument("--latent-interpret", action="store_true",
                    help="interpret the kernels: rehearses the control flow "
                         "on the CPU; its times mean nothing")
    ap.add_argument("--window", action="store_true",
                    help="time the latent family's expanded attention alone "
                         "instead, whole and tiled, over prior buckets")
    ap.add_argument("--window-config", default="kimi-vl-a3b-cut",
                    help="a file of chipbench/configs: the widths")
    ap.add_argument("--window-tokens", type=int, default=1024)
    ap.add_argument("--window-priors", default="0,64,128,256,512",
                    help="prior table buckets in blocks, comma-separated")
    ap.add_argument("--window-live", type=float, default=1.0,
                    help="share of a prior bucket's rows that the prefix "
                         "fills (the rest is the bucket's padding)")
    ap.add_argument("--window-iters", type=int, default=20)
    ap.add_argument("--window-interpret", action="store_true",
                    help="interpret the kernel: rehearses the control flow "
                         "on the CPU; its times mean nothing")
    ap.add_argument("--kv-prefill", action="store_true",
                    help="time one continuation window's K/V attention, a "
                         "full and a window layer, gathered and tiled")
    ap.add_argument("--kv-prefill-config", default="smallthinker-21b-a3b-cut",
                    help="chipbench/configs/<name>.json")
    ap.add_argument("--kv-prefill-tokens", type=int, default=1024)
    ap.add_argument("--kv-prefill-lanes", type=int, default=32)
    ap.add_argument("--kv-prefill-priors", default="64,128,256,512,1024",
                    help="prior table widths (blocks)")
    ap.add_argument("--kv-prefill-live", type=float, default=0.75,
                    help="share of the prior bucket that holds cached rows")
    ap.add_argument("--kv-prefill-query-tiles", default="",
                    help="queries a program of the kernel, to choose "
                         "ops/pallas_paged_attention.QUERY_TILE from "
                         "(default: the served one)")
    ap.add_argument("--kv-prefill-iters", type=int, default=10)
    ap.add_argument("--kv-prefill-interpret", action="store_true",
                    help="run the kernel through the interpreter (CPU "
                         "rehearsal)")
    ap.add_argument("--points", default="16x1000,16x300,8x300",
                    help="lanes x context tokens a lane, comma-separated")
    ap.add_argument("--max-model-len", type=int, default=1024)
    ap.add_argument("--tables", default="runs",
                    help="block tables to time the attention kernel over: "
                         "shuffled, churn, runs")
    ap.add_argument("--kv-window", type=int, default=0,
                    help="also time the window layers' walk "
                         "(swa_paged_decode_attention) at this window")
    ap.add_argument("--config-file", default="",
                    help="take the shapes from this benchmark configuration "
                         "(chipbench/configs/<name>.json), not from --model")
    ap.add_argument("--attn-only", action="store_true",
                    help="the attention kernels alone: no weights are made")
    ap.add_argument("--interpret", action="store_true",
                    help="rehearse the attention kernels through the Pallas "
                         "interpreter on the CPU; the times mean nothing")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_inference_scheduler_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    if args.moe_glue:
        return moe_glue_main(args)
    if args.moe_decode:
        return moe_decode_main(args)
    if args.moe:
        return moe_main(args)
    if args.ssm:
        return ssm_main(args)
    if args.ssm1:
        return ssm1_main(args)
    if args.latent:
        return latent_main(args)
    if args.window:
        return window_main(args)
    if args.kv_prefill:
        return kv_prefill_main(args)

    import types

    from llm_d_inference_scheduler_tpu.engine.sampling import sample_tokens
    from llm_d_inference_scheduler_tpu.kvcache import pages
    from llm_d_inference_scheduler_tpu.models import llama
    from llm_d_inference_scheduler_tpu.models.configs import get_config
    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf
    from llm_d_inference_scheduler_tpu.ops import attention as plain
    from llm_d_inference_scheduler_tpu.ops import pallas_paged_attention
    from llm_d_inference_scheduler_tpu.ops.pallas_latent_attention import (
        RUN_PAGES,
        table_runs,
    )

    kernels = _chipbench_kernels()

    if args.config_file:
        with open(args.config_file) as f:
            mcfg = config_from_hf(types.SimpleNamespace(**json.load(f)),
                                  name=os.path.basename(
                                      args.config_file)[:-len(".json")])
    else:
        mcfg = get_config(args.model)
    block = mcfg.kv_block_size
    kernel = functools.partial(pages.decode_attention, kernel=True,
                               interpret=args.interpret)
    params = None if args.attn_only else llama.init_params(
        mcfg, jax.random.key(0))
    # Interpreted on the CPU the times mean nothing: no share of a peak then.
    peak = None if args.interpret else kernels.peaks(
        jax.devices()[0].device_kind)["bytes_per_s"]

    def report(component, B, ctx, ms, **more):
        print(json.dumps({"component": component, "B": B, "ctx": ctx,
                          "ms_per_step": round(ms, 3), **more}), flush=True)

    def rel_err(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        return float(np.abs(got - want).max() / np.abs(want).max())

    def run_share(tables, seq_lens):
        """Of the whole groups inside the lanes' cached pages, the runs."""
        runs = np.asarray(table_runs(tables, seq_lens, block, RUN_PAGES))
        whole = -(-(np.asarray(seq_lens) - 1) // block) // RUN_PAGES
        return round(100 * float(runs.sum() / max(whole.sum(), 1)), 1)

    for B, ctx in [map(int, pt.split("x")) for pt in args.points.split(",")]:
        max_blocks = args.max_model_len // block
        # (A pool keeps a lone KV head twice; the yardstick counts it once.)
        L, G, D = mcfg.n_layers, mcfg.kv_heads_kept, mcfg.head_dim
        if args.attn_only:
            # The walks alone: two layers' pools do, read in turn CALLS times.
            L, calls = 2, 24
            k_pages = v_pages = jnp.zeros(
                (L, 1 + B * max_blocks, block, G, D), jnp.bfloat16)
        else:
            calls = L
            k_pages, v_pages = pages.alloc(pages.PageGeometry.for_model(
                mcfg, 1 + B * max_blocks, max_blocks, dtype="bfloat16"))
        ids = 1 + np.arange(B * max_blocks, dtype=np.int32)
        tables = jnp.asarray(ids.reshape(B, max_blocks))
        tokens = jnp.ones((B,), jnp.int32)
        positions = jnp.full((B,), ctx, jnp.int32)

        # full decode step: scan of 8 steps (keeps the production scan +
        # donation semantics), reported per-step. params passed as an
        # argument — closing over them bakes GBs of constants into the graph.
        def chain(params, k_pages, v_pages):
            def body(carry, _):
                kp, vp = carry
                logits, kp, vp = llama.decode_step(
                    params, mcfg, tokens, positions, kp, vp, tables,
                    attention_fn=kernel)
                return (kp, vp), logits[:, 0]

            (kp, vp), ls = jax.lax.scan(body, (k_pages, v_pages), None, length=8)
            return ls.sum()

        if not args.attn_only:
            ms = timeit(jax.jit(chain), params, k_pages, v_pages, iters=5) / 8
            report("decode_step(all)", B, ctx, ms)

        # attention kernel alone
        keys = jax.random.split(jax.random.key(args.seed), 4)
        q = jax.random.normal(keys[0], (B, mcfg.n_heads, D), jnp.bfloat16)
        cur = jax.random.normal(keys[1], (B, G, D), jnp.bfloat16)
        seq_lens = jnp.full((B,), ctx + 1, jnp.int32)
        if args.tables != "runs" or args.kv_window:
            # Rows worth comparing with the plain form's.
            k_pages = jax.random.normal(keys[2], k_pages.shape, k_pages.dtype)
            v_pages = jax.random.normal(keys[3], v_pages.shape, v_pages.dtype)
        n_pages = -(-ctx // block)

        def table_of(kind):
            if kind == "runs":
                return tables
            if kind == "shuffled":
                return jnp.asarray(np.random.default_rng(
                    args.seed).permutation(ids).reshape(B, max_blocks))
            # The churn's newest tables that reach the point's context.
            room = args.max_model_len - ctx
            reach = [t for t in churned_tables(
                B, args.max_model_len, (max(ctx // 2, block), ctx),
                (min(512, room), min(1536, room)), block=block,
                seed=args.seed) if len(t) >= n_pages][-B:]
            if len(reach) < B:
                raise SystemExit(
                    f"the churn handed out too few tables of {n_pages} pages")
            table = np.zeros((B, max_blocks), np.int32)
            for row, t in zip(table, reach):
                row[:len(t)] = t[:max_blocks]
            return jnp.asarray(table)

        # The stacked pools and a layer index, as decode_step calls it.
        def attn_chain(walk, q, k_pages, v_pages, tables, seq_lens):
            def body(acc, layer):
                o = walk(q, k_pages, v_pages, layer, tables, seq_lens, cur,
                         cur)
                return acc + o.astype(jnp.float32).sum(), None

            acc, _ = jax.lax.scan(body, jnp.float32(0),
                                  jnp.arange(calls, dtype=jnp.int32) % L)
            return acc

        def time_walk(name, walk, plain_walk, need, k_pages, v_pages, tables,
                      seq_lens, kind, pages_read):
            ms = timeit(jax.jit(functools.partial(attn_chain, walk)), q,
                        k_pages, v_pages, tables, seq_lens, iters=5)
            call_s = ms / calls * 1e-3
            report(f"{name} x{calls}", B, ctx, ms, table=kind,
                   run_share_pct=run_share(*pages_read[:2]),
                   ms_per_call=round(call_s * 1e3, 4),
                   us_per_page=round(call_s * 1e6 / pages_read[2], 4),
                   peak_bandwidth_share_pct=peak and round(
                       100 * need["bytes"] / call_s / peak, 2),
                   # (Two lanes: the plain form gathers a lane's whole
                   # table of rows, a query head at a time.)
                   max_err_vs_plain=rel_err(*(
                       form(q[:2], k_pages, v_pages, 1, tables[:2],
                            seq_lens[:2], cur[:2], cur[:2])
                       for form in (walk, plain_walk))))

        for kind in args.tables.split(","):
            bt = table_of(kind)
            time_walk("pallas_attn", kernel, plain.paged_decode_attention,
                      kernels.paged_attention_decode(B * ctx, B, mcfg.n_heads,
                                                     mcfg.n_kv_heads, D),
                      k_pages, v_pages, bt, seq_lens, kind,
                      (bt, seq_lens, B * n_pages))

        if args.kv_window:
            W = args.kv_window
            swa = functools.partial(
                pallas_paged_attention.swa_paged_decode_attention_kernel,
                window=W, interpret=args.interpret)
            swa_plain = functools.partial(plain.swa_paged_decode_attention,
                                          window=W)
            wmodel = types.SimpleNamespace(
                n_layers=L, n_window_layers=L, window=W, kv_block_size=block,
                latent_dim=0, n_kv_heads=G, head_dim=D, dtype="bfloat16")
            geom = pages.PageGeometry.for_engine(wmodel, B, args.max_model_len)
            pool_w = geom.window.n_blocks
            kw, vw = (jax.random.normal(k, (L, pool_w, block, G, D),
                                        jnp.bfloat16) for k in keys[2:])
            first = max(ctx + 1 - W, 0) // block
            # The logical pages in reach, from a stretch's first as the
            # window pool hands them out.
            held = np.arange(first - first % RUN_PAGES, n_pages + 1)
            for kind in args.tables.split(","):
                wt, lens = np.zeros((B, max_blocks), np.int32), seq_lens
                if kind == "churn":
                    # longctx-16k's mix at 16,384; each lane's newest row
                    # (not all in flight together: a walk cannot feel it).
                    n, newest = args.max_model_len, {}
                    for _, decoded in window_churn(
                            geom, B, (n // 4, 3 * n // 4),
                            (n // 32, 3 * n // 32), prefill=n // 16,
                            seed=args.seed):
                        for lane in decoded:
                            newest.pop(id(lane[0]), None)
                            newest[id(lane[0])] = lane
                    decoded = list(newest.values())[-B:]
                    for row, (_, got, _) in zip(wt, decoded):
                        row[:] = got
                    lens = jnp.asarray([pos + 1 for *_, pos in decoded],
                                       jnp.int32)
                else:
                    ids_w = 1 + np.arange(B * len(held), dtype=np.int32)
                    if kind == "shuffled":
                        ids_w = np.random.default_rng(args.seed).permutation(
                            ids_w)
                    wt[:, held] = ids_w.reshape(B, len(held))
                wt = jnp.asarray(wt)
                cut = plain.window_table(wt, lens, block, W, RUN_PAGES)
                seen = int(np.minimum(np.asarray(lens) - 1, W - 1).sum())
                time_walk("swa_paged_decode_attention", swa, swa_plain,
                          kernels.paged_attention_decode(
                              seen, B, mcfg.n_heads, mcfg.n_kv_heads, D),
                          kw, vw, wt, lens, kind,
                          (cut[0], cut[1],
                           int((-(-(np.asarray(cut[1]) - 1) // block)).sum())))

        if args.attn_only:
            continue

        # current-token KV scatter alone (all layers fused, K+V)
        k_cur = jnp.ones((L, B, G, D), jnp.bfloat16)
        slots = pages.token_slots(k_pages, tables, positions)

        def scatter_chain(kp, vp):
            def body(carry, _):
                return pages.write(*carry, k_cur, k_cur, *slots), ()

            (kp, vp), _ = jax.lax.scan(body, (kp, vp), None, length=8)
            return kp.reshape(-1)[0]

        ms = timeit(jax.jit(scatter_chain), k_pages, v_pages, iters=5) / 8
        report("kv_scatter(K+V, all L)", B, ctx, ms)

        # sampler alone
        logits = jnp.ones((B, mcfg.vocab_size), jnp.float32)
        temps = jnp.ones((B,), jnp.float32)
        zeros = jnp.zeros((B,), jnp.int32)
        ones = jnp.ones((B,), jnp.float32)

        def samp_chain(logits):
            def body(acc, k):
                t = sample_tokens(logits, k, temps, zeros, ones)
                return acc + t.sum(), None

            acc, _ = jax.lax.scan(body, jnp.int32(0),
                                  jax.random.split(jax.random.key(1), 8))
            return acc

        ms = timeit(jax.jit(samp_chain), logits, iters=5) / 8
        report("sample_tokens", B, ctx, ms)


if __name__ == "__main__":
    main()
