"""Compile the engine's step programs for a described TPU, without the chip.

The TPU compiler is installed beside JAX and compiles for a topology that is
described and not attached (`jax.experimental.topologies`). This hands it the
programs a single-device `TpuEngine` serves with — the random-weight init,
the fused decode chunk, plain prefill buckets and prefix-prefill buckets, of
any block family (models.family) and its page pool (and state pool) — at a registered
model's full size, and prints for each the compile seconds,
`memory_analysis()`, whether the Pallas call (`tpu_custom_call`) is in the
compiled text, and every `copy` of a parameter-shaped operand the text holds
(`param_copies`: a weight re-laid out once a program). The weights are handed
over as the engine holds them: where the family leaves a stacked weight's
layout to the decode program (`TpuEngine._param_formats`), in the layout its
compile settles on, printed first. What the compiler refuses here (a kernel
it cannot lower, a program that does not fit the device) it would refuse on
the chip.

Nothing runs: no result and no time printed here says anything about the
device. Each whole-model program takes minutes to compile on a few CPU cores,
so this is a script and not a tier-1 test (`tests/test_chip_compile.py` keeps
the kernels alone, a few seconds each).

Usage:
  JAX_PLATFORMS=cpu python scripts/aot_rehearsal.py [--model qwen3-4b]
      [--max-batch 16] [--max-model-len 2048] [--decode-batches 1,8]
      [--prefill 128x1,2048x1] [--prefix 16x128] [--topology v5e:2x2]

Comparing two trees' programs: `--lowered-dir DIR --no-compile` writes each
program's lowered StableHLO text to DIR and compiles nothing; run it in both
checkouts and `diff -r` the directories. The text carries no source
locations; a Pallas kernel's body (serialized in its custom call with file
names, lines and the Python call stack) is printed as assembly without them.
With `--lowered-dir` and a compile, each program's compiled text is kept
beside it (`<program>.hlo.txt`).
`--config-file` takes a published config.json as the benchmark's
configurations are (`chipbench/configs/*.json`), for a model the registry
does not name.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _without_locations(text: str) -> str:
    """Lowered text with every Mosaic kernel body (base64 MLIR bytecode,
    debug locations inside) replaced by its assembly without locations."""
    import base64
    import re

    from jaxlib.mlir import ir

    def body(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            return json.dumps(module.operation.get_asm(
                enable_debug_info=False))

    return re.sub(r'(?<=\\22body\\22: )\\22([A-Za-z0-9+/=]+)\\22', body, text)


def _param_copies(text: str, params) -> list[str]:
    """Every ``copy`` in a compiled program's text whose result has the
    shape and dtype of a parameter of two axes or more, as ``name:
    dtype[shape] {operand's layout} -> {result's layout}``: the compiler
    re-laying a weight out once a program (PERF.md section 6, PR 57), or,
    where the two orders of axes are one, moving it to another memory
    (``S(1)``). The CPU cannot make this check: its layouts are not the
    chip's."""
    import jax

    short = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
    shapes = {f"{short.get(a.dtype.name, a.dtype.name)}"
              f"[{','.join(map(str, a.shape))}]"
              for a in jax.tree.leaves(params) if len(a.shape) >= 2}
    layout_of = {m.group(1): m.group(2) for m in re.finditer(
        r"%?([\w.-]+) = \w+\[[\d,]*\](\{[^}]*\})", text)}
    return [
        f"{m.group(1)}: {m.group(2)} {layout_of.get(m.group(4), '{?}')} -> "
        f"{m.group(3)}"
        for m in re.finditer(
            r"%?([\w.-]+) = (\w+\[[\d,]*\])(\{[^}]*\}) copy\(%?([\w.-]+)\)",
            text)
        if m.group(2) in shapes]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen3-4b")
    ap.add_argument("--config-file", default="",
                    help="a published config.json, registered as --model")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-model-len", type=int, default=2048)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--decode-batches", default="1,8",
                    help="decode lane buckets to compile")
    ap.add_argument("--prefill", default="128x1,2048x1",
                    help="plain prefill programs as BUCKETxROWS")
    ap.add_argument("--prefix", default="16x128",
                    help="prefix-prefill programs as SUFFIXxPREFIX_BLOCKS")
    ap.add_argument("--hbm-kv-blocks", type=int, default=0,
                    help="pages in the pool (0: every lane at max_model_len), "
                         "to rehearse a smaller pool beside the weights")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--skip-init", action="store_true")
    ap.add_argument("--lowered-dir", default="",
                    help="write each program's lowered text here")
    ap.add_argument("--no-compile", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the device; keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    from llm_d_inference_scheduler_tpu.engine.config import EngineConfig
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
    from llm_d_inference_scheduler_tpu.kvcache import pages as kvpages
    from llm_d_inference_scheduler_tpu.kvcache import state as kvstate
    from llm_d_inference_scheduler_tpu.models import bind

    if args.config_file:
        import types

        from llm_d_inference_scheduler_tpu.models import configs
        from llm_d_inference_scheduler_tpu.models.convert_hf import (
            config_from_hf,
        )

        with open(args.config_file) as f:
            published = json.load(f)
        configs._REGISTRY[args.model] = config_from_hf(
            types.SimpleNamespace(**published), name=args.model)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    cfg = EngineConfig(model=args.model, max_batch=args.max_batch,
                       max_model_len=args.max_model_len,
                       decode_chunk=args.decode_chunk, pallas_attention=True,
                       hbm_kv_blocks=args.hbm_kv_blocks)
    # The jitted bodies are methods; they read only the engine's config, the
    # bound model, how to attend and the (absent) pipeline mesh, so a bare
    # instance carries them — building a real engine would materialise the
    # weights on the host.
    eng = object.__new__(TpuEngine)
    eng.cfg, eng.mesh, eng.pp_mesh, eng._prefill_fns = cfg, None, None, {}
    eng.device = topo.devices[0]
    # (Bound for the described chip, not this host's CPU.)
    eng.bound = bind(cfg.model_config, platform="tpu")
    eng.model = model = eng.bound.module
    eng.mcfg = mcfg = eng.bound.mcfg
    eng.geom = geom = kvpages.PageGeometry.for_engine(
        mcfg, cfg.max_batch, cfg.max_model_len, cfg.hbm_kv_blocks)
    state_geom = geom.state
    eng._decode_attention = kvpages.attention_for(geom, kernel=True,
                                                  interpret=False)

    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    params = on_chip(jax.eval_shape(
        lambda k: model.init_params(mcfg, k), jax.random.key(0)))
    width = eng.max_blocks_per_seq = geom.max_blocks_per_seq
    # The weights as the engine holds them: where the family leaves some
    # stacked weights' layout to the decode program, in the formats its
    # compile settles on (TpuEngine._param_formats).
    t0 = time.monotonic()
    formats = eng._param_formats(params)
    if formats is not None:
        print(json.dumps({
            "weight_layouts_major_to_minor": {
                name: list(formats["layers"][name].layout.major_to_minor)
                for name in model.LAID_BY_DECODE},
            "compile_s_on_this_host": round(time.monotonic() - t0, 1)}),
            flush=True)
        params = jax.tree.map(
            lambda a, f: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=f),
            params, formats)
    pages = sds(geom.shape, jnp.dtype(geom.dtype))

    def pool(rows, reads=False):
        """The pool pair as a step function of ``rows`` rows takes it: K and
        V, (latent, None), or (pages and state as one cache, None);
        ``reads``: a decode chunk that counts the held experts it read."""
        read = sds((), jnp.int32) if reads else None
        if state_geom:
            return (kvstate.Cache(
                pages, pages,
                # (None: the slots keep a convolution's tail alone.)
                None if state_geom.ssm_shape is None
                else sds(state_geom.ssm_shape, jnp.float32),
                sds(state_geom.conv_shape, jnp.dtype(state_geom.dtype)),
                slots=sds((rows,), jnp.int32), held=sds((), jnp.int32),
                read=read), None)
        if geom.counted or geom.window:
            # A latent pool that rides with counts, or pools of either kind
            # beside a window pool, which rides with its step's tables.
            window = (sds(geom.window.shape, jnp.dtype(geom.dtype))
                      if geom.window else None)
            return (kvstate.Cache(
                pages, None if geom.latent_dim else pages, None, None,
                slots=sds((rows,), jnp.int32),
                held=sds((), jnp.int32) if geom.counted else None, read=read,
                zero=sds((), jnp.int32) if geom.counts_zero else None,
                counts_zero=geom.counts_zero,
                idx=(sds(geom.index_shape, jnp.dtype(geom.dtype))
                     if geom.index_dim else None),
                win=window, win_v=None if geom.latent_dim else window,
                wt=(sds((rows, width), jnp.int32)
                    if geom.window else None),
                counted=geom.counted), None)
        return (pages, None) if geom.latent_dim else (pages, pages)

    def sampling(rows):
        return (key, sds((rows,), jnp.float32), sds((rows,), jnp.int32),
                sds((rows,), jnp.float32))

    programs = []
    if not args.skip_init:
        programs.append(("init", jax.jit(
            lambda k: model.init_params(mcfg, k), out_shardings=one_chip),
            (key,)))
    for b in [int(x) for x in args.decode_batches.split(",") if x]:
        programs.append((f"decode {b}x{width}", jax.jit(
            eng._decode_chunk_impl, donate_argnums=(3, 4)),
            (params, sds((b,), jnp.int32), sds((b,), jnp.int32),
             *pool(b, reads=eng.bound.decode_expert_visits(b) > 0),
             sds((b, width), jnp.int32), *sampling(b), sds((), jnp.int32))))
    for spec in [s for s in args.prefill.split(",") if s]:
        bucket, rows = (int(x) for x in spec.split("x"))
        programs.append((f"prefill {rows}x{bucket}", eng._prefill_fn(bucket),
                         (params, sds((rows, bucket), jnp.int32),
                          sds((rows,), jnp.int32), *pool(rows),
                          sds((rows, width), jnp.int32), *sampling(rows))))
    for spec in [s for s in args.prefix.split(",") if s]:
        suffix, prefix_blocks = (int(x) for x in spec.split("x"))
        programs.append((f"prefix_prefill {suffix}x{prefix_blocks}",
                         eng._prefix_prefill_fn(suffix, prefix_blocks),
                         (params, sds((1, suffix), jnp.int32),
                          sds((1,), jnp.int32), sds((1,), jnp.int32),
                          *pool(1), sds((1, width), jnp.int32),
                          sds((1, prefix_blocks), jnp.int32), *sampling(1))))

    ok = True
    for name, fn, fn_args in programs:
        t0 = time.monotonic()
        lowered = fn.lower(*fn_args)
        if args.lowered_dir:
            os.makedirs(args.lowered_dir, exist_ok=True)
            with open(os.path.join(args.lowered_dir,
                                   name.replace(" ", "_") + ".mlir"), "w") as f:
                f.write(_without_locations(lowered.as_text()))
        if args.no_compile:
            continue
        try:
            compiled = lowered.compile()
        except Exception as e:  # the compiler's refusal is the finding
            ok = False
            print(json.dumps({"program": name, "compiled": False,
                              "error": str(e)[:2000]}), flush=True)
            continue
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        if args.lowered_dir:
            with open(os.path.join(args.lowered_dir,
                                   name.replace(" ", "_") + ".hlo.txt"), "w") as f:
                f.write(text)
        # A ``copy`` whose result has a page pool's shape is the compiler
        # re-laying a whole pool out (PERF.md section 7, PR 48).
        pool_copy = re.compile("|".join(
            rf"= \w+\[{','.join(map(str, shape))}\]\S* copy\("
            for shape in (geom.shape, geom.window and geom.window.shape)
            if shape))
        print(json.dumps({
            "program": name, "compiled": True,
            "compile_s_on_this_host": round(time.monotonic() - t0, 1),
            "tpu_custom_call": "tpu_custom_call" in text,
            "pool_copies": len(pool_copy.findall(text)),
            "param_copies": _param_copies(text, params),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
        }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
