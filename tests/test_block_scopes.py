"""The models' blocks name themselves: every matmul and every kernel of the
step programs sits under a ``blk.`` scope of models/scopes.py, each family
emits the scopes it should, and the scopes change nothing but metadata.

The programs are the engine's own (the fused decode chunk, a plain prefill
and a window that continues a prefix), built on a bare ``TpuEngine`` as
scripts/aot_rehearsal.py builds them, at the tiny configurations, with the
Pallas kernels through the interpreter so that each ``pallas_call`` is in
the traced program. A scope is read as the device trace will show it: the
name stack of an equation, the inner jaxprs of ``scan`` / ``cond`` / ``pjit``
walked with their outer stack, the innermost ``blk.`` component winning
(chipbench/trace_scopes.py takes the same component of an op's ``tf_op``).
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from llm_d_inference_scheduler_tpu.engine.config import EngineConfig
from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
from llm_d_inference_scheduler_tpu.kvcache import pages as kvpages
from llm_d_inference_scheduler_tpu.kvcache import state as kvstate
from llm_d_inference_scheduler_tpu.models import bind, scopes

EVERY = {"embed", "attn.proj", "attn.core", "kv.write", "head", "sample"}
MOE = {"ffn.router", "ffn.experts"}
# (configuration, what every program of it emits beside EVERY, what its
# prefill programs emit besides: a window carries latent rows out, and
# tiny-moe's window of 512 tokens takes the grouped experts.)
FAMILIES = [
    ("tiny", {"ffn.dense"}, set()),
    ("tiny-moe", MOE, {"ffn.experts.glue"}),
    ("tiny-swa-kv", MOE, set()),
    ("tiny-mla", MOE | {"ffn.dense", "ffn.shared"}, {"attn.expand"}),
    ("tiny-dsa", MOE | {"ffn.dense", "ffn.shared", "attn.index"},
     {"attn.expand"}),
    ("tiny-swa", MOE | {"ffn.dense", "ffn.shared", "attn.index"},
     {"attn.expand"}),
    ("tiny-longcat", MOE | {"ffn.dense"}, {"attn.expand"}),
    ("tiny-hybrid", MOE | {"ffn.shared", "state.proj", "state.update"}, set()),
    ("tiny-jamba", {"ffn.dense", "state.proj", "state.update"}, set()),
]
LANES, WINDOW = 4, 512      # decode rows; a prefill window's tokens
NAMED = ("dot_general", "pallas_call")


def _programs(model: str, interpret: bool):
    """(name, jitted function, arguments as shapes) of the engine's step
    programs for ``model``, as a one-device engine on the CPU traces them."""
    cfg = EngineConfig(model=model, max_batch=LANES, max_model_len=2 * WINDOW,
                       decode_chunk=2, pallas_attention=interpret,
                       pallas_interpret=interpret)
    eng = object.__new__(TpuEngine)
    eng.cfg, eng.pp_mesh, eng._prefill_fns = cfg, None, {}
    eng.bound = bind(cfg.model_config, platform="cpu", interpret=interpret)
    eng.model, eng.mcfg = eng.bound.module, eng.bound.mcfg
    geom = eng.geom = kvpages.PageGeometry.for_engine(
        eng.mcfg, cfg.max_batch, cfg.max_model_len, cfg.hbm_kv_blocks)
    eng._decode_attention = kvpages.attention_for(
        geom, kernel=interpret, interpret=interpret)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    params = jax.eval_shape(
        lambda k: eng.model.init_params(eng.mcfg, k), jax.random.key(0))
    width = geom.max_blocks_per_seq
    pages = sds(geom.shape, jnp.dtype(geom.dtype))

    def pool(rows, reads=False):
        read = sds((), jnp.int32) if reads else None
        if geom.state:
            return (kvstate.Cache(
                pages, pages, sds(geom.state.ssm_shape, jnp.float32),
                sds(geom.state.conv_shape, jnp.dtype(geom.state.dtype)),
                slots=sds((rows,), jnp.int32), held=sds((), jnp.int32),
                read=read), None)
        if geom.counted or geom.window:
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
                wt=sds((rows, width), jnp.int32) if geom.window else None,
                counted=geom.counted), None)
        return (pages, None) if geom.latent_dim else (pages, pages)

    def sampling(rows):
        return (jax.eval_shape(lambda: jax.random.key(0)),
                sds((rows,), jnp.float32), sds((rows,), jnp.int32),
                sds((rows,), jnp.float32))

    one = sds((1,), jnp.int32)
    return [
        ("decode", jax.jit(eng._decode_chunk_impl),
         (params, sds((LANES,), jnp.int32), sds((LANES,), jnp.int32),
          *pool(LANES, reads=eng.bound.decode_expert_visits(LANES) > 0),
          sds((LANES, width), jnp.int32), *sampling(LANES),
          sds((), jnp.int32))),
        ("prefill", eng._prefill_fn(WINDOW),
         (params, sds((1, WINDOW), jnp.int32), one, *pool(1),
          sds((1, width), jnp.int32), *sampling(1))),
        ("prefix_prefill", eng._prefix_prefill_fn(WINDOW, 8),
         (params, sds((1, WINDOW), jnp.int32), one, one, *pool(1),
          sds((1, width), jnp.int32), sds((1, 8), jnp.int32), *sampling(1))),
    ]


def _scope_of(stack: str) -> str | None:
    """The innermost ``blk.`` component of a name stack, without the
    prefix."""
    found = [part for part in stack.split("/")
             if part.startswith(scopes.PREFIX)]
    return found[-1][len(scopes.PREFIX):] if found else None


def _walk(jaxpr, outer: str = ""):
    """(primitive name, whole name stack) of every equation, the inner
    jaxprs under their equation's stack."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, stack
        if eqn.primitive.name == "pallas_call":
            continue                    # a kernel's body is the kernel's
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner, stack)


@pytest.mark.parametrize("model,always,in_prefill", FAMILIES,
                         ids=[f[0] for f in FAMILIES])
def test_every_matmul_and_kernel_is_named_and_every_scope_appears(
        model, always, in_prefill):
    for name, fn, args in _programs(model, interpret=True):
        eqns = list(_walk(jax.make_jaxpr(fn)(*args).jaxpr))
        unnamed = [(prim, stack) for prim, stack in eqns
                   if prim in NAMED and _scope_of(stack) is None]
        assert not unnamed, (name, unnamed[:5])
        seen = {_scope_of(stack) for _, stack in eqns} - {None}
        assert seen <= set(scopes.BLOCKS), (name, seen)
        want = EVERY | always | (set() if name == "decode" else in_prefill)
        assert seen == want, (name, sorted(seen ^ want))
        if any(prim == "pallas_call" for prim, _ in eqns):
            # The attention's kernels sit where the metric looks for them.
            kernels = {_scope_of(s) for p, s in eqns if p == "pallas_call"}
            assert kernels <= {"attn.core", "attn.index", "ffn.experts",
                               "state.update"}, (name, kernels)


def _stripped(text: str) -> str:
    """Compiled text without what names where an instruction came from (each
    instruction's ``metadata={...}`` and the module's tables of files,
    functions and stack frames that the metadata points into) and without
    the numbers XLA hands its instructions as it makes them (``fusion.12``:
    they count every instruction a pass ever made, and shift with the
    metadata; the lowered text, which has neither, is compared whole)."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = re.sub(r"(?<=[\w\-])\.\d+\b", "", text)
    return "\n".join(
        line for line in text.splitlines()
        if not re.match(r"(FileNames|FunctionNames|FileLocations|StackFrames"
                        r"|\d+ [{\"])", line))


@pytest.mark.parametrize("model", [f[0] for f in FAMILIES])
def test_the_scopes_change_nothing_but_metadata(model, monkeypatch):
    """With ``scopes.block`` a null context the programs are the same: the
    lowered text to the letter, the compiled text once ``metadata={...}`` is
    stripped. Instruction for instruction the parent's."""
    def texts():
        lowered = [(name, fn.lower(*args))
                   for name, fn, args in _programs(model, interpret=False)]
        return {name: (low.as_text(), low.compile().as_text())
                for name, low in lowered}

    named = texts()
    assert any("blk.attn.core" in text for _, text in named.values())
    monkeypatch.setattr(scopes, "block", lambda name: contextlib.nullcontext())
    plain = texts()
    assert not any(scopes.PREFIX in text for _, text in plain.values())
    for name in named:
        assert named[name][0] == plain[name][0], name
        assert _stripped(named[name][1]) == _stripped(plain[name][1]), name
