"""The main path's kernels, handed to the TPU compiler at real widths.

Interpret mode cannot show what Mosaic refuses: a DMA slice off the tiling,
more VMEM than a kernel may hold. The TPU compiler is installed beside JAX
and compiles for a chip that is described and not attached, so each kernel is
lowered here for one v5e chip at the shapes the engine serves it with. Nothing
runs — a compile that passes says nothing about results or speed. The whole
Qwen3-4B step programs take minutes each and live in
scripts/aot_rehearsal.py; one decode step at Qwen3-4B's widths and two
layers is here, as the guard that no layer's page pool is copied.
"""

import dataclasses
import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from llm_d_inference_scheduler_tpu.kvcache.pages import decode_attention
from llm_d_inference_scheduler_tpu.models import llama
from llm_d_inference_scheduler_tpu.models.configs import MIXTRAL_8X7B, QWEN3_4B
from llm_d_inference_scheduler_tpu.ops import pallas_moe
from llm_d_inference_scheduler_tpu.ops.pallas_paged_attention import (
    paged_decode_attention_pallas,
    pages_per_stage,
)
from llm_d_inference_scheduler_tpu.utils.compile_cache import (
    outside_compile_cache,
)


@pytest.fixture(scope="module")
def one_chip():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the device (the next run would warn
    # and compile again): keep these out of it.
    with outside_compile_cache():
        yield SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,batch,table_width", [
    (QWEN3_4B, 16, 128), (QWEN3_4B, 64, 32), (MIXTRAL_8X7B, 16, 128)],
    ids=lambda v: getattr(v, "name", str(v)))
def test_paged_attention_compiles_at_qwen3_4b(one_chip, m, batch, table_width):
    """32 Q / 8 KV heads of 128, bf16 pages of 16 tokens; the 36 layers'
    cache of max_batch 16 x 2048 tokens (2,049 pages a layer), at decode batch
    16 (table 128 wide) and 64 (table 32 wide); and Mixtral's 32 layers at
    the same heads."""
    dt = jnp.dtype(m.dtype)
    pages = _sds(one_chip, (m.n_layers, 2049, m.kv_block_size, m.n_kv_heads,
                            m.head_dim), dt)
    cur = _sds(one_chip, (batch, m.n_kv_heads, m.head_dim), dt)
    args = (_sds(one_chip, (batch, m.n_heads, m.head_dim), dt), pages, pages,
            _sds(one_chip, (), jnp.int32),
            _sds(one_chip, (batch, table_width), jnp.int32),
            _sds(one_chip, (batch,), jnp.int32), cur, cur)
    compiled = paged_decode_attention_pallas.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # A step of the kernel is a stage of P pages: the call's K and V scratch
    # hold two tiles of P pages of 16 rows each, a page a leading index so
    # that a run of adjacent pages is one copy, and the traced call says so.
    # A kernel back at a page a step has `bf16[2,1,16,8,128]` here. Which
    # groups of 8 table entries are runs rides beside the table.
    p = pages_per_stage(m.kv_block_size, m.n_kv_heads, m.head_dim,
                        dt.itemsize, table_width)
    assert p >= 8
    tile = (f"Ref<vmem>{{bf16[2,{p},{m.kv_block_size},{m.n_kv_heads},"
            f"{m.head_dim}]}}")
    assert tile in str(jax.make_jaxpr(paged_decode_attention_pallas)(*args))
    assert f"s32[{batch * table_width // 8}]" in compiled.as_text()


@functools.cache
def _two_layer_decode_step(one_chip):
    """One decode step at Qwen3-4B's widths, depth cut to two, 16 lanes on
    the 16 x 2048 cache, Pallas attention on, compiled for ``one_chip``:
    (the model, one layer's pool shape, the compiled program)."""
    m = dataclasses.replace(QWEN3_4B, n_layers=2)
    dt = jnp.dtype(m.dtype)
    batch, width = 16, 128
    one_layer = (2049, m.kv_block_size, m.n_kv_heads, m.head_dim)
    pages = _sds(one_chip, (m.n_layers, *one_layer), dt)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: llama.init_params(m, k), jax.random.key(0)))
    compiled = jax.jit(
        lambda *a: llama.decode_step(
            a[0], m, *a[1:],
            attention_fn=functools.partial(decode_attention, kernel=True)),
        donate_argnums=(3, 4),
    ).lower(params, _sds(one_chip, (batch,), jnp.int32),
            _sds(one_chip, (batch,), jnp.int32), pages, pages,
            _sds(one_chip, (batch, width), jnp.int32)).compile()
    return m, one_layer, compiled


def test_decode_step_copies_no_layers_page_pool(one_chip):
    """The kernel must be handed the stacked pools: a pool scanned over
    reaches the custom call as one layer's slice, which XLA copies out first
    (67 MB of K and of V a layer)."""
    m, one_layer, compiled = _two_layer_decode_step(one_chip)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    shape = "bf16[" + ",".join(map(str, one_layer)) + "]"
    made = [ln.strip()[:160] for ln in hlo.splitlines()
            if re.search(r"=\s*" + re.escape(shape), ln)]
    assert not made, made
    assert (compiled.memory_analysis().temp_size_in_bytes
            < jnp.dtype(m.dtype).itemsize * math.prod(one_layer))


def test_the_engine_holds_the_projections_as_its_decode_chunk_reads_them(
        one_chip):
    """Handed ``wq`` and ``wk`` as [L, D, H * Dh] the TPU compiler copies
    both whole into another order of axes once a decode chunk, outside the
    layer loop. A one-chip engine asks the decode program's compile which
    layout it wants of the weights the family names (``LAID_BY_DECODE``) and
    holds them so (TpuEngine._param_formats): built for the arrays as they
    then lie, neither a decode bucket nor a prefill program holds a copy
    with a stacked weight's shape. On any other device the weights stay as
    they come."""
    from llm_d_inference_scheduler_tpu.engine.config import EngineConfig
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine
    from llm_d_inference_scheduler_tpu.kvcache import pages as kvpages
    from llm_d_inference_scheduler_tpu.models import bind, configs

    name = "qwen3-4b-two-layers"
    configs._REGISTRY[name] = dataclasses.replace(QWEN3_4B, name=name,
                                                  n_layers=2)
    try:
        cfg = EngineConfig(model=name, max_batch=16, max_model_len=2048,
                           pallas_attention=True)
        # (A bare instance, as scripts/aot_rehearsal.py makes one: the
        # jitted bodies read these and nothing else.)
        eng = object.__new__(TpuEngine)
        eng.cfg, eng.mesh, eng.pp_mesh, eng._prefill_fns = cfg, None, None, {}
        eng.device = next(iter(one_chip.device_set))
        eng.bound = bind(cfg.model_config, platform="tpu")
        eng.model, eng.mcfg = eng.bound.module, eng.bound.mcfg
        eng.geom = kvpages.PageGeometry.for_engine(eng.mcfg, 16, 2048, 0)
        eng.max_blocks_per_seq = width = eng.geom.max_blocks_per_seq
        eng._decode_attention = kvpages.attention_for(
            eng.geom, kernel=True, interpret=False)
    finally:
        del configs._REGISTRY[name]
    sds = functools.partial(_sds, one_chip)
    shapes = jax.eval_shape(
        lambda k: llama.init_params(eng.mcfg, k), jax.random.key(0))
    formats = eng._param_formats(shapes)
    assert jax.tree.structure(formats) == jax.tree.structure(shapes)
    assert all(sorted(formats["layers"][w].layout.major_to_minor) == [0, 1, 2]
               for w in llama.LAID_BY_DECODE)
    params = jax.tree.map(
        lambda a, f: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=f),
        shapes, formats)
    stacked = {"bf16[" + ",".join(map(str, a.shape)) + "]"
               for a in jax.tree.leaves(shapes["layers"]) if a.ndim == 3}
    pool = sds(eng.geom.shape, jnp.dtype(eng.geom.dtype))
    key = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                       jax.eval_shape(lambda: jax.random.key(0)))

    def sampling(rows):
        return (key, sds((rows,), jnp.float32), sds((rows,), jnp.int32),
                sds((rows,), jnp.float32))

    programs = {
        "decode": (jax.jit(eng._decode_chunk_impl, donate_argnums=(3, 4)),
                   (params, sds((8,), jnp.int32), sds((8,), jnp.int32), pool,
                    pool, sds((8, width), jnp.int32), *sampling(8),
                    sds((), jnp.int32))),
        "prefill": (eng._prefill_fn(512),
                    (params, sds((1, 512), jnp.int32), sds((1,), jnp.int32),
                     pool, pool, sds((1, width), jnp.int32), *sampling(1)))}
    for what, (fn, args) in programs.items():
        copies = [ln.strip()[:120] for ln in
                  fn.lower(*args).compile().as_text().splitlines()
                  if (m := re.search(r"= (\w+\[[\d,]*\])\S* copy\(", ln))
                  and m.group(1) in stacked]
        assert not copies, (what, copies)
    eng.device = jax.devices("cpu")[0]
    assert eng._param_formats(shapes) is None


def test_decode_step_names_its_blocks_for_the_device_trace(one_chip):
    """The same step as the TPU compiler leaves it: the Pallas call carries
    ``blk.attn.core`` in its ``op_name`` and the matmul fusions the scope of
    their block, which is what the profiler records as an op's framework
    name and chipbench/trace_scopes.py books device time by. (A fusion takes
    its root's ``op_name``.)"""
    _, _, compiled = _two_layer_decode_step(one_chip)
    named = {}
    for ln in compiled.as_text().splitlines():
        op = re.search(r'op_name="([^"]*)"', ln)
        if op and " = " in ln:
            named[ln.split(" = ")[0].split()[-1]] = (ln, op.group(1))
    calls = [path for ln, path in named.values() if "tpu_custom_call" in ln]
    assert calls and all("/blk.attn.core/" in path for path in calls), calls
    matmuls = {path.split("/blk.")[-1].split("/")[0]
               for ln, path in named.values()
               if " fusion(" in ln and path.endswith("/dot_general")}
    assert matmuls == {"attn.proj", "ffn.dense", "head"}, matmuls


def test_latent_decode_step_compiles_and_copies_no_pool(one_chip):
    """One decode step of the latent-attention family at Kimi-VL-A3B's
    widths, one dense and one expert layer, 32 lanes on the cell's pool (32 x
    8,192 tokens: 16,385 pages of 640 stored values a token), the latent
    kernel on: Mosaic takes the 640-wide page DMA and the 16-row products,
    the pool reaches the custom call whole (a layer's slice would be 336 MB
    of copy), and the step's temporaries stay under one layer's pool."""
    from llm_d_inference_scheduler_tpu.kvcache.pages import (
        PageGeometry, latent_decode_attention)
    from llm_d_inference_scheduler_tpu.models import mla
    from llm_d_inference_scheduler_tpu.models.configs import KIMI_VL_A3B

    m = dataclasses.replace(KIMI_VL_A3B, n_layers=2)
    geom = PageGeometry.for_engine(m, 32, 8192)
    dt = jnp.dtype(m.dtype)
    batch = 32
    pool = _sds(one_chip, geom.shape, dt)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: mla.init_params(m, k), jax.random.key(0)))
    compiled = jax.jit(
        lambda *a: mla.decode_step(
            a[0], m, *a[1:],
            attention_fn=functools.partial(latent_decode_attention,
                                           kernel=True)),
        donate_argnums=(3,),
    ).lower(params, _sds(one_chip, (batch,), jnp.int32),
            _sds(one_chip, (batch,), jnp.int32), pool, None,
            _sds(one_chip, (batch, geom.max_blocks_per_seq), jnp.int32)
            ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "mla_paged_decode_attention" in hlo
    shape = "bf16[" + ",".join(map(str, geom.shape[1:])) + "]"
    made = [ln.strip()[:160] for ln in hlo.splitlines()
            if re.search(r"=\s*" + re.escape(shape), ln)]
    assert not made, made
    assert (compiled.memory_analysis().temp_size_in_bytes
            < dt.itemsize * math.prod(geom.shape[1:]))


def test_no_window_of_a_latent_block_holds_its_scores_whole(one_chip):
    """A 1,024-token window of Kimi-VL-A3B's block over a prior table of 512
    blocks (8,192 rows), as an engine on a TPU binds it: the scores would be
    f32[1,16,1024,9216], 604 MB written and read three times a layer (4.3
    ms a layer on the chip; the windows' scores were 13.7% of longdoc-batch's
    busy time; builder's trace, PR 47). They stay in
    VMEM a tile at a time (ops/pallas_dsa.py under the name
    ``mla_window_attention``): the compiled program names no array of 16 x
    1,024 x 9,216 elements, and its temporaries are the rows' keys and values
    for all heads (2 x 9,216 x 16 x 128 bf16 = 75 MB), the experts' grouped
    rows and little else."""
    from llm_d_inference_scheduler_tpu.kvcache.pages import PageGeometry
    from llm_d_inference_scheduler_tpu.models import bind, mla
    from llm_d_inference_scheduler_tpu.models.configs import KIMI_VL_A3B

    S, prior = 1024, 512
    bound = bind(dataclasses.replace(KIMI_VL_A3B, n_layers=2), platform="tpu")
    m = bound.model_for(S)
    assert (m.expanded_impl, m.index_impl, m.moe_impl) == (
        "kernel", "xla", "grouped")
    geom = PageGeometry.for_engine(m, 32, 8192)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: mla.init_params(m, k), jax.random.key(0)))
    one = _sds(one_chip, (1,), jnp.int32)
    compiled = jax.jit(lambda p, *a: mla.prefill_with_prefix(p, m, *a),
                       donate_argnums=(4,)).lower(
        params, _sds(one_chip, (1, S), jnp.int32), one, one,
        _sds(one_chip, geom.shape, jnp.dtype(m.dtype)), None,
        _sds(one_chip, (1, geom.max_blocks_per_seq), jnp.int32),
        _sds(one_chip, (1, prior), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "mla_window_attention" in hlo and "dsa_window_attention" not in hlo
    rows = prior * geom.block + S
    # (The pool, the head and the experts' weights are larger: no rows' axis.)
    too_large = {t: n for t, n in _array_elements(hlo).items()
                 if n >= m.n_heads * S * rows and str(rows) in t}
    assert not too_large, too_large
    assert compiled.memory_analysis().temp_size_in_bytes < 400 << 20


def test_a_padded_batch_of_the_double_layers_prefill_takes_the_tiles(one_chip):
    """LongCat-Flash's double layer at the cell's widths (64 heads), a batch
    of two prompts in the 512 bucket under a padding mask: both sublayers'
    attention through the tiled kernel (16 grid steps of four heads a
    sequence), no f32[2,64,512,512] of scores."""
    import json
    import types

    from llm_d_inference_scheduler_tpu.models import bind, mla
    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "longcat-flash-omni-cut.json")) as f:
        published = json.load(f)
    B, S = 2, 512
    m = bind(config_from_hf(types.SimpleNamespace(
        **{**published, "num_layers": 1})), platform="tpu").model_for(B * S)
    assert (m.n_heads, m.expanded_impl) == (64, "kernel")
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: mla.init_params(m, k), jax.random.key(0)))
    compiled = jax.jit(lambda p, t, v: mla.forward(
        p, m, t, want_kv=True, kv_valid=v)).lower(
        params, _sds(one_chip, (B, S), jnp.int32),
        _sds(one_chip, (B, S), jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert hlo.count("mla_window_attention") >= 2
    assert not re.search(rf"\[{B},{m.n_heads},{S},{S}\]", hlo)


def test_double_layer_decode_step_compiles_and_copies_no_weight(one_chip):
    """One decode step of LongCat-Flash's double layer at the cell's widths
    (`chipbench/configs/longcat-flash-omni-cut.json`, two layers of its four),
    64 lanes on the cell's pool (64 x 2,048 tokens, four cache layers here),
    the pool in the cache that carries the counts: Mosaic takes the latent
    kernel's 64-row products (four times Kimi's), the pool reaches the custom
    call whole, and no weight is copied out of its stack -- a sublayer's
    tensors sliced a layer as [2, ...] and then a sublayer were, every layer
    of every step (0.9 GB of temporaries at four layers; AOT, PR 39), which
    is why the scan closes over them and reads row 2 l + i where it lies."""
    import json
    import types

    from llm_d_inference_scheduler_tpu.kvcache import state
    from llm_d_inference_scheduler_tpu.kvcache.pages import (
        PageGeometry, latent_decode_attention)
    from llm_d_inference_scheduler_tpu.models import mla
    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "longcat-flash-omni-cut.json")) as f:
        published = json.load(f)
    m = config_from_hf(types.SimpleNamespace(**{**published, "num_layers": 2}))
    assert (m.n_heads, m.n_kv_layers, m.held_experts) == (64, 4, (0, 16))
    geom = PageGeometry.for_engine(m, 64, 2048)
    dt = jnp.dtype(m.dtype)
    batch = 64
    cache = state.Cache(
        _sds(one_chip, geom.shape, dt), None, None, None,
        slots=_sds(one_chip, (batch,), jnp.int32),
        held=_sds(one_chip, (), jnp.int32), zero=_sds(one_chip, (), jnp.int32),
        counts_zero=True)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: mla.init_params(m, k), jax.random.key(0)))
    compiled = jax.jit(
        lambda *a: mla.decode_step(
            a[0], m, *a[1:],
            attention_fn=functools.partial(latent_decode_attention,
                                           kernel=True)),
        donate_argnums=(3,),
    ).lower(params, _sds(one_chip, (batch,), jnp.int32),
            _sds(one_chip, (batch,), jnp.int32), cache, None,
            _sds(one_chip, (batch, geom.max_blocks_per_seq), jnp.int32)
            ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "mla_paged_decode_attention" in hlo
    shape = "bf16[" + ",".join(map(str, geom.shape[1:])) + "]"
    made = [ln.strip()[:160] for ln in hlo.splitlines()
            if re.search(r"=\s*" + re.escape(shape), ln)]
    assert not made, made
    # Under the two dense-FFN matrices of one sublayer: the copy of a layer's
    # [2, 6144, 12288] slices alone was three times that.
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 2 * dt.itemsize * m.d_model * m.d_ff)


@pytest.mark.parametrize("steps", [1, 2])
def test_hybrid_decode_step_compiles_and_copies_no_pool(one_chip, steps):
    """One decode step of the nemotron_h family at Nemotron-3-Super's widths,
    one layer of each kind (a LatentMoE layer holding 128 of 512 experts, a
    Mamba-2 layer, attention with 2 KV heads), 64 lanes on the cell's pools
    (64 x 2,048 tokens; 65 slots of 4.19 MB of f32 state a layer), alone and
    as a ``lax.scan`` of two steps, the way the engine's decode chunk carries
    the cache. The paged attention kernel takes 2 KV heads; the state layer's
    rows are updated in place in the pool by ops/pallas_ssm.py's kernel, the
    pool aliased through the call and through the loop, and neither it nor
    the page pool is made anew. What went with the gathered form: the three
    sets of 64 rows a state layer (268 MB each: the gathered rows, the new
    ones, the gather loop's buffer) that made the step's temporaries 3.5 sets
    of rows; what is left is under one."""
    from llm_d_inference_scheduler_tpu.kvcache import state
    from llm_d_inference_scheduler_tpu.kvcache.pages import PageGeometry
    from llm_d_inference_scheduler_tpu.models import hybrid
    from llm_d_inference_scheduler_tpu.models.configs import (
        NEMOTRON_3_SUPER_CUT)
    from llm_d_inference_scheduler_tpu.ops import pallas_ssm

    m = dataclasses.replace(NEMOTRON_3_SUPER_CUT, n_layers=3,
                            layer_pattern="EM*")
    assert pallas_ssm.use_kernel(m.ssm_state, m.ssm_head_dim, platform="tpu",
                                 sharded=False)
    m = dataclasses.replace(m, ssm_impl="kernel")
    batch = 64
    geom = PageGeometry.for_engine(m, batch, 2048)
    sgeom = state.StateGeometry.for_engine(m, batch)
    dt = jnp.dtype(m.dtype)
    pages = _sds(one_chip, geom.shape, dt)
    cache = state.Cache(
        pages, pages, _sds(one_chip, sgeom.ssm_shape, jnp.float32),
        _sds(one_chip, sgeom.conv_shape, dt),
        slots=_sds(one_chip, (batch,), jnp.int32),
        held=_sds(one_chip, (), jnp.int32))
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: hybrid.init_params(m, k), jax.random.key(0)))

    def chunk(params, tokens, positions, cache, tables):
        def step(carry, _):
            tokens, positions, cache = carry
            logits, cache, _ = hybrid.decode_step(
                params, m, tokens, positions, cache, None, tables,
                attention_fn=functools.partial(decode_attention, kernel=True))
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, positions + 1, cache), nxt

        if steps == 1:
            (_, _, cache), toks = step((tokens, positions, cache), None)
        else:
            (_, _, cache), toks = jax.lax.scan(
                step, (tokens, positions, cache), None, length=steps)
        return toks, cache

    compiled = jax.jit(chunk, donate_argnums=(3,)).lower(
        params, _sds(one_chip, (batch,), jnp.int32),
        _sds(one_chip, (batch,), jnp.int32), cache,
        _sds(one_chip, (batch, geom.max_blocks_per_seq), jnp.int32)
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "ssm_state_update" in hlo
    for pool, dtype in ((sgeom.ssm_shape, "f32"), (geom.shape, "bf16")):
        shape = f"{dtype}[" + ",".join(map(str, pool)) + "]"
        made = [ln.strip()[:160] for ln in hlo.splitlines()
                if re.search(r"=\s*" + re.escape(shape), ln)
                and "parameter(" not in ln and "bitcast(" not in ln
                and "scatter" not in ln and "dynamic-update-slice" not in ln
                and "fusion(" not in ln and "get-tuple-element(" not in ln]
        assert not made, made
    rows = batch * 4 * math.prod(sgeom.ssm_shape[2:])
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * rows


@pytest.mark.parametrize("lanes,heads,head_dim,state_dim,groups,head_block", [
    # Nemotron-3-Super's state layer at the cell's smallest and largest lane
    # buckets: a slot-layer's 4 MB whole (16 MiB of VMEM in and out, past the
    # compiler's default scoped limit, which the call raises), and in blocks.
    (2, 128, 64, 128, 8, None), (64, 128, 64, 128, 8, None),
    (64, 128, 64, 128, 8, 32),
    # What else the rule lets through: a wider state, heads that are no whole
    # lane tile, a head of one sublane tile.
    (8, 64, 8, 256, 4, None), (2, 24, 16, 128, 3, None),
    (16, 256, 64, 128, 8, None)])
def test_ssm_state_update_compiles(one_chip, lanes, heads, head_dim,
                                   state_dim, groups, head_block):
    from llm_d_inference_scheduler_tpu.ops import pallas_ssm

    assert pallas_ssm.use_kernel(state_dim, head_dim, platform="tpu",
                                 sharded=False)
    f32 = functools.partial(_sds, one_chip, dtype=jnp.float32)
    pool = (5, lanes + 1, heads, head_dim, state_dim)
    compiled = jax.jit(
        functools.partial(pallas_ssm.update_in_place, head_block=head_block),
        donate_argnums=(0,)
    ).lower(f32(pool), _sds(one_chip, (), jnp.int32),
            _sds(one_chip, (lanes,), jnp.int32), f32((lanes, heads)),
            f32((lanes, heads, head_dim)), f32((lanes, groups, state_dim)),
            f32((lanes, groups, state_dim))).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "ssm_state_update" in hlo
    # In place: the pool is the call's and the program's, no second one.
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 4 * math.prod(pool[1:]))


@pytest.mark.parametrize("tokens", [512, 1024])
def test_grouped_relu2_experts_compile_on_a_held_range(one_chip, tokens):
    """Nemotron-3-Super's routed experts (1024 -> 2688 -> 1024, not gated),
    128 of 512 held, 22 a token: the one-operand relu-squared epilogue and
    the buffer of T x 22 + 128 x 128 rows, at the cell's grouped buckets."""
    bf16 = functools.partial(_sds, one_chip, dtype=jnp.bfloat16)
    held, k, z, f = 128, 22, 1024, 2688
    lp = {"w1": bf16((5, held, z, f)), "w2": bf16((5, held, f, z))}
    compiled = jax.jit(
        lambda lp, x, idx, gates: pallas_moe.grouped_experts(
            lp, x, idx, gates, held, layer=jnp.asarray(3, jnp.int32),
            first=256, gated=False)
    ).lower(lp, bf16((tokens, z)), _sds(one_chip, (tokens, k), jnp.int32),
            _sds(one_chip, (tokens, k), jnp.float32)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 2
    assert "moe_grouped_relu2" in hlo and "moe_grouped_swiglu" not in hlo


@pytest.mark.parametrize("rows,held,k,d_model,d_ff,gated", [
    # The decode buckets of the four cells that hold a range of their
    # router's experts (chipbench/configs), at their full batch and at the
    # two lanes of a ramp.
    (64, 16, 12, 6144, 2048, True),      # longcat-flash-omni-cut
    (2, 16, 12, 6144, 2048, True),
    (32, 16, 8, 7168, 2048, True),       # deepseek-v3.2-exp-cut
    (64, 32, 8, 5120, 1536, True),       # dots3-note-prev-cut
    (64, 128, 22, 1024, 2688, False),    # nemotron-3-super-cut's latent
])
def test_chosen_experts_compile_at_the_held_range_cells_widths(
        one_chip, rows, held, k, d_model, d_ff, gated):
    """Dense over the chosen experts: the grouped matmul twice with one row
    tile of the step's rows (padded to a bf16 tile's 16), the weights stacked
    over layers and read in place."""
    bf16 = functools.partial(_sds, one_chip, dtype=jnp.bfloat16)
    lp = {"w1": bf16((3, held, d_model, d_ff)),
          "w2": bf16((3, held, d_ff, d_model)),
          **({"w3": bf16((3, held, d_model, d_ff))} if gated else {})}
    compiled = jax.jit(
        lambda lp, x, local, gates: pallas_moe.chosen_experts(
            lp, x, local, gates, held, layer=jnp.asarray(2, jnp.int32),
            gated=gated)
    ).lower(lp, bf16((rows, d_model)), _sds(one_chip, (rows, k), jnp.int32),
            _sds(one_chip, (rows, k), jnp.float32)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 2
    assert ("moe_grouped_swiglu" if gated else "moe_grouped_relu2") in hlo
    # Nothing the size of a layer's weights is made: the largest temporary
    # is the tiles' outputs, held x rows x the wider width.
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 8 * held * max(rows, 16) * max(d_model, d_ff))


@pytest.mark.parametrize("d_model,d_ff,n_experts,top_k,tokens", [
    # Mixtral-8x7B, the one MoE model registered, at the two prefill buckets
    # the rule hands to the grouped form in mixtral-8x7b-cut.batch-full. Its
    # weight blocks ([4096, 1024] twice, double-buffered: 32 MiB) pass the
    # compiler's default scoped limit of 16 MiB, which the call raises.
    (MIXTRAL_8X7B.d_model, MIXTRAL_8X7B.d_ff, MIXTRAL_8X7B.n_experts, 2, 512),
    (MIXTRAL_8X7B.d_model, MIXTRAL_8X7B.d_ff, MIXTRAL_8X7B.n_experts, 2, 1024),
    # Many small experts (the OLMoE-like shape ROADMAP R2 plans).
    (2048, 1024, 64, 8, 512),
    (2048, 1024, 64, 8, 1024),
])
def test_grouped_moe_compiles(one_chip, d_model, d_ff, n_experts, top_k,
                              tokens):
    bf16 = functools.partial(_sds, one_chip, dtype=jnp.bfloat16)
    lp = {"router": bf16((d_model, n_experts)),
          "w1": bf16((n_experts, d_model, d_ff)),
          "w3": bf16((n_experts, d_model, d_ff)),
          "w2": bf16((n_experts, d_ff, d_model))}
    compiled = jax.jit(
        lambda lp, x: pallas_moe.moe_ffn_grouped(lp, x, n_experts, top_k)
    ).lower(lp, bf16((1, tokens, d_model))).compile()
    # Two grouped matmuls: x.[w1|w3] with the SwiGLU, then .w2.
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_moe_tiles_come_from_the_shapes():
    """One rule for the kernel's tiles (what fits VMEM, the fewest re-reads
    of the rows) and one for the form: widths pick_tiles cannot tile are
    widths use_grouped never hands to the kernel."""
    rows = 2 * 1024 + 8 * pallas_moe.ROW_TILE
    for k_dim, n_dim, n_rhs in ((4096, 14336, 2), (14336, 4096, 1)):
        tk, tn = pallas_moe.pick_tiles(rows, k_dim, n_dim, n_rhs, 2)
        assert k_dim % tk == 0 and n_dim % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        assert pallas_moe._vmem_bytes(
            rows // pallas_moe.ROW_TILE, pallas_moe.ROW_TILE, tk, tn, n_rhs,
            2, k_dim // tk) <= pallas_moe.VMEM_BUDGET_BYTES
    # Mixtral's up-projection runs whole-K (no f32 scratch) on wide N tiles.
    assert pallas_moe.pick_tiles(rows, 4096, 14336, 2, 2) == (4096, 1024)
    # No 128-multiple divides 200.
    with pytest.raises(ValueError, match="no tile"):
        pallas_moe.pick_tiles(rows, 128, 200, 2, 2)
    facts = dict(n_experts=8, experts_per_token=2, platform="tpu",
                 sharded=False)
    assert not pallas_moe.use_grouped(1024, d_model=128, d_ff=200, **facts)
    assert pallas_moe.use_grouped(1024, d_model=128, d_ff=256, **facts)


# ---------- learned sparse attention (DeepSeek-V3.2's block) ----------

def _dsa_cut(n_layers=2):
    """The cell's configuration at its published widths and fewer layers (the
    dense one and one expert layer: every kind), forms as the engine binds
    them on a TPU."""
    import json
    import types

    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "deepseek-v3.2-exp-cut.json")
    with open(path) as f:
        doc = json.load(f)
    doc["num_hidden_layers"] = n_layers
    return dataclasses.replace(
        config_from_hf(types.SimpleNamespace(**doc), name="dsa-cut"),
        index_impl="kernel", expanded_impl="kernel")


def _array_elements(hlo):
    """(elements, its text) of every array type the compiled program names."""
    found = {}
    for m in re.finditer(r"\b(?:f32|bf16|s32|u32|pred|s8|u8)\[([\d,]+)\]", hlo):
        found[m.group(0)] = math.prod(int(d) for d in m.group(1).split(","))
    return found


def test_no_window_of_the_selecting_block_holds_a_heads_by_queries_by_rows(
        one_chip):
    """A 1,024-token window over a 16k prefix at 128 heads and 64 indexer
    heads: the per-head scores of either would be [64 or 128, 1024, 17408]
    (4.6 / 9.1 GB in f32). Both stay in VMEM a tile at a time (ops/
    pallas_dsa.py: the indexer's kernel and the window's attention with its
    running softmax): the compiled program names no array of 64 x 1,024 x
    17,408 elements, and its temporaries are the rows' keys and values for
    all heads (2 x 17,408 x 128 x 128 bf16 = 1.14 GB) and little else."""
    from llm_d_inference_scheduler_tpu.kvcache import state
    from llm_d_inference_scheduler_tpu.kvcache.pages import PageGeometry
    from llm_d_inference_scheduler_tpu.models import mla

    m = dataclasses.replace(_dsa_cut(), moe_impl="grouped")
    geom = PageGeometry.for_engine(m, 4, 18432)
    dt = jnp.dtype(m.dtype)
    cache = state.Cache(
        _sds(one_chip, geom.shape, dt), None, None, None,
        slots=_sds(one_chip, (1,), jnp.int32),
        held=_sds(one_chip, (), jnp.int32),
        idx=_sds(one_chip, geom.index_shape, dt))
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: mla.init_params(m, k), jax.random.key(0)))
    S, prior = 1024, 1024
    one = _sds(one_chip, (1,), jnp.int32)
    compiled = jax.jit(lambda p, *a: mla.prefill_with_prefix(p, m, *a),
                       donate_argnums=(4,)).lower(
        params, _sds(one_chip, (1, S), jnp.int32), one, one, cache, None,
        _sds(one_chip, (1, geom.max_blocks_per_seq), jnp.int32),
        _sds(one_chip, (1, prior), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "dsa_index_scores_window" in hlo and "dsa_window_attention" in hlo
    rows = prior * geom.block + S
    too_large = {t: n for t, n in _array_elements(hlo).items()
                 if n >= 64 * S * rows}
    assert not too_large, too_large
    # What IS whole: a query's scores over the rows, [1, 1024, 17408] f32.
    assert any(n == S * rows for n in _array_elements(hlo).values())
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 29


@pytest.mark.parametrize("moe_impl", ["dense", "chosen"])
def test_the_selecting_blocks_decode_step_compiles_with_both_kernels(
        one_chip, moe_impl):
    """One decode step of 32 lanes over a table 1,152 blocks wide: the
    indexer's kernel over the lanes' key pages (read by the block table, not
    gathered), the selection, and the masked kernel over the latent pages,
    with neither pool made anew. In the form the cell's decode buckets trace
    (``chosen``: the held experts a lane chose) the experts' weights reach
    the kernel whole beside the scan: no layer's 1.4 GB of them is copied."""
    from llm_d_inference_scheduler_tpu.kvcache import pages as kvpages
    from llm_d_inference_scheduler_tpu.kvcache import state
    from llm_d_inference_scheduler_tpu.models import mla

    m = dataclasses.replace(_dsa_cut(), moe_impl=moe_impl)
    batch = 32
    geom = kvpages.PageGeometry.for_engine(m, batch, 18432)
    dt = jnp.dtype(m.dtype)
    cache = state.Cache(
        _sds(one_chip, geom.shape, dt), None, None, None,
        slots=_sds(one_chip, (batch,), jnp.int32),
        held=_sds(one_chip, (), jnp.int32),
        read=_sds(one_chip, (), jnp.int32),
        idx=_sds(one_chip, geom.index_shape, dt))
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: mla.init_params(m, k), jax.random.key(0)))
    lanes = _sds(one_chip, (batch,), jnp.int32)
    compiled = jax.jit(lambda p, *a: mla.decode_step(
        p, m, *a, attention_fn=functools.partial(
            kvpages.latent_decode_attention, kernel=True)),
        donate_argnums=(3,)).lower(
        params, lanes, lanes, cache, None,
        _sds(one_chip, (batch, geom.max_blocks_per_seq), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "dsa_index_scores_decode" in hlo
    assert "dsa_paged_decode_attention" in hlo
    assert "mla_paged_decode_attention" not in hlo
    assert ("moe_grouped_swiglu" in hlo) == (moe_impl == "chosen")
    for pool in (geom.shape, geom.index_shape):
        shape = "bf16[" + ",".join(map(str, pool)) + "]"
        made = [ln.strip()[:160] for ln in hlo.splitlines()
                if re.search(r"=\s*" + re.escape(shape), ln)
                and "parameter(" not in ln and "bitcast(" not in ln
                and "scatter" not in ln and "dynamic-update-slice" not in ln
                and "fusion(" not in ln and "get-tuple-element(" not in ln]
        assert not made, made
    # No lane's keys are gathered (32 x 18,432 x 128 bf16 would be 151 MB a
    # layer): the step's temporaries are the dense parts' and the scores'.
    gathered = "bf16[" + ",".join(map(str, (
        batch, geom.max_blocks_per_seq, geom.block, geom.index_dim))) + "]"
    assert gathered not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# ---------- window and full latent attention in one model ----------

def _dots3_cut():
    """The cell's configuration at its published widths (the dense layer,
    one full and three window expert layers), forms as the engine binds them
    on a TPU."""
    import json
    import types

    from llm_d_inference_scheduler_tpu.models import bind
    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "dots3-note-prev-cut.json")
    with open(path) as f:
        doc = json.load(f)
    return bind(config_from_hf(types.SimpleNamespace(**doc), name="dots3-cut"),
                platform="tpu").mcfg


def _dots3_cache(one_chip, m, geom, rows):
    from llm_d_inference_scheduler_tpu.kvcache import state

    dt = jnp.dtype(m.dtype)
    return state.Cache(
        _sds(one_chip, geom.shape, dt), None, None, None,
        slots=_sds(one_chip, (rows,), jnp.int32),
        held=_sds(one_chip, (), jnp.int32),
        idx=_sds(one_chip, geom.index_shape, dt),
        win=_sds(one_chip, geom.window.shape, dt),
        wt=_sds(one_chip, (rows, geom.max_blocks_per_seq), jnp.int32))


def test_the_window_decode_kernel_compiles_at_the_cells_widths(one_chip):
    """64 lanes, 64 heads over rows stored 1,152 wide, a window of 513 under
    a table 1,152 entries wide: the walk starts at the aligned group of the
    window's first page, read out of the whole table by the kernel (no gather
    ahead of it), in stages of 16 pages as 33 + 7 entries allow."""
    from llm_d_inference_scheduler_tpu.ops import pallas_latent_attention as la

    lanes, heads, dk, width = 64, 64, 1088, 1152
    bf16 = jnp.bfloat16
    args = (_sds(one_chip, (lanes, heads, dk), bf16),
            _sds(one_chip, (3, 3505, 16, width), bf16),
            _sds(one_chip, (), jnp.int32),
            _sds(one_chip, (lanes, 1152), jnp.int32),
            _sds(one_chip, (lanes,), jnp.int32),
            _sds(one_chip, (lanes, dk), bf16))
    compiled = jax.jit(functools.partial(
        la.swa_latent_decode_attention_pallas, value_dim=1024, scale=0.0625,
        window=513)).lower(*args).compile()
    hlo = compiled.as_text()
    assert "swa_latent_decode_attention" in hlo and "tpu_custom_call" in hlo
    assert f"s32[{lanes * 1152}]" in hlo and "gather" not in hlo
    assert la.window_pages(16, 513, 8) == 40 and "bf16[2,16,16,1152]" in str(
        jax.make_jaxpr(functools.partial(
            la.swa_latent_decode_attention_pallas, value_dim=1024,
            scale=0.0625, window=513))(
                *(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_mixed_models_decode_step_compiles_and_copies_no_pool(one_chip):
    """One decode step of 64 lanes: the full layers' indexer and masked walk,
    the window layers' walk over their own pool by their own table, and none
    of the three pools made anew."""
    from llm_d_inference_scheduler_tpu.kvcache import pages as kvpages
    from llm_d_inference_scheduler_tpu.models import mla

    m = _dots3_cut()
    assert (m.index_impl, m.swa_impl) == ("kernel", "kernel")
    batch = 64
    geom = kvpages.PageGeometry.for_engine(m, batch, 18432)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: mla.init_params(m, k), jax.random.key(0)))
    lanes = _sds(one_chip, (batch,), jnp.int32)
    compiled = jax.jit(lambda p, *a: mla.decode_step(
        p, m, *a, attention_fn=functools.partial(
            kvpages.latent_decode_attention, kernel=True)),
        donate_argnums=(3,)).lower(
        params, lanes, lanes, _dots3_cache(one_chip, m, geom, batch), None,
        _sds(one_chip, (batch, geom.max_blocks_per_seq), jnp.int32)).compile()
    hlo = compiled.as_text()
    for op in ("dsa_index_scores_decode", "dsa_paged_decode_attention",
               "swa_latent_decode_attention"):
        assert op in hlo, op
    assert "mla_paged_decode_attention" not in hlo
    for pool in (geom.shape, geom.index_shape, geom.window.shape):
        shape = "bf16[" + ",".join(map(str, pool)) + "]"
        made = [ln.strip()[:160] for ln in hlo.splitlines()
                if re.search(r"=\s*" + re.escape(shape), ln)
                and "parameter(" not in ln and "bitcast(" not in ln
                and "scatter" not in ln and "dynamic-update-slice" not in ln
                and "fusion(" not in ln and "get-tuple-element(" not in ln]
        assert not made, made
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_a_window_of_the_mixed_model_compiles_with_both_kinds_kernels(
        one_chip):
    """A 1,024-token window over a 16k prefix: the full layers score and
    select over 17,408 rows, the window layers attend over the 32 pages that
    end where the window starts and its own rows (1,536 rows), each a tile at
    a time; the program fits beside the cell's weights and pools."""
    from llm_d_inference_scheduler_tpu.kvcache.pages import PageGeometry
    from llm_d_inference_scheduler_tpu.models import mla

    m = dataclasses.replace(_dots3_cut(), moe_impl="grouped")
    geom = PageGeometry.for_engine(m, 64, 18432)
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: mla.init_params(m, k), jax.random.key(0)))
    S, prior = 1024, 1024
    one = _sds(one_chip, (1,), jnp.int32)
    compiled = jax.jit(lambda p, *a: mla.prefill_with_prefix(p, m, *a),
                       donate_argnums=(4,)).lower(
        params, _sds(one_chip, (1, S), jnp.int32), one, one,
        _dots3_cache(one_chip, m, geom, 1), None,
        _sds(one_chip, (1, geom.max_blocks_per_seq), jnp.int32),
        _sds(one_chip, (1, prior), jnp.int32)).compile()
    hlo = compiled.as_text()
    for op in ("dsa_index_scores_window", "dsa_window_attention",
               "swa_window_attention"):
        assert op in hlo, op
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3 << 29
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9


# ---- window and full K/V attention in one model (SmallThinker's widths) ------

@pytest.mark.parametrize("window", [0, 4096])
def test_the_kv_kernels_compile_at_seven_heads_a_group(one_chip, window):
    """28 query heads on 4 KV heads of 128 (7 a group: no multiple of 8, and
    the query block is not padded), 32 lanes under a table 1,024 entries wide:
    the full layers' walk, and the window layers' from the aligned group of
    the window's first page (read out of the whole table by the kernel, no
    gather ahead of it; stages of 32 pages either way), each with a flag a
    group of 8 entries beside its table."""
    from llm_d_inference_scheduler_tpu.ops import pallas_paged_attention as pa

    lanes, heads, kv, d = 32, 28, 4, 128
    bf16 = jnp.bfloat16
    blocks = 10065 if window else 32769
    pool = _sds(one_chip, (6 if window else 2, blocks, 16, kv, d), bf16)
    cur = _sds(one_chip, (lanes, kv, d), bf16)
    args = (_sds(one_chip, (lanes, heads, d), bf16), pool, pool,
            _sds(one_chip, (), jnp.int32),
            _sds(one_chip, (lanes, 1024), jnp.int32),
            _sds(one_chip, (lanes,), jnp.int32), cur, cur)
    fn = (functools.partial(pa.swa_paged_decode_attention_kernel,
                            window=window) if window
          else pa.paged_decode_attention_pallas)
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert ("swa_paged_decode_attention" in hlo) == bool(window)
    assert f"s32[{lanes * 1024}]" in hlo and f"s32[{lanes * 128}]" in hlo
    assert "gather" not in hlo
    traced = str(jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)))
    assert "bf16[2,32,16,4,128]" in traced
    # A stage is computed a KV head apart in the pool's dtype: no f32 array
    # of a stage's tile, as it lies or as rows, is in the program (a head's
    # rows pass through f32 words on their way out of a pair), and the
    # products read the stage's rows stacked by head.
    assert "f32[32,16,4,128]" not in traced and "f32[2048,128]" not in traced
    assert "f32[4,512,128]" not in traced
    assert "f32[512,128]" in traced and "bf16[4,512,128]" in traced
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("S", [1024, 128, 16])
def test_the_continuation_windows_kernel_compiles_and_copies_no_pool(
        one_chip, S):
    """A window's 28 query heads (1,024 of them, and the cell's narrower
    window buckets) against BOTH kinds' stacked pools at the cell's sizes,
    the sequence's whole table and the 256 pages a band of 4,096 reaches:
    one custom call that is handed the pools as they lie — no ``copy`` of a
    pool's shape, which XLA's banded form paid once a program for each V
    pool, and no gather — with stages of 32 pages and a program's
    temporaries a few query-sized arrays."""
    from llm_d_inference_scheduler_tpu.ops import pallas_paged_attention as pa

    heads, kv, d = 28, 4, 128
    bf16 = jnp.bfloat16
    shapes = ((2, 32769, 16, kv, d), (6, 10065, 16, kv, d))
    full, near = (_sds(one_chip, shape, bf16) for shape in shapes)
    one = _sds(one_chip, (1,), jnp.int32)
    own = _sds(one_chip, (1, S, kv, d), bf16)
    args = (_sds(one_chip, (1, S, heads, d), bf16), own, own, full, full,
            near, near, _sds(one_chip, (), jnp.bool_),
            _sds(one_chip, (), jnp.int32),
            _sds(one_chip, (1, 1024), jnp.int32),
            _sds(one_chip, (1, 256), jnp.int32), one, one, one)
    fn = functools.partial(pa.kv_window_prefill_attention, window=4096)
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "kv_window_prefill_attention" in hlo
    for shape in shapes:
        dims = ",".join(map(str, shape))
        assert f"bf16[{dims}]" in hlo
        assert not re.search(rf"= bf16\[{dims}\]\S* copy\(", hlo)
    assert "gather" not in hlo
    assert "s32[1280]" in hlo and "s32[160]" in hlo
    assert "bf16[2,32,16,4,128]" in str(jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("tokens", [512, 1024])
def test_grouped_reglu_experts_compile_at_the_cells_widths(one_chip, tokens):
    """64 experts of 2,560 x 768, 6 a token, the router's choices made ahead
    of the attention: the two-operand ReGLU epilogue under its own op name,
    at the cell's grouped prefill buckets."""
    e, k, d, f = 64, 6, 2560, 768
    bf16 = functools.partial(_sds, one_chip, dtype=jnp.bfloat16)
    lp = {"w1": bf16((e, d, f)), "w3": bf16((e, d, f)), "w2": bf16((e, f, d))}
    assert pallas_moe.use_grouped(tokens, n_experts=e, experts_per_token=k,
                                  d_model=d, d_ff=f, platform="tpu",
                                  interpret=False, sharded=False)
    compiled = jax.jit(lambda lp, x, idx, gates: pallas_moe.grouped_experts(
        lp, x, idx, gates, e, reglu=True)).lower(
        lp, bf16((tokens, d)), _sds(one_chip, (tokens, k), jnp.int32),
        _sds(one_chip, (tokens, k), jnp.float32)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 2
    assert "moe_grouped_reglu" in hlo and "moe_grouped_swiglu" not in hlo


# ---- Mamba-1 state beside one-KV-head attention (AI21-Jamba2-3B's widths) ----

@pytest.mark.parametrize("lanes,channel_block", [(2, None), (64, None),
                                                 (64, 1280)])
def test_ssm1_state_update_compiles_at_16_by_5120(one_chip, lanes,
                                                  channel_block):
    """The decode step of a Mamba-1 layer in place in the pool's second
    layout, 26 layers of 65 slots of [16, 5120] f32: a lane's tile whole, and
    in channel blocks."""
    from llm_d_inference_scheduler_tpu.ops import pallas_ssm

    assert pallas_ssm.use_kernel(5120, 16, platform="tpu", sharded=False)
    f32 = functools.partial(_sds, one_chip, dtype=jnp.float32)
    pool = (26, 65, 16, 5120)
    compiled = jax.jit(
        functools.partial(pallas_ssm.update1_in_place,
                          channel_block=channel_block),
        donate_argnums=(0,)
    ).lower(f32(pool), _sds(one_chip, (), jnp.int32),
            _sds(one_chip, (lanes,), jnp.int32), f32((lanes, 5120)),
            f32((lanes, 5120)), f32((lanes, 16)), f32((lanes, 16)),
            f32((16, 5120)), f32((5120,))).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "ssm1_state_update" in hlo
    # In place: the pool is the call's and the program's, no second one.
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 4 * math.prod(pool[2:]) * 65)


@pytest.mark.parametrize("rows", [1024, 128, 16])
def test_ssm1_selective_scan_compiles_at_16_by_5120(one_chip, rows):
    """A prompt window's recurrence, the state tile of a channel block
    resident over the rows: the cell's largest window, one lane tile of rows,
    and the smallest bucket (one block of rows that is no lane tile)."""
    from llm_d_inference_scheduler_tpu.ops import pallas_ssm

    f32 = functools.partial(_sds, one_chip, dtype=jnp.float32)
    compiled = jax.jit(pallas_ssm.selective_scan).lower(
        f32((1, rows, 5120)), f32((1, rows, 5120)), f32((1, rows, 16)),
        f32((1, rows, 16)), f32((16, 5120)), f32((5120,)),
        f32((1, 16, 5120))).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "ssm1_selective_scan" in hlo
    # dt, x and y a row: nothing the size of (exp(dt A), dt B x), 16 times
    # that, is made.
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 4 * 4 * rows * 5120 + (1 << 20))


def test_the_paged_walk_compiles_at_one_kv_head_and_twenty_query_heads(
        one_chip):
    """20 query heads on ONE KV head of 128, 64 lanes under a table 320
    entries wide over the cell's two attention layers' 20,481 pages. A bf16
    pool [.., 16, 1, 128] lies padded to two heads in HBM and Mosaic refuses
    to slice the padded dim, so the pages keep the head twice
    (``ModelConfig.kv_heads_kept``) and the walk is the two-KV-head program
    at ten query heads a group, not padded."""
    from llm_d_inference_scheduler_tpu.models.configs import ModelConfig

    lanes, heads, d = 64, 20, 128
    kv = ModelConfig(name="j", vocab_size=8, d_model=2560, n_layers=1,
                     n_heads=20, n_kv_heads=1, d_ff=8,
                     layer_pattern="A").kv_heads_kept
    assert kv == 2
    bf16 = jnp.bfloat16
    pool = _sds(one_chip, (2, 20481, 16, kv, d), bf16)
    cur = _sds(one_chip, (lanes, kv, d), bf16)
    args = (_sds(one_chip, (lanes, heads, d), bf16), pool, pool,
            _sds(one_chip, (), jnp.int32),
            _sds(one_chip, (lanes, 320), jnp.int32),
            _sds(one_chip, (lanes,), jnp.int32), cur, cur)
    compiled = paged_decode_attention_pallas.lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "gather" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_jamba_decode_step_compiles_and_copies_no_pool(one_chip):
    """One decode chunk step at AI21-Jamba2-3B's widths, two Mamba-1 layers
    and one attention layer with their dense FFNs, 64 lanes on the cell's
    pools, as a ``lax.scan`` of two steps: both kernels are in the program,
    and neither the state pool nor the page pool is made anew."""
    from llm_d_inference_scheduler_tpu.kvcache import state
    from llm_d_inference_scheduler_tpu.kvcache.pages import PageGeometry
    from llm_d_inference_scheduler_tpu.models import bind, hybrid
    from llm_d_inference_scheduler_tpu.models.configs import ModelConfig

    m = bind(ModelConfig(
        name="jamba-cut", vocab_size=65536, d_model=2560, n_layers=3,
        n_heads=20, n_kv_heads=1, d_ff=8192, norm_eps=1e-6,
        layer_pattern="SAS", ssm_state=16, ssm_dt_rank=160, ssm_expand=2),
        platform="tpu").mcfg
    assert (m.ssm_impl, m.ssm_scan_impl) == ("kernel", "kernel")
    batch = 64
    geom = PageGeometry.for_engine(m, batch, 5120)
    sgeom = geom.state
    dt = jnp.dtype(m.dtype)
    pages = _sds(one_chip, geom.shape, dt)
    cache = state.Cache(
        pages, pages, _sds(one_chip, sgeom.ssm_shape, jnp.float32),
        _sds(one_chip, sgeom.conv_shape, dt),
        slots=_sds(one_chip, (batch,), jnp.int32),
        held=_sds(one_chip, (), jnp.int32))
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: hybrid.init_params(m, k), jax.random.key(0)))

    def chunk(params, tokens, positions, cache, tables):
        def step(carry, _):
            tokens, positions, cache = carry
            logits, cache, _ = hybrid.decode_step(
                params, m, tokens, positions, cache, None, tables,
                attention_fn=functools.partial(decode_attention, kernel=True))
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, positions + 1, cache), nxt

        (_, _, cache), toks = jax.lax.scan(
            step, (tokens, positions, cache), None, length=2)
        return toks, cache

    compiled = jax.jit(chunk, donate_argnums=(3,)).lower(
        params, _sds(one_chip, (batch,), jnp.int32),
        _sds(one_chip, (batch,), jnp.int32), cache,
        _sds(one_chip, (batch, geom.max_blocks_per_seq), jnp.int32)
    ).compile()
    hlo = compiled.as_text()
    assert "ssm1_state_update" in hlo and "paged_decode_attention" in hlo
    for pool, dtype in ((sgeom.ssm_shape, "f32"), (geom.shape, "bf16")):
        shape = f"{dtype}[" + ",".join(map(str, pool)) + "]"
        made = [ln.strip()[:160] for ln in hlo.splitlines()
                if re.search(r"=\s*" + re.escape(shape), ln)
                and "parameter(" not in ln and "bitcast(" not in ln
                and "scatter" not in ln and "dynamic-update-slice" not in ln
                and "fusion(" not in ln and "get-tuple-element(" not in ln]
        assert not made, made
    # Under one set of the 64 lanes' rows of one layer.
    assert (compiled.memory_analysis().temp_size_in_bytes
            < batch * 4 * math.prod(sgeom.ssm_shape[2:]))


def _lfm2_cut(n_layers=3, pattern="CQC"):
    """LFM2-8B-A1B's widths, a few layers of it: one dense FFN, then routed
    experts."""
    from llm_d_inference_scheduler_tpu.models.configs import ModelConfig

    return ModelConfig(
        name="lfm2-cut", vocab_size=65536, d_model=2048, n_layers=n_layers,
        n_heads=32, n_kv_heads=8, d_ff=7168, rope_theta=1e6, qk_norm=True,
        n_experts=32, experts_per_token=4, first_k_dense=1, moe_d_ff=1792,
        layer_pattern=pattern, ssm_conv=3)


def test_a_pool_of_64_wide_heads_lies_padded_where_a_kernel_reads_it(
        one_chip):
    """Why two heads of 64 lie side by side a page row
    (``ModelConfig.kv_heads_a_row``): in the row-major layout a Pallas call's
    operand has, a bf16 pool ``[.., 16, 8, 64]`` takes twice the model's
    bytes (its minor dim padded to a tile's 128 lanes), and the same values
    as ``[.., 16, 4, 128]`` take them once."""
    from jax.experimental.layout import Format, Layout

    n = 2049
    for shape, padding in (((4, n, 16, 8, 64), 2), ((4, n, 16, 4, 128), 1)):
        fmt = Format(Layout(major_to_minor=(0, 1, 2, 3, 4)), one_chip)
        pool = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=fmt)
        compiled = jax.jit(lambda p: p.at[0, 0, 0].set(1),
                           out_shardings=fmt).lower(pool).compile()
        assert (compiled.memory_analysis().argument_size_in_bytes
                == padding * 2 * math.prod(shape))


@pytest.mark.parametrize("lanes,table_width", [(64, 288), (2, 288)])
def test_the_paged_walk_compiles_over_two_heads_of_64_a_row(
        one_chip, lanes, table_width):
    """32 query heads on 8 KV heads of 64, the cell's four attention layers'
    18,433 pages of two heads a row ([16, 4, 128]): the walk is the four-head
    program of 128 lanes, the query zeroed outside its own head's lanes
    ahead of it, and each row's own 64 lanes taken inside it. The call hands
    back two query heads a row of whole lanes, so what the block does next --
    the heads flattened ahead of the output projection -- is no operation:
    nothing but a bitcast reads the kernel's result (a [lanes, 32, 64]
    result was re-laid out by a copy that named the kernel, which the
    benchmark's roofline reader counted as a call: it read 145)."""
    m = _lfm2_cut()
    assert (m.kv_heads_a_row, m.head_dim) == (2, 64)
    bf16 = jnp.bfloat16
    pool = _sds(one_chip, (4, 18433, 16, 4, 128), bf16)
    cur = _sds(one_chip, (lanes, 8, 64), bf16)

    def attend(*args):
        out = decode_attention(*args, kernel=True)
        assert out.shape == (lanes, 32, 64)
        return out.reshape(lanes, -1)       # as models/hybrid._out does

    compiled = jax.jit(attend).lower(
        _sds(one_chip, (lanes, 32, 64), bf16), pool, pool,
        _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (lanes, table_width), jnp.int32),
        _sds(one_chip, (lanes,), jnp.int32), cur, cur).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "gather" not in hlo
    readers = [ln.strip()[:200] for ln in hlo.splitlines()
               if re.search(r"\(.*%paged_decode_attention_pallas", ln)
               and "bitcast(" not in ln]
    assert not readers, readers
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_lfm2_decode_step_compiles_and_copies_no_pool(one_chip):
    """One decode chunk step at LFM2-8B-A1B's widths (a convolution layer
    with the dense FFN, an attention layer and a convolution layer with 32
    experts each), 64 lanes on the cell's pools, as a ``lax.scan`` of two
    steps: the paged walk is in the program, and neither the tails' pool nor
    the page pool is made anew."""
    from llm_d_inference_scheduler_tpu.kvcache import state
    from llm_d_inference_scheduler_tpu.kvcache.pages import PageGeometry
    from llm_d_inference_scheduler_tpu.models import bind, hybrid

    bound = bind(_lfm2_cut(), platform="tpu")
    batch = 64
    m = bound.model_for(batch)
    assert m.moe_impl == "dense" and bound.model_for(1024).moe_impl == "grouped"
    geom = PageGeometry.for_engine(m, batch, 4608)
    assert geom.shape == (1, 18433, 16, 4, 128) and geom.token_bytes == 2048
    sgeom = geom.state
    assert sgeom.ssm_shape is None and sgeom.conv_shape == (2, 65, 4096)
    dt = jnp.dtype(m.dtype)
    pages = _sds(one_chip, geom.shape, dt)
    cache = state.Cache(
        pages, pages, None, _sds(one_chip, sgeom.conv_shape, dt),
        slots=_sds(one_chip, (batch,), jnp.int32),
        held=_sds(one_chip, (), jnp.int32))
    params = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: hybrid.init_params(m, k), jax.random.key(0)))

    def chunk(params, tokens, positions, cache, tables):
        def step(carry, _):
            tokens, positions, cache = carry
            logits, cache, _ = hybrid.decode_step(
                params, m, tokens, positions, cache, None, tables,
                attention_fn=functools.partial(decode_attention, kernel=True))
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, positions + 1, cache), nxt

        (_, _, cache), toks = jax.lax.scan(
            step, (tokens, positions, cache), None, length=2)
        return toks, cache

    compiled = jax.jit(chunk, donate_argnums=(3,)).lower(
        params, _sds(one_chip, (batch,), jnp.int32),
        _sds(one_chip, (batch,), jnp.int32), cache,
        _sds(one_chip, (batch, geom.max_blocks_per_seq), jnp.int32)
    ).compile()
    hlo = compiled.as_text()
    assert "paged_decode_attention" in hlo
    for pool, dtype in ((sgeom.conv_shape, "bf16"), (geom.shape, "bf16")):
        shape = f"{dtype}[" + ",".join(map(str, pool)) + "]"
        made = [ln.strip()[:160] for ln in hlo.splitlines()
                if re.search(r"=\s*" + re.escape(shape), ln)
                and "parameter(" not in ln and "bitcast(" not in ln
                and "scatter" not in ln and "dynamic-update-slice" not in ln
                and "fusion(" not in ln and "get-tuple-element(" not in ln]
        assert not made, made
    # No pool padded to 128 lanes from a minor dim of 64.
    assert not re.search(r"bf16\[\d+,18433,16,8,64\]", hlo)
    # The walk's result goes to the output projection as it lies: a copy or
    # reshape that read it would be counted as a call of the kernel.
    readers = [ln.strip()[:200] for ln in hlo.splitlines()
               if re.search(r"= \S+ (copy|reshape|transpose)\(.*"
                            r"%paged_decode_attention_pallas", ln)]
    assert not readers, readers
