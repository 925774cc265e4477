"""The KV page pool's one owner (`kvcache/`) against plain NumPy, and the guard
that no other module of the package knows the pool's layout."""

from __future__ import annotations

import ast
import dataclasses
import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from llm_d_inference_scheduler_tpu.kvcache import pages, wire
from llm_d_inference_scheduler_tpu.kvcache.pages import PageGeometry
from llm_d_inference_scheduler_tpu.models.configs import QWEN3_4B, ModelConfig

PKG = pathlib.Path(pages.__file__).resolve().parents[1]

SMALL = ModelConfig(name="kv-small", vocab_size=64, d_model=64, n_layers=3,
                    n_heads=4, n_kv_heads=2, head_dim_override=32, d_ff=128,
                    dtype="float32", kv_block_size=16)


def _geom(n_blocks=13, width=4):
    return PageGeometry.for_model(SMALL, n_blocks, width)


def _random_pool(geom, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=geom.shape).astype(np.float32),
            rng.normal(size=geom.shape).astype(np.float32))


# ---------- geometry ----------

def test_geometry_of_the_benchmarks_engine():
    g = PageGeometry.for_engine(QWEN3_4B, max_batch=16, max_model_len=2048)
    assert g.n_blocks == 2049 and g.max_blocks_per_seq == 128
    assert g.shape == (36, 2049, 16, 8, 128) and g.dtype == "bfloat16"
    assert g.block_bytes == 2 * 36 * 16 * 8 * 128 * 2
    assert g.pool_bytes == 4_834_197_504                  # 4.83 GB the pair
    assert (g.blocks_for(1), g.blocks_for(16), g.blocks_for(17)) == (1, 1, 2)
    assert pages.TRASH_BLOCK == 0


@pytest.mark.parametrize("hbm_kv_blocks, want", [(0, 1 + 4 * 8), (9, 9),
                                                 (1, 2)])
def test_geometry_block_count(hbm_kv_blocks, want):
    """Room for every lane beside the trash block; a given count as given;
    never fewer than the trash block and one to use."""
    g = PageGeometry.for_engine(SMALL, max_batch=4, max_model_len=8 * 16 - 3,
                                hbm_kv_blocks=hbm_kv_blocks)
    assert g.n_blocks == want and g.max_blocks_per_seq == 8


def test_geometry_is_frozen_and_for_model_takes_a_dtype():
    g = PageGeometry.for_model(SMALL, 5, dtype="bfloat16")
    assert (g.n_blocks, g.max_blocks_per_seq, g.dtype) == (5, 4, "bfloat16")
    assert PageGeometry.for_model(SMALL, 0).n_blocks == 2
    with pytest.raises(dataclasses.FrozenInstanceError):
        g.n_blocks = 6


# ---------- allocation and the sharding rule ----------

def test_alloc_on_one_device():
    g = _geom()
    dev = jax.devices()[1]
    k, v = pages.alloc(g, device=dev)
    assert k.shape == v.shape == g.shape and k.dtype == jnp.float32
    assert k.devices() == v.devices() == {dev}
    assert not np.asarray(k).any() and not np.asarray(v).any()


@pytest.mark.parametrize("axes, shape, want", [
    (("dp", "tp", "ep"), (2, 2, 1), P(None, None, None, "tp", None)),
    (("pp", "tp", "ep"), (3, 2, 1), P("pp", None, None, "tp", None)),
], ids=["serve-mesh", "stage-ring"])
def test_sharding_rule_and_sharded_alloc(axes, shape, want):
    n = int(np.prod(shape))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)
    assert pages.page_spec(mesh) == want
    g = _geom()
    k, v = pages.alloc(g, sharding=pages.page_sharding(mesh))
    assert k.shape == g.shape and k.sharding.spec == want == v.sharding.spec
    local = list(g.shape)
    if "pp" in axes:
        local[0] //= 3                   # a stage's layers
    local[3] //= 2                       # tp's share of the KV heads
    assert k.addressable_shards[0].data.shape == tuple(local)


# ---------- writes and reads ----------

def _numpy_attention(q, k_pool, v_pool, layer, tables, seq_lens, cur_k, cur_v):
    """One token a lane against its cached rows and its own K/V, in f64."""
    B, H, D = q.shape
    group = H // k_pool.shape[3]
    out = np.zeros((B, H, D))
    for b in range(B):
        n = int(seq_lens[b]) - 1
        rows_k = k_pool[layer, tables[b]].reshape(-1, *k_pool.shape[3:])[:n]
        rows_v = v_pool[layer, tables[b]].reshape(-1, *v_pool.shape[3:])[:n]
        ks = np.concatenate([rows_k, cur_k[b][None]]).astype(np.float64)
        vs = np.concatenate([rows_v, cur_v[b][None]]).astype(np.float64)
        for h in range(H):
            logit = ks[:, h // group] @ q[b, h].astype(np.float64) / D ** 0.5
            w = np.exp(logit - logit.max())
            out[b, h] = (w / w.sum()) @ vs[:, h // group]
    return out


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_token_write_then_decode_attention(kernel):
    """A token a lane goes where the table says; a lane pointed at the trash
    block lands there; attention over the written pool matches NumPy."""
    g = _geom()
    k_np, v_np = _random_pool(g)
    rng = np.random.default_rng(1)
    tables = np.array([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]], np.int32)
    positions = np.array([37, 0, 5], np.int32)      # third lane is padding
    B = len(positions)
    k_cur = rng.normal(size=(g.n_layers, B, g.n_kv_heads,
                             g.head_dim)).astype(np.float32)
    v_cur = rng.normal(size=k_cur.shape).astype(np.float32)

    blocks, slots = pages.token_slots(jnp.asarray(k_np), jnp.asarray(tables),
                                      jnp.asarray(positions))
    assert np.asarray(blocks).tolist() == [3, 5, 0]
    assert np.asarray(slots).tolist() == [5, 0, 5]
    k_out, v_out = pages.write(jnp.asarray(k_np), jnp.asarray(v_np),
                               jnp.asarray(k_cur), jnp.asarray(v_cur),
                               blocks, slots)
    want_k, want_v = k_np.copy(), v_np.copy()
    for b, (blk, slot) in enumerate([(3, 5), (5, 0), (0, 5)]):
        want_k[:, blk, slot] = k_cur[:, b]
        want_v[:, blk, slot] = v_cur[:, b]
    np.testing.assert_array_equal(np.asarray(k_out), want_k)
    np.testing.assert_array_equal(np.asarray(v_out), want_v)

    q = rng.normal(size=(B, SMALL.n_heads, g.head_dim)).astype(np.float32)
    seq_lens = positions + 1
    layer = 2
    got = pages.decode_attention(
        jnp.asarray(q), jnp.asarray(k_np), jnp.asarray(v_np), layer,
        jnp.asarray(tables), jnp.asarray(seq_lens),
        jnp.asarray(k_cur[layer]), jnp.asarray(v_cur[layer]),
        kernel=kernel, interpret=True)
    want = _numpy_attention(q, k_np, v_np, layer, tables, seq_lens,
                            k_cur[layer], v_cur[layer])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("start", [None, [16, 21]], ids=["from-0", "offset"])
def test_sequence_write_then_read_prefix(start):
    """A run of tokens a sequence lands at start + t in its own blocks,
    padding in block 0 and nowhere else; read_prefix hands a layer's rows
    back in order."""
    g = _geom()
    k_np, v_np = _random_pool(g, seed=2)
    rng = np.random.default_rng(3)
    tables = np.array([[1, 2, 3, 4], [9, 10, 11, 12]], np.int32)
    lens = np.array([19, 7], np.int32)
    S = 32
    k_new = rng.normal(size=(g.n_layers, 2, S, g.n_kv_heads,
                             g.head_dim)).astype(np.float32)
    v_new = rng.normal(size=k_new.shape).astype(np.float32)
    k_out, v_out = pages.write_sequences(
        jnp.asarray(k_np), jnp.asarray(v_np), jnp.asarray(k_new),
        jnp.asarray(v_new), jnp.asarray(tables), jnp.asarray(lens),
        None if start is None else jnp.asarray(start, jnp.int32))
    k_out, v_out = np.asarray(k_out), np.asarray(v_out)

    want_k, want_v = k_np.copy(), v_np.copy()
    for b in range(2):
        for t in range(int(lens[b])):
            pos = t + (0 if start is None else start[b])
            blk, slot = tables[b, pos // g.block], pos % g.block
            want_k[:, blk, slot] = k_new[:, b, t]
            want_v[:, blk, slot] = v_new[:, b, t]
    real = np.ones(g.n_blocks, bool)
    real[pages.TRASH_BLOCK] = False
    np.testing.assert_array_equal(k_out[:, real], want_k[:, real])
    np.testing.assert_array_equal(v_out[:, real], want_v[:, real])
    # Padding rows (t >= lens) went to the trash block's slot 0 and changed it.
    assert (k_out[:, pages.TRASH_BLOCK, 0] != k_np[:, pages.TRASH_BLOCK, 0]).any()
    np.testing.assert_array_equal(k_out[:, pages.TRASH_BLOCK, 1:],
                                  k_np[:, pages.TRASH_BLOCK, 1:])

    for layer in range(g.n_layers):
        k_rows, v_rows = pages.read_prefix(
            jnp.asarray(k_out[layer]), jnp.asarray(v_out[layer]),
            jnp.asarray(tables[:1, :2]))
        assert k_rows.shape == (1, 2 * g.block, g.n_kv_heads, g.head_dim)
        np.testing.assert_array_equal(
            np.asarray(k_rows)[0], want_k[layer, [1, 2]].reshape(
                -1, g.n_kv_heads, g.head_dim))
        np.testing.assert_array_equal(
            np.asarray(v_rows)[0], want_v[layer, [1, 2]].reshape(
                -1, g.n_kv_heads, g.head_dim))
    assert pages.block_size(jnp.asarray(k_np)) == g.block
    assert np.asarray(pages.layer_indices(jnp.asarray(k_np))).tolist() \
        == [0, 1, 2]


# ---------- export, the wire, import ----------

def test_gather_encode_decode_scatter_reproduces_the_pool():
    g = _geom()
    k_np, v_np = _random_pool(g, seed=4)
    ids = np.array([5, 2, 9, 0], np.int32)          # padded to 4: tail -> trash
    k_st, v_st = pages.gather_blocks(jnp.asarray(k_np), jnp.asarray(v_np),
                                     jnp.asarray(ids))
    body, headers = wire.encode(k_st, v_st, real_blocks=3)
    assert headers == {"x-kv-num-blocks": "4", "x-kv-real-blocks": "3",
                       "x-kv-dtype": "float32",
                       "x-kv-shape": "[3, 4, 16, 2, 32]"}
    assert body == np.asarray(k_st).tobytes() + np.asarray(v_st).tobytes()
    headers["x-kv-seq-len"] = "40"
    k_in, v_in, seq_len, real_nb = wire.decode(g, headers, body, n_alloc=3)
    assert (seq_len, real_nb) == (40, 3)

    k_pad, v_pad = pages.pad_blocks(k_in, v_in, g.max_blocks_per_seq)
    target = np.zeros(g.max_blocks_per_seq, np.int32)
    target[:real_nb] = [7, 8, 11]                    # the importer's own blocks
    empty_k, empty_v = pages.alloc(g)
    k_out, v_out = pages.scatter_blocks(empty_k, empty_v, jnp.asarray(target),
                                        jnp.asarray(k_pad), jnp.asarray(v_pad))
    np.testing.assert_array_equal(np.asarray(k_out)[:, [7, 8, 11]],
                                  k_np[:, [5, 2, 9]])
    np.testing.assert_array_equal(np.asarray(v_out)[:, [7, 8, 11]],
                                  v_np[:, [5, 2, 9]])
    untouched = [b for b in range(1, g.n_blocks) if b not in (7, 8, 11)]
    assert not np.asarray(k_out)[:, untouched].any()


def test_chunks_join_to_the_whole_export():
    """Chunks of consecutive blocks, each its own K-then-V body, join to what
    one encode of all the blocks gives — for more than one layer too, where
    the blocks axis is not the outermost."""
    g = _geom()
    k_np, v_np = _random_pool(g, seed=5)
    ids = np.array([3, 4, 6, 1, 10], np.int32)
    whole = pages.gather_blocks(k_np, v_np, ids)
    chunks = []
    for lo, hi in [(0, 2), (2, 3), (3, 5)]:
        body, headers = wire.encode(*pages.block_range(*whole, lo, hi),
                                    chunk=True)
        assert headers == {"x-kv-chunk-shape": f"[3, {hi - lo}, 16, 2, 32]",
                           "x-kv-dtype": "float32"}
        chunks.append(({**headers, "x-kv-chunk-blocks": str(hi - lo)}, body))
    assert wire.join_chunks(chunks) == wire.encode(*whole)
    # A sim exporter's chunks carry block counts and no bytes.
    assert wire.join_chunks([({"x-kv-chunk-blocks": "2"}, b"")]) is None
    assert wire.join_chunks([]) is None


_GOOD = (3, 4, 16, 2, 32)


@pytest.mark.parametrize("shape, seq_len, real_nb, n_alloc, message", [
    (_GOOD[:4], 40, 3, 3, "bad kv shape"),
    ((2, 4, 16, 2, 32), 40, 3, 3, "kv geometry mismatch"),
    ((3, 4, 8, 2, 32), 40, 3, 3, "kv geometry mismatch"),
    ((3, 4, 16, 4, 32), 40, 3, 3, "kv geometry mismatch"),
    ((3, 4, 16, 2, 64), 40, 3, 3, "kv geometry mismatch"),
    (_GOOD, 40, 0, 3, "real block count 0 outside padded 4"),
    (_GOOD, 40, 5, 5, "real block count 5 outside padded 4"),
    ((3, 8, 16, 2, 32), 40, 3, 3, "exported blocks exceed budget"),
    (_GOOD, 40, 3, 2, "exported blocks exceed budget"),
    (_GOOD, 0, 3, 3, "kv seq_len 0 outside exported blocks"),
    (_GOOD, 49, 3, 3, "kv seq_len 49 outside exported blocks"),
])
def test_validate_refuses(shape, seq_len, real_nb, n_alloc, message):
    with pytest.raises(ValueError, match=message):
        wire.validate(_geom(), shape, seq_len, real_nb, n_alloc)


def test_validate_accepts_and_decode_checks_the_payload():
    g = _geom()
    assert wire.validate(g, _GOOD, 48, 3, 3) == (4, 3)
    assert wire.validate(g, _GOOD, 64, None, 4) == (4, 4)   # not padded
    headers = {"x-kv-shape": "[3, 4, 16, 2, 32]", "x-kv-seq-len": "48",
               "x-kv-dtype": "float32", "x-kv-real-blocks": "3"}
    n = 2 * int(np.prod(_GOOD)) * 4
    wire.decode(g, headers, bytes(n), n_alloc=3)
    with pytest.raises(ValueError, match="kv payload size"):
        wire.decode(g, headers, bytes(n - 4), n_alloc=3)
    with pytest.raises(KeyError):
        wire.decode(g, {"x-kv-shape": "[3, 4, 16, 2, 32]"}, bytes(n), 3)


def test_param_bytes():
    assert wire.param_bytes({"kv_shape": [3, 4, 16, 2, 32],
                             "kv_dtype": "bfloat16"}) == 2 * 12288 * 2
    assert wire.param_bytes({"kv_shape": [3, 4, 16, 2, 32],
                             "kv_dtype": "float32"}) == 2 * 12288 * 4
    assert wire.param_bytes({}) is None


# ---------- the state pool beside the pages ----------

def _state_cache(slots=3):
    from llm_d_inference_scheduler_tpu.kvcache import state

    model = dataclasses.replace(SMALL, layer_pattern="M*M", n_layers=3,
                                ssm_heads=4, ssm_head_dim=8, ssm_state=16,
                                ssm_groups=2)
    geom = PageGeometry.for_engine(model, slots, 64)
    sgeom = geom.state
    assert sgeom == state.StateGeometry.for_engine(model, slots)
    cache, none = pages.alloc(geom)
    return state, model, sgeom, geom, cache, none


def test_state_pool_is_indexed_by_slot_with_one_row_that_is_nobodys():
    state, model, sgeom, geom, cache, none = _state_cache()
    assert none is None
    assert (sgeom.n_layers, geom.n_layers) == (2, 1)    # M, M and one *
    assert cache.ssm.shape == sgeom.ssm_shape == (2, 4, 4, 8, 16)
    assert cache.conv.shape == sgeom.conv_shape == (2, 4, 3 * (32 + 2 * 2 * 16))
    assert cache.ssm.dtype == jnp.float32 and cache.conv.dtype == jnp.float32
    assert cache.k.shape == geom.shape and cache.slots is None
    assert sgeom.slot_bytes == 2 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert sgeom.pool_bytes == 4 * sgeom.slot_bytes

    rng = np.random.default_rng(1)
    new_ssm = [rng.normal(size=(2, 4, 8, 16)).astype(np.float32)
               for _ in range(2)]
    new_conv = [rng.normal(size=(2, 3, 96)).astype(np.float32)
                for _ in range(2)]
    stepped = state.write(state.at_slots(cache, [2, 0]), new_ssm, new_conv)
    kept, held, zero, _ = state.take_counts(stepped)
    assert kept.slots is None and kept.held is None and int(held) == 0
    assert zero is None          # only a router with zero-compute outputs
    for layer in range(2):
        got_ssm, got_conv = state.read(state.at_slots(kept, [0, 2]), layer)
        np.testing.assert_array_equal(np.asarray(got_ssm),
                                      new_ssm[layer][::-1])
        np.testing.assert_array_equal(
            np.asarray(got_conv), new_conv[layer][::-1].reshape(2, -1))
    # Slot 1 and nobody's row (3) were not touched; padding lanes write the
    # latter and leave every slot as it was.
    assert not np.asarray(kept.ssm[:, [1, 3]]).any()
    padded = state.write(state.at_slots(kept, [3, 3]), new_ssm, new_conv)
    np.testing.assert_array_equal(np.asarray(padded.ssm[:, :3]),
                                  np.asarray(kept.ssm[:, :3]))


def test_a_first_window_starts_its_slots_state_and_writes_its_pages():
    state, model, sgeom, geom, cache, _ = _state_cache()
    rng = np.random.default_rng(2)
    fresh = state.Fresh(
        k=jnp.asarray(rng.normal(size=(1, 1, 16, 2, 32)), jnp.float32),
        v=jnp.asarray(rng.normal(size=(1, 1, 16, 2, 32)), jnp.float32),
        ssm=jnp.asarray(rng.normal(size=(2, 1, 4, 8, 16)), jnp.float32),
        conv=jnp.asarray(rng.normal(size=(2, 1, 3, 96)), jnp.float32),
        held=jnp.asarray(7, jnp.int32))
    dirty = dataclasses.replace(cache, ssm=cache.ssm + 5.0)   # a past tenant
    got, none = pages.write_sequences(
        state.at_slots(dirty, [1]), None, fresh, None,
        jnp.asarray([[2, 0, 0, 0]], jnp.int32), jnp.asarray([11]))
    assert none is None and int(got.held) == 7
    np.testing.assert_array_equal(np.asarray(got.ssm[:, 1]),
                                  np.asarray(fresh.ssm[:, 0]))
    np.testing.assert_array_equal(np.asarray(got.ssm[:, 0]),
                                  np.asarray(dirty.ssm[:, 0]))
    np.testing.assert_array_equal(np.asarray(got.k[0, 2, :11]),
                                  np.asarray(fresh.k[0, 0, :11]))
    assert not np.asarray(got.k[0, 2, 11:]).any()


# ---------- which op attends ----------

@pytest.mark.parametrize("head_dim, asked, interpret, platform, sharded, want", [
    (128, None, False, "tpu", False, True),
    (128, None, False, "cpu", False, False),
    (128, None, False, "tpu", True, False),
    (64, None, False, "tpu", False, False),
    (128, False, False, "tpu", False, False),
    (128, True, False, "cpu", False, True),       # by name: as asked
    (64, True, True, "cpu", False, True),         # interpreted: any head_dim
])
def test_use_kernel(head_dim, asked, interpret, platform, sharded, want):
    assert pages.use_kernel(head_dim, asked=asked, interpret=interpret,
                            platform=platform, sharded=sharded) is want


def test_use_kernel_asked_for_and_impossible_is_an_error():
    with pytest.raises(ValueError, match="not lane-aligned"):
        pages.use_kernel(64, asked=True, interpret=False, platform="tpu",
                         sharded=False)


# ---------- the layout has one owner ----------

_POOLS = {"k_pages", "v_pages", "kp", "vp"}
_WIDTHS = {"kv_block_size", "n_kv_heads", "head_dim"}
_HEADERS = {"x-kv-shape", "x-kv-chunk-shape", "x-kv-dtype", "x-kv-real-blocks"}


def _name(node) -> str | None:
    return getattr(node, "id", None) or getattr(node, "attr", None)


def layout_knowledge(source: str) -> list[str]:
    """Where a module reaches into the page pool's layout: it indexes,
    scatters into or reads a shape axis of a pool; it writes the pool's shape
    out of the model's widths; it spells a geometry header of the wire."""
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Subscript):
            v = node.value
            if _name(v) in _POOLS:
                found.append(f"line {node.lineno}: {_name(v)}[...]")
            elif isinstance(v, ast.Attribute) and _name(v.value) in _POOLS \
                    and (v.attr == "at" or (
                        v.attr == "shape"
                        and isinstance(node.slice, ast.Constant))):
                found.append(f"line {node.lineno}: {_name(v.value)}.{v.attr}[...]")
        elif isinstance(node, ast.Tuple) \
                and _WIDTHS <= {_name(e) for e in node.elts}:
            found.append(f"line {node.lineno}: a shape tuple of the widths")
        elif isinstance(node, ast.Constant) and node.value in _HEADERS:
            found.append(f"line {node.lineno}: {node.value!r}")
    return found


@pytest.mark.parametrize("module", [
    "engine/core.py", "engine/server.py", "engine/config.py",
    "engine/kv_shards.py", "models/llama.py", "parallel/pp_serve.py",
    "parallel/serve.py", "engine/sim.py"])
def test_layout_is_known_to_kvcache_alone(module):
    assert layout_knowledge((PKG / module).read_text()) == []


def test_the_guard_sees_what_it_guards_against():
    assert len(layout_knowledge(
        "k = k_pages[:, i]\n"
        "vp = vp.at[:, b, s].set(x)\n"
        "n = self.v_pages.shape[2]\n"
        "s = (c.n_layers, n, c.kv_block_size, c.n_kv_heads, c.head_dim)\n"
        "h = {'x-kv-shape': 1}\n"
        "ok = (k_pages.shape, k_pages.dtype, c.head_dim, 'x-kv-seq-len')\n"
    )) == 5


def test_kvcache_imports_only_downwards():
    """ops <- kvcache <- models, parallel <- engine."""
    for path in (PKG / "kvcache").glob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom):
                top = (node.module or "").split(".")[0]
                assert node.level <= 2, (path.name, node.lineno)
                if node.level == 2:
                    assert top == "ops", (path.name, node.lineno, node.module)
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    assert not alias.name.startswith(
                        "llm_d_inference_scheduler_tpu"), (path.name, alias.name)
