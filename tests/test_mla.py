"""The latent-attention family (models/mla.py, Kimi-VL-A3B's language model)
at a small size with widths aligned to nothing: the block against the plain
reference, the two forms of its attention against each other, its page pool,
its kernel against the XLA gather, the engine end to end, and everything the
engine refuses for it."""

import asyncio
import dataclasses
import functools
import importlib.util
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.kvcache import pages
from llm_d_inference_scheduler_tpu.models import configs, family, llama, mla
from llm_d_inference_scheduler_tpu.models.convert_hf import (
    config_from_hf, convert_state_dict)
import latent_table_cases as table_cases
from llm_d_inference_scheduler_tpu.ops import (pallas_latent_attention,
                                               pallas_moe)
from llm_d_inference_scheduler_tpu.ops.attention import (
    latent_paged_decode_attention)
from llm_d_inference_scheduler_tpu.ops.pallas_latent_attention import (
    latent_paged_decode_attention_pallas, pages_per_stage, run_pages,
    table_runs)

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = dataclasses.replace(configs.get_config("tiny-mla"), dtype="float32")
# float32 on both sides, different summation order (test_reference.py's).
TOL = dict(rtol=2e-4, atol=2e-4)


def _reference():
    path = REPO / "chipbench" / "configs" / "reference_mla_moe.py"
    spec = importlib.util.spec_from_file_location("reference_mla_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(cfg):
    return dict(n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                experts_per_token=cfg.experts_per_token,
                routed_scaling_factor=cfg.routed_scaling_factor)


@functools.lru_cache(maxsize=None)
def _fixture():
    params = mla.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(9), (2, 48), 0, CFG.vocab_size)
    logits, (rows, _), routes = mla.forward(params, CFG, tokens, want_kv=True,
                                            want_routes=True)
    return params, tokens, logits, rows, routes


def _pool_with(rows, n_tokens):
    """A latent pool holding the first ``n_tokens`` rows of both sequences,
    and their block tables."""
    geom = pages.PageGeometry.for_engine(CFG, 2, 64)
    pool, none = pages.alloc(geom)
    assert none is None
    tables = jnp.asarray([[3, 1, 5, 0], [2, 6, 4, 0]], jnp.int32)
    bucket = -(-n_tokens // 16) * 16    # a prefill hands over whole pages
    pool, _ = pages.write_sequences(pool, None, rows[:, :, :bucket], None,
                                    tables, jnp.asarray([n_tokens] * 2))
    return pool, tables


# ---------- the block against the plain reference ----------

def test_family_picks_the_module():
    assert family(CFG) is mla
    assert family(configs.get_config("tiny-moe")) is llama
    assert CFG.head_dim == 28 and CFG.latent_dim == 32


@pytest.mark.parametrize("row", [0, 1])
def test_forward_matches_the_plain_reference(row):
    params, tokens, logits, _, routes = _fixture()
    ref = _reference()
    hidden, ref_routes = ref.hidden(params, tokens[row], q_block=7,
                                    **_sizes(CFG))
    np.testing.assert_allclose(np.asarray(logits[row]),
                               np.asarray(ref.logits(params, hidden)), **TOL)
    np.testing.assert_allclose(
        np.asarray(ref.forward(params, tokens[row], **_sizes(CFG))),
        np.asarray(logits[row]), **TOL)
    ours = routes.reshape(routes.shape[0], 2, -1, CFG.experts_per_token)
    assert (np.sort(np.asarray(ours[:, row]), -1)
            == np.sort(np.asarray(ref_routes), -1)).all()


def _without(part):
    """(params, cfg) of a program that leaves ``part`` of the mathematics
    out, by making it the identity in what the program is given."""
    params, *_ = _fixture()
    cfg = CFG
    layers = dict(params["layers"])
    if part == "selection bias":
        layers["router_bias"] = jnp.zeros_like(layers["router_bias"])
    elif part == "latent norm weight":
        layers["kv_norm"] = jnp.ones_like(layers["kv_norm"])
    elif part == "shared expert":
        layers["w2s"] = jnp.zeros_like(layers["w2s"])
    elif part == "gate scale":
        cfg = dataclasses.replace(CFG, routed_scaling_factor=1.0)
    elif part == "rotation of the rope part":
        cfg = dataclasses.replace(CFG, rope_theta=1e30)   # every angle ~ 0
    return {**params, "layers": layers}, cfg


@pytest.mark.parametrize("part", [
    "selection bias", "latent norm weight", "shared expert", "gate scale",
    "rotation of the rope part"])
def test_the_comparison_sees_each_part(part):
    """The drawn weights make every part of the layer matter: a program
    without it misses the reference by far more than the tolerance."""
    params, tokens, logits, *_ = _fixture()
    changed, cfg = _without(part)
    ours, _ = mla.forward(changed, cfg, tokens[:1])
    assert float(jnp.abs(ours[0] - logits[0]).max()) > 50 * TOL["atol"]


def test_gates_are_normalised_scores_times_the_scale():
    params, tokens, *_ = _fixture()
    lp = {k: v[0] for k, v in params["layers"].items()}
    h = params["embed"][tokens[0]]
    idx, gates = mla.route(CFG, lp, h)
    scores = jax.nn.sigmoid(h @ lp["router"])
    want_idx = np.argsort(-np.asarray(scores + lp["router_bias"]), -1)[:, :3]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want_idx, -1)).all()
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(gates),
        chosen / chosen.sum(-1, keepdims=True) * CFG.routed_scaling_factor,
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.446, rtol=1e-5)


# ---------- prefill, pages, decode ----------

@pytest.mark.parametrize("kernel", [False, True])
def test_prefill_then_paged_decode_equals_the_full_forward(kernel):
    params, tokens, logits, rows, routes = _fixture()
    start = 21                                   # mid-page
    pool, tables = _pool_with(rows, start)
    attend = functools.partial(pages.latent_decode_attention, kernel=kernel,
                               interpret=kernel)
    step = jax.jit(functools.partial(mla.decode_step, attention_fn=attend,
                                     want_routes=True), static_argnums=1)
    for t in range(start, tokens.shape[1]):
        got, pool, none, chose = step(
            params, CFG, tokens[:, t], jnp.full((2,), t, jnp.int32), pool,
            None, tables)
        assert none is None
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(logits[:, t]), **TOL)
        whole = routes.reshape(routes.shape[0], 2, -1, 3)[:, :, t]
        assert (np.sort(np.asarray(chose), -1)
                == np.sort(np.asarray(whole), -1)).all()
    # Every row the steps wrote is the row the whole prefill computed.
    want, _ = _pool_with(rows, tokens.shape[1])
    np.testing.assert_allclose(np.asarray(pool[:, 1:]),
                               np.asarray(want[:, 1:]), **TOL)


@pytest.mark.parametrize("form", ["xla", "kernel_interpret"])
def test_a_prompt_prefilled_in_windows_equals_one_prefilled_whole(form):
    """With the scores whole, and with the tiled kernel interpreted (the form
    an engine on a TPU binds, models/binding.py)."""
    params, tokens, logits, rows, _ = _fixture()
    cfg = dataclasses.replace(CFG, expanded_impl=form)
    pool, tables = _pool_with(rows, 16)          # the first window, plain
    row = tables[:1]
    for lo, n, bucket, prior in [(16, 16, 16, 1), (32, 7, 16, 2)]:
        window = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
            tokens[0, lo:lo + n])
        got, pool, none = mla.prefill_with_prefix(
            params, cfg, window, jnp.asarray([n]), jnp.asarray([lo]), pool,
            None, row, row[:, :prior])
        assert none is None
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(logits[0, lo + n - 1]), **TOL)
    want, _ = _pool_with(rows, 39)
    blocks = np.asarray(row[0, :3])
    np.testing.assert_allclose(
        np.asarray(pool[:, blocks]).reshape(3, 48, -1)[:, :39],
        np.asarray(want[:, blocks]).reshape(3, 48, -1)[:, :39], **TOL)


def _window_masks(case):
    """(B, S, T, mask [B, S, T]) as ``forward`` (a prefill: causal, and the
    padding mask of a batch) and ``prefill_with_prefix`` (the prior bucket's
    rows up to ``prefix_len``, then the window's own up to ``suffix_len``)
    build them."""
    if case in ("first_window", "padded_batch_of_two"):
        B, S = (1, 40) if case == "first_window" else (2, 48)
        pos = np.broadcast_to(np.arange(S), (B, S))
        mask = pos[:, :, None] >= pos[:, None, :]
        if B == 2:
            mask = mask & (np.arange(S)[None] < np.array([[48], [19]])
                           )[:, None, :]
        return B, S, S, mask
    S, suffix, T, prefix = {"partly_dead_prior": (40, 33, 8192, 5000),
                            "one_token_suffix": (16, 1, 256, 200)}[case]
    pos = prefix + np.arange(S)[None]
    kv_pos = np.concatenate([np.arange(T)[None], pos], axis=1)
    valid = np.concatenate([np.arange(T)[None] < prefix,
                            np.arange(S)[None] < suffix], axis=1)
    return 1, S, T + S, (pos[:, :, None] >= kv_pos[:, None, :]) & valid[:, None]


def _both_forms(heads, case, dtype):
    """(tiled, whole, mask): models/mla.expanded_attention on one draw of
    queries, rows and ``W_kvb`` in the form a TPU engine binds (ops/
    pallas_dsa.py's tiles, interpreted) and in the plain form, as f32."""
    cfg = dataclasses.replace(CFG, n_heads=heads)
    B, S, T, mask = _window_masks(case)
    ks = jax.random.split(jax.random.key(heads + T), 4)
    lp = {"wkvb": (jax.random.normal(
        ks[0], (cfg.kv_lora_rank,
                heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)))
        * cfg.kv_lora_rank ** -0.5).astype(dtype)}
    q_nope = jax.random.normal(ks[1], (B, S, heads, cfg.qk_nope_head_dim),
                               dtype)
    q_rope = jax.random.normal(ks[2], (B, S, heads, cfg.qk_rope_head_dim),
                               dtype)
    rows = jax.random.normal(ks[3], (B, T, cfg.latent_dim), dtype)
    tiled, whole = (np.asarray(mla.expanded_attention(
        dataclasses.replace(cfg, expanded_impl=form), lp, q_nope, q_rope,
        rows, jnp.asarray(mask)), np.float32)
        for form in ("kernel_interpret", "xla"))
    assert tiled.shape == (B, S, heads * cfg.v_head_dim)
    return tiled, whole, mask


@pytest.mark.parametrize("case", ["first_window", "partly_dead_prior",
                                  "padded_batch_of_two", "one_token_suffix"])
@pytest.mark.parametrize("heads", [16, 64])      # Kimi's, LongCat's
def test_expanded_attention_tiled_equals_whole_where_nothing_selects(
        heads, case):
    """The form a TPU engine binds for every latent block against the plain
    form, under the masks the two entry points build: no head, row or window
    is left out, and a prior bucket's dead tiles change nothing."""
    got, want, mask = _both_forms(heads, case, jnp.float32)
    # A padded query that sees no row at all is nobody's: the plain form
    # averages every row there, the kernel writes zeros.
    seen = mask.any(-1)
    assert seen.sum() >= {"one_token_suffix": 16}.get(case, 33)
    np.testing.assert_allclose(got[seen], want[seen], rtol=2e-5, atol=2e-5)
    assert np.isfinite(got).all()


def test_expanded_attention_tiled_equals_whole_in_bf16():
    """At the cells' dtype: the kernel rounds the exponentials to the
    values' dtype and divides by their f32 sum afterwards, the plain form
    rounds the quotients; bf16's rounding apart."""
    got, want, mask = _both_forms(16, "partly_dead_prior", jnp.bfloat16)
    seen = mask.any(-1)
    assert np.abs(got[seen] - want[seen]).max() < 0.02


def test_expanded_attention_reads_its_own_form_and_names_its_kernel():
    """``index_impl`` is the indexer's: the expanded attention of a block
    that does not select takes the kernel by ``expanded_impl`` alone, under
    a name of its own in a device trace, which the decode kernel's metric
    (``mla_decode_roofline``: ``mla_paged_decode_attention``) does not
    match."""
    import json
    import re

    with open(REPO / "chipbench" / "layer_metrics"
              / "mla_decode_roofline.json") as f:
        decode_metric = json.load(f)["op_regex"]
    cfg = dataclasses.replace(CFG, n_heads=4)
    lp = {"wkvb": jnp.zeros((cfg.kv_lora_rank, 4 * (
        cfg.qk_nope_head_dim + cfg.v_head_dim)))}
    args = (lp, jnp.zeros((1, 8, 4, cfg.qk_nope_head_dim)),
            jnp.zeros((1, 8, 4, cfg.qk_rope_head_dim)),
            jnp.zeros((1, 8, cfg.latent_dim)), jnp.ones((1, 8, 8), bool))

    def traced(c):
        return str(jax.make_jaxpr(
            lambda *a: mla.expanded_attention(c, *a))(*args))

    tiled = traced(dataclasses.replace(cfg, expanded_impl="kernel_interpret"))
    assert "mla_window_attention" in tiled and "pallas_call" in tiled
    assert not re.search(decode_metric, tiled)
    assert "dsa_window_attention" not in tiled
    whole = traced(dataclasses.replace(cfg, index_impl="kernel_interpret"))
    assert "pallas_call" not in whole
    selecting = traced(dataclasses.replace(
        cfg, index_topk=4, expanded_impl="kernel_interpret"))
    assert ("dsa_window_attention" in selecting
            and "mla_window_attention" not in selecting)


@pytest.mark.parametrize("config,heads", [("kimi-vl-a3b-cut", 16),
                                          ("longcat-flash-omni-cut", 64)])
def test_the_window_microbenchmark_rehearses_on_the_cpu(config, heads, capsys,
                                                        monkeypatch):
    """scripts/microbench_decode.py --window at a cell's widths and a small
    window, the kernel interpreted: a line a (form, prior bucket), the tiled
    form within bf16's rounding of the whole one, and only the whole one
    writing scores."""
    import json

    spec = importlib.util.spec_from_file_location(
        "microbench_decode", REPO / "scripts" / "microbench_decode.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setattr("llm_d_inference_scheduler_tpu.utils.compile_cache."
                        "configure_compile_cache", lambda: "")
    bench.main(["--window", "--window-interpret", "--window-config", config,
                "--window-tokens", "32", "--window-priors", "0,8",
                "--window-live", "0.6", "--window-iters", "1"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [(ln["form"], ln["prior_blocks"]) for ln in lines] == [
        ("xla", 0), ("kernel_interpret", 0), ("xla", 8),
        ("kernel_interpret", 8)]
    for ln in lines:
        assert ln["component"] == "mla_window_attention"
        assert (ln["heads"], ln["window"]) == (heads, 32)
        assert ln["rows"] == ln["prior_blocks"] * 16 + 32
        if ln["form"] == "xla":
            assert ln["score_bytes"] == heads * 32 * ln["rows"] * 4
        else:
            assert ln["score_bytes"] == 0 and ln["max_err_vs_plain"] < 0.05
    assert lines[-1]["live_rows"] == int(128 * 0.6) + 32


def test_absorbed_attention_equals_expanded_on_the_same_cache():
    params, tokens, _, rows, _ = _fixture()
    lp = {k: v[1] for k, v in params["layers"].items()}
    t = 29
    h = jax.random.normal(jax.random.key(3), (2, CFG.d_model), jnp.float32)
    pos = jnp.full((2,), t, jnp.int32)
    from llm_d_inference_scheduler_tpu.ops import rope_table

    cos, sin = rope_table(pos, CFG.qk_rope_head_dim, CFG.rope_theta)
    q_nope, q_rope, cur = mla._project(CFG, lp, h, cos, sin)
    cached = rows[2, :, :t]                                   # [2, t, 32]
    seen = jnp.concatenate([cached, cur[:, None]], axis=1)
    expanded = mla.expanded_attention(
        CFG, lp, q_nope[:, None], q_rope[:, None], seen,
        jnp.ones((2, 1, t + 1), bool))[:, 0]

    def attend(q, cur_row):
        scores = jnp.einsum("bhd,btd->bht", q, seen) * mla._scale(CFG)
        return jnp.einsum("bht,btd->bhd", jax.nn.softmax(scores, -1),
                          seen[..., :CFG.kv_lora_rank])

    absorbed = mla.absorbed_attention(CFG, lp, q_nope, q_rope, cur, attend)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               rtol=1e-4, atol=1e-5)


# ---------- the kernel against the gather ----------

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("width,value_dim,block,table", [
    (32, 24, 16, 8), (576, 512, 16, 40)])
def test_latent_kernel_matches_the_gather_at_ragged_lengths(
        dtype, tol, width, value_dim, block, table):
    """Interpreted on the CPU: lanes of no cached token, of one page and a
    bit, of a whole stage and of several stages, the current token's column
    among the rows; the second of two layers read."""
    stored = -(-width // 128) * 128
    H, B, N = 5, 5, 1 + 5 * table
    key = jax.random.split(jax.random.key(11), 5)
    pool = jax.random.normal(key[0], (2, N, block, stored), jnp.float32)
    pool = pool.at[..., width:].set(0).astype(dtype)
    q = jax.random.normal(key[1], (B, H, width), jnp.float32).astype(dtype)
    cur = jax.random.normal(key[2], (B, width), jnp.float32).astype(dtype)
    tables = jax.random.permutation(key[3], jnp.arange(1, N)).reshape(
        B, table).astype(jnp.int32)
    P = pages_per_stage(block, stored, jnp.dtype(dtype).itemsize, table)
    lens = jnp.asarray([1, block + 4, P * block + 1, table * block,
                        2 * P * block - 3][:B], jnp.int32)
    lens = jnp.minimum(lens, table * block)
    kw = dict(value_dim=value_dim, scale=0.07)
    want = latent_paged_decode_attention(q, pool, 1, tables, lens, cur, **kw)
    got = latent_paged_decode_attention_pallas(q, pool, 1, tables, lens, cur,
                                               interpret=True, **kw)
    assert got.shape == (B, H, value_dim) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)
    # The lane with nothing cached attends to its own row alone.
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32),
        np.broadcast_to(np.asarray(cur[0, :value_dim], np.float32),
                        (H, value_dim)), rtol=tol, atol=tol)


@pytest.mark.parametrize("case", table_cases.CASES)
def test_latent_kernel_fetches_runs_of_adjacent_pages_as_one_copy(
        case, monkeypatch):
    """The kernel against the gather over every kind of table
    (tests/latent_table_cases.py): which groups it takes as one copy is what
    was counted by hand, and the result is the gather's either way. Rows
    past a lane's length and pages it does not own hold large values: they
    weigh nothing."""
    c = table_cases
    monkeypatch.setattr(pallas_latent_attention, "STAGE_VMEM_BYTES",
                        c.STAGE_VMEM_BYTES)
    assert pages_per_stage(c.BLOCK, 128, 4, c.WIDTH) == c.STAGE
    group = run_pages(c.STAGE)
    tables, lens = c.tables(case, group)
    runs = np.asarray(table_runs(jnp.asarray(tables), jnp.asarray(lens),
                                 c.BLOCK, group))
    np.testing.assert_array_equal(runs, c.runs_by_hand(tables, lens, group))
    if group == 8:
        assert runs.sum(axis=1).tolist() == c.RUNS[case]
    H, width, value_dim = 3, 72, 40
    key = jax.random.split(jax.random.key(21), 2)
    pool = c.pool_under(tables, lens, width, 128, seed=21)
    q = jax.random.normal(key[0], (len(lens), H, width), jnp.float32)
    cur = jax.random.normal(key[1], (len(lens), width), jnp.float32)
    args = (q, jnp.asarray(pool), 1, jnp.asarray(tables),
            jnp.maximum(jnp.asarray(lens), 1), cur)
    kw = dict(value_dim=value_dim, scale=0.11)
    want = latent_paged_decode_attention(*args, **kw)
    got = latent_paged_decode_attention_pallas(
        *args[:4], jnp.asarray(lens), cur, interpret=True, **kw)
    assert np.abs(np.asarray(want)).max() < 10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", table_cases.CASES)
def test_a_stages_fetch_waits_for_every_byte_it_starts(case):
    """stage_fetch alone, driven as the three kernels drive it (the next
    stage started before this one is waited for, two slots): a DMA semaphore
    counts bytes, the wait is for a region's bytes however many copies bring
    them, and after a lane's last stage both semaphores read zero again —
    no copy is left in flight, none is waited for twice. (Pages of 16 x 8
    values: the interpreter's semaphore is an int16.) What the stages held
    is the lane's cached pages and zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = table_cases
    group, W = run_pages(c.STAGE), 8
    tables, lens = c.tables(case, group)
    pool = c.pool_under(tables, lens, W, W, seed=5)

    def kernel(bt_ref, run_ref, sl_ref, pool_hbm, sems_ref, sum_ref, tile,
               sem):
        b = pl.program_id(0)
        n_pages = pl.cdiv(sl_ref[b] - 1, c.BLOCK)
        n_stages = pl.cdiv(n_pages, c.STAGE)
        start, wait = pallas_latent_attention.stage_fetch(
            bt_ref, run_ref, pool_hbm, tile, sem, lane=b, layer=1,
            n_pages=n_pages, max_blocks=c.WIDTH, group=group, zero_rest=True)

        @pl.when(n_stages > 0)
        def _prologue():
            start(0, 0)

        def stage(s, acc):
            slot = jax.lax.rem(s, 2)

            @pl.when(s + 1 < n_stages)
            def _next():
                start(s + 1, 1 - slot)

            wait(s, slot)
            return acc + jnp.sum(tile[slot])

        sum_ref[0, 0] = jax.lax.fori_loop(0, n_stages, stage, jnp.float32(0))
        sems_ref[0, 0] = pltpu.semaphore_read(sem.at[0])
        sems_ref[0, 1] = pltpu.semaphore_read(sem.at[1])

    def in_smem(width):
        return pl.BlockSpec((1, width), lambda b, *_: (b, 0),
                            memory_space=pltpu.SMEM)

    sems, sums = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(len(lens),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[in_smem(2), in_smem(1)],
            scratch_shapes=[pltpu.VMEM((2, c.STAGE, c.BLOCK, W), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((len(lens), 2), jnp.int32),
                   jax.ShapeDtypeStruct((len(lens), 1), jnp.float32)],
        interpret=True,
    )(jnp.asarray(tables).reshape(-1),
      table_runs(jnp.asarray(tables), jnp.asarray(lens), c.BLOCK,
                 group).reshape(-1), jnp.asarray(lens), jnp.asarray(pool))
    assert not np.asarray(sems).any()
    for lane, n in enumerate(lens):
        owned = tables[lane, :-(-max(int(n) - 1, 0) // c.BLOCK)]
        np.testing.assert_allclose(float(sums[lane, 0]),
                                   pool[1, owned].sum(), rtol=1e-4)


def test_latent_stage_comes_from_the_vmem_budget():
    assert pages_per_stage(16, 640, 2, 512) == 32     # the cell's shapes
    assert pages_per_stage(16, 640, 2, 8) == 8        # never past the table
    assert pages_per_stage(16, 128, 4, 3) == 2        # a power of two


# ---------- the latent page ----------

def test_latent_geometry_at_the_cells_sizes():
    model = types.SimpleNamespace(n_layers=9, kv_block_size=16, n_kv_heads=16,
                                  head_dim=192, dtype="bfloat16",
                                  latent_dim=576)
    geom = pages.PageGeometry.for_engine(model, 32, 8192)
    assert geom.shape == (9, 1 + 32 * 512, 16, 640)
    assert geom.row_width == 640 and geom.token_bytes == 1280
    assert geom.block_bytes == 9 * 16 * 1280
    assert geom.pool_bytes == 9 * 16385 * 16 * 1280
    assert 2.7e9 < geom.pool_bytes * 576 / 640 < geom.pool_bytes < 3.1e9
    # The K/V pair's numbers are what they were.
    kv = pages.PageGeometry.for_engine(configs.get_config("qwen3-4b"), 16, 2048)
    assert kv.latent_dim == 0 and kv.token_bytes == 2 * 8 * 128 * 2
    assert kv.pool_bytes == 2 * int(np.prod(kv.shape)) * 2


def test_latent_write_and_read_prefix_round_trip():
    _, _, _, rows, _ = _fixture()
    pool, tables = _pool_with(rows, 37)
    assert pool.shape[-1] == 128 and not np.asarray(pool[..., 32:]).any()
    for layer in range(3):
        got = pages.read_latent_prefix(pool, layer, tables[1:, :3], 32)
        assert got.shape == (1, 48, 32)
        np.testing.assert_array_equal(np.asarray(got[0, :37]),
                                      np.asarray(rows[layer, 1, :37]))
    # Pages wholly past a sequence's length go to the trash block: the
    # second sequence (length 0) and the first one's second page keep theirs.
    pool2, _ = pages.write_sequences(pool, None, -rows[:, :, :32], None,
                                     tables, jnp.asarray([3, 0]))
    for kept in (1, 2, 6, 4):
        np.testing.assert_array_equal(np.asarray(pool2[:, kept]),
                                      np.asarray(pool[:, kept]))
    np.testing.assert_array_equal(np.asarray(pool2[:, 3, :, :32]),
                                  -np.asarray(rows[:, 0, :16]))
    # One row a lane, a page at a time: the page's other rows stay.
    pool3, _ = pages.write(pool, None, -rows[:, :, 40], None,
                           jnp.asarray([5, 4]), jnp.asarray([8, 8]))
    np.testing.assert_array_equal(np.asarray(pool3[:, 5, 8, :32]),
                                  -np.asarray(rows[:, 0, 40]))
    np.testing.assert_array_equal(np.asarray(pool3[:, 5, :8]),
                                  np.asarray(pool[:, 5, :8]))
    np.testing.assert_array_equal(np.asarray(pool3[:, 5, 9:]),
                                  np.asarray(pool[:, 5, 9:]))


def test_a_latent_pool_is_not_sharded():
    geom = pages.PageGeometry.for_engine(CFG, 2, 64)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                             ("dp", "tp"))
    with pytest.raises(ValueError, match="latent page pool is not sharded"):
        pages.alloc(geom, sharding=pages.page_sharding(mesh))
    assert pages.use_kernel(geom.shape[-1], asked=None, interpret=False,
                            platform="tpu", sharded=False)


def test_models_mla_knows_no_pool_layout():
    from test_kvcache import PKG, layout_knowledge

    assert layout_knowledge((PKG / "models/mla.py").read_text()) == []


# ---------- the experts, both forms ----------

def test_grouped_experts_equal_dense_over_experts():
    cfg = dataclasses.replace(CFG, d_model=128, moe_d_ff=128)
    params = mla.init_params(cfg, jax.random.key(2), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(4), (1, 48), 0, cfg.vocab_size)
    dense, _ = mla.forward(params, cfg, tokens)
    grouped, _ = mla.forward(
        params, dataclasses.replace(cfg, moe_impl="grouped_interpret"), tokens)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense), **TOL)


def test_the_rule_and_the_tiles_at_the_published_widths():
    """64 experts of width 1408, 6 a token: grouped from 512 tokens up, on
    one path with Mixtral's; 1408 = 11 x 128 leaves the tiler two choices."""
    big = configs.get_config("kimi-vl-a3b")
    rule = functools.partial(
        pallas_moe.use_grouped, n_experts=big.n_experts,
        experts_per_token=big.experts_per_token, d_model=big.d_model,
        d_ff=big.moe_d_ff, platform="tpu", sharded=False)
    assert [rule(n) for n in (32, 256, 512, 1024)] == [False, False, True, True]
    assert not rule(1024, sharded=True)
    rows = 1024 * 6 + 64 * pallas_moe.ROW_TILE
    assert pallas_moe.pick_tiles(rows, 2048, 1408, 2, 2) == (2048, 1408)
    assert pallas_moe.pick_tiles(rows, 1408, 2048, 1, 2) == (1408, 2048)


# ---------- the published keys ----------

PUBLISHED = dict(
    vocab_size=163840, max_position_embeddings=131072, hidden_size=2048,
    intermediate_size=11264, moe_intermediate_size=1408, num_hidden_layers=27,
    num_attention_heads=16, n_shared_experts=2, n_routed_experts=64,
    routed_scaling_factor=2.446, kv_lora_rank=512, q_lora_rank=None,
    qk_rope_head_dim=64, v_head_dim=128, qk_nope_head_dim=128,
    topk_method="noaux_tc", n_group=1, topk_group=1, num_experts_per_tok=6,
    moe_layer_freq=1, first_k_dense_replace=1, norm_topk_prob=True,
    scoring_func="sigmoid", num_key_value_heads=16, hidden_act="silu",
    rms_norm_eps=1e-05, rope_theta=800000, rope_scaling=None,
    attention_bias=False, tie_word_embeddings=False)


@pytest.mark.parametrize("nested", ["dict", "object"])
def test_config_from_hf_reads_the_nested_language_model(nested):
    text = PUBLISHED if nested == "dict" else types.SimpleNamespace(**PUBLISHED)
    got = config_from_hf(types.SimpleNamespace(model_type="kimi_vl",
                                               text_config=text),
                         name="kimi-vl-a3b")
    assert got == configs.get_config("kimi-vl-a3b")
    assert got.moe_impl == "dense"


@pytest.mark.parametrize("key,value", [
    ("index_topk", 2048), ("n_group", 7), ("scoring_func", "softmax"),
    ("rope_scaling", {"type": "linear", "factor": 4}),
    ("norm_topk_prob", False)])
def test_config_from_hf_refuses_what_the_block_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf(types.SimpleNamespace(**{**PUBLISHED, key: value}))


def test_convert_state_dict_refuses_the_family():
    with pytest.raises(NotImplementedError, match="latent"):
        convert_state_dict({}, CFG)


# ---------- the engine ----------

@pytest.fixture
def served():
    """tiny-mla in float32 under a name of its own (greedy tokens of two
    programs are comparable in float32 only)."""
    name = "tiny-mla-f32"
    configs._REGISTRY[name] = dataclasses.replace(CFG, name=name)
    yield name
    del configs._REGISTRY[name]


@pytest.mark.parametrize("option,value", [
    ("tp_size", 2), ("ep_size", 2), ("pp_size", 2), ("dist_num_processes", 2),
    ("role", "prefill"), ("role", "decode")])
def test_engine_refuses_at_start_by_name(option, value):
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    with pytest.raises(ValueError, match=f"{option}={value}"):
        TpuEngine(EngineConfig(model="tiny-mla", backend="tpu", max_batch=2,
                               max_model_len=64, kv_events_port=0,
                               **{option: value}))


def test_engine_serves_through_chunked_prefill_prefix_cache_and_kernel(served):
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    long = [1] + [(j * 17) % 450 + 3 for j in range(150)]
    short = [1] + [(j * 5) % 450 + 3 for j in range(30)]
    base = dict(model=served, backend="tpu", max_batch=4, max_model_len=256,
                decode_chunk=4, kv_events_port=0, seed=7)

    async def serve(cfg):
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            async def one(rid, prompt, n):
                out = eng.submit(EngineRequest(
                    request_id=rid, prompt_token_ids=list(prompt),
                    max_tokens=n, temperature=0.0, ignore_eos=True))
                toks, cached = [], 0
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=300)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                        cached = max(cached, ev.cached_tokens or 0)
                    if ev.finish_reason is not None:
                        return toks, cached

            first = await asyncio.gather(one("L", long, 6), one("S", short, 12))
            again = await one("L2", long, 6)
            with pytest.raises(ValueError, match="latent"):
                eng.submit(EngineRequest(
                    request_id="pd", prompt_token_ids=short,
                    kv_transfer_params={"do_remote_decode": True}))
            def series(name, label):
                return {s.labels[label]: s.value
                        for m in eng.telemetry.registry.collect()
                        for s in m.samples if s.name == f"jetstream:{name}"}

            return (first, again,
                    series("mla_attention_tokens_total", "form"),
                    dict(eng.describe()["settings"],
                         table_groups=series("kv_table_groups_total", "kind"),
                         window_tokens=series(
                             "mla_window_attention_tokens_total", "form")))
        finally:
            await eng.stop()

    whole = asyncio.run(serve(EngineConfig(**base)))
    chunked = asyncio.run(serve(EngineConfig(
        **base, prefill_chunk=32, warmup=True, pallas_attention=True,
        pallas_interpret=True)))
    (lw, sw), aw, counted, settings = whole
    (lc, sc), ac, counted_c, settings_c = chunked
    assert (lc[0], sc[0]) == (lw[0], sw[0])          # the same tokens
    assert aw[0] == lw[0] and ac[0] == lc[0]
    assert aw[1] >= 144 and ac[1] >= 144             # the rerun hit the cache
    assert counted["expanded"] > 0 and counted["absorbed"] > 0
    assert counted_c["expanded"] > counted["expanded"]   # warm-up's ladder
    # Every expanded row under the form the engine bound: the scores whole
    # on the CPU, the tiled kernel where the engine interprets its kernels
    # (on a TPU: "kernel"), and /health says which.
    assert settings["window_tokens"] == {"xla": counted["expanded"]}
    assert settings_c["window_tokens"] == {"kernel": counted_c["expanded"]}
    assert (settings["expanded_attention"], settings_c["expanded_attention"],
            settings["index_scores"]) == ("xla", "kernel_interpret", None)
    assert settings["kv_token_bytes"] == 128 * 4 and settings_c["pallas_attention"]
    assert settings["kv_pool_bytes"] == 3 * 65 * 16 * 512
    # Every expert is held and none computes nothing: this block's programs
    # count no choices (models/mla.py).
    assert (settings["kv_layers"], settings["experts_first"],
            settings["experts_held"], settings["zero_experts"]) == (3, 0, 8, 0)
    # Tables counted at admission by the groups the latent kernel fetches:
    # the long prompt's ten pages ascend (a run of 8, a short group), the
    # short one's three are a short group, and the rerun's nine borrowed
    # pages are the long prompt's first nine, in its order.
    assert settings["kv_run_pages"] == settings_c["kv_run_pages"] == 8
    assert settings["table_groups"] == {"run": 2.0, "split": 3.0}
    assert settings_c["table_groups"] == {"run": 2.0, "split": 3.0}
