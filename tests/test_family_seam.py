"""The seam between the engine and a model family (PR 44): the engine asks,
``models/`` answers (``models.bind``), ``engine/telemetry.py`` counts, and
``kvcache/`` says what the cache keeps. No engine is built here but for the
``/health`` keys."""

from __future__ import annotations

import ast
import dataclasses
import pathlib

import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine.telemetry import (
    PROGRAM_COUNTERS, EngineTelemetry)
from llm_d_inference_scheduler_tpu.kvcache import pages, state
from llm_d_inference_scheduler_tpu.models import (
    bind, configs, family, hybrid, llama, mla)
from llm_d_inference_scheduler_tpu.models.binding import selection_counts

PKG = pathlib.Path(__file__).resolve().parent.parent / \
    "llm_d_inference_scheduler_tpu"


# ---------- the engine names no family ----------

_FIELD_PREFIXES = ("ssm_", "index_", "moe_", "kv_lora", "mla_", "dsa_",
                   "expanded_")
_FIELDS = {"n_experts", "n_zero_experts", "held_experts"}
_COUNTERS = ("jetstream:moe_", "jetstream:mla_", "jetstream:ssm_",
             "jetstream:dsa_")
_GONE = ("_bind_", "_model_for", "_note_selection", "_ctx_bucket",
         "_ctx_widths", "_moe_grouped", "_mcfg_grouped")


def family_knowledge(source: str) -> list[str]:
    """Where a module knows a model family by name: it imports from
    ``ops/``, reads a family's field off a configuration (or a family's
    counter off the telemetry), spells such a counter's name, or keeps one
    of the methods that did."""
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.ImportFrom):
            if "ops" in (node.module or "").split(".") + [
                    a.name for a in node.names]:
                found.append(f"line {node.lineno}: an import from ops/")
        elif isinstance(node, ast.Attribute) and (
                node.attr.startswith(_FIELD_PREFIXES)
                or node.attr in _FIELDS
                or any(g in node.attr for g in _GONE)):
            found.append(f"line {node.lineno}: .{node.attr}")
        elif isinstance(node, ast.FunctionDef) \
                and any(g in node.name for g in _GONE):
            found.append(f"line {node.lineno}: def {node.name}")
        elif isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and any(c in node.value for c in _COUNTERS):
            found.append(f"line {node.lineno}: a family's counter by name")
    return found


def test_the_engine_names_no_family():
    assert family_knowledge((PKG / "engine/core.py").read_text()) == []


def test_the_guard_sees_what_it_guards_against():
    assert len(family_knowledge(
        "from ..ops import pallas_moe\n"
        "from ..ops.pallas_latent_attention import RUN_PAGES\n"
        "from .. import ops\n"
        "a = self.mcfg.ssm_impl\n"
        "b = cfg.index_topk or cfg.n_experts or m.held_experts\n"
        "self.telemetry.moe_ffn_tokens.labels(form='dense').inc()\n"
        "self.telemetry.dsa_rows['scored'].inc(1)\n"
        "def _bind_state_form(self, platform): pass\n"
        "x = self._model_for(4)\n"
        "'booked in jetstream:mla_attention_tokens_total'\n"
        "ok = (cfg.kv_block_size, self.geom.state, cfg.device_index,\n"
        "      self.bound.model_for(4), 'jetstream:slot_refills_total')\n"
    )) == 12


def test_models_import_nothing_from_the_engine():
    """ops <- kvcache <- models <- engine: the arrow stays one way."""
    for path in (PKG / "models").glob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom):
                assert "engine" not in (node.module or "").split("."), \
                    (path.name, node.lineno)
                assert not any(a.name == "engine" for a in node.names), \
                    (path.name, node.lineno)
            elif isinstance(node, ast.Import):
                assert not any(".engine" in a.name for a in node.names), \
                    (path.name, node.lineno)


# ---------- binding the forms ----------

WIDE_SSM = dict(ssm_state=128, ssm_head_dim=8)    # whole (8, 128) tiles


@pytest.mark.parametrize("name, module, widen, kw, forms", [
    # models/llama.py: nothing to resolve, the MoE twin alone.
    ("tiny-moe", llama, {}, dict(platform="cpu"), {}),
    ("tiny-moe", llama, {}, dict(platform="tpu"), {}),
    # models/mla.py: every latent block's expanded attention, and a
    # selecting block's indexer beside it (``index_impl`` stays "xla" where
    # nothing selects).
    ("tiny-mla", mla, {}, dict(platform="cpu"),
     dict(expanded_impl="xla", index_impl="xla")),
    ("tiny-mla", mla, {}, dict(platform="tpu"),
     dict(expanded_impl="kernel", index_impl="xla")),
    ("tiny-mla", mla, {}, dict(platform="cpu", interpret=True),
     dict(expanded_impl="kernel_interpret", index_impl="xla")),
    ("tiny-longcat", mla, {}, dict(platform="cpu"),
     dict(expanded_impl="xla", index_impl="xla")),
    ("tiny-longcat", mla, {}, dict(platform="tpu"),
     dict(expanded_impl="kernel", index_impl="xla")),
    ("tiny-dsa", mla, {}, dict(platform="cpu"),
     dict(expanded_impl="xla", index_impl="xla")),
    ("tiny-dsa", mla, {}, dict(platform="tpu"),
     dict(expanded_impl="kernel", index_impl="kernel")),
    ("tiny-dsa", mla, {}, dict(platform="cpu", interpret=True),
     dict(expanded_impl="kernel_interpret", index_impl="kernel_interpret")),
    ("tiny-swa", mla, {}, dict(platform="cpu"),
     dict(expanded_impl="xla", index_impl="xla", swa_impl="xla")),
    ("tiny-swa", mla, {}, dict(platform="tpu"),
     dict(expanded_impl="kernel", index_impl="kernel", swa_impl="kernel")),
    # models/hybrid.py: the state update (tiny-hybrid's state is 16 wide).
    ("tiny-hybrid", hybrid, {}, dict(platform="tpu"),
     dict(ssm_impl="gathered")),
    ("tiny-hybrid", hybrid, WIDE_SSM, dict(platform="cpu"),
     dict(ssm_impl="gathered")),
    ("tiny-hybrid", hybrid, WIDE_SSM, dict(platform="tpu"),
     dict(ssm_impl="kernel")),
    ("tiny-hybrid", hybrid, WIDE_SSM, dict(platform="tpu", sharded=True),
     dict(ssm_impl="gathered")),
    ("tiny-hybrid", hybrid, WIDE_SSM, dict(platform="cpu", interpret=True),
     dict(ssm_impl="kernel_interpret")),
])
def test_bind_resolves_a_familys_forms(name, module, widen, kw, forms):
    cfg = dataclasses.replace(configs.get_config(name), **widen)
    bound = bind(cfg, **kw)
    assert bound.module is module is family(cfg)
    # What the rules resolved, and nothing else of the configuration.
    assert bound.mcfg == dataclasses.replace(cfg, **forms)
    assert bound.mcfg.moe_impl == "dense"
    assert bound.grouped == dataclasses.replace(
        bound.mcfg, moe_impl="grouped_interpret" if kw.get("interpret")
        else "grouped")
    assert bound.chosen == dataclasses.replace(
        bound.mcfg, moe_impl="chosen_interpret" if kw.get("interpret")
        else "chosen")
    with pytest.raises(dataclasses.FrozenInstanceError):
        bound.mcfg = cfg


@pytest.mark.parametrize("name, kernels", [
    ("tiny-dsa", ["dsa_index_scores_window", "dsa_window_attention"]),
    ("tiny-swa", ["dsa_index_scores_window", "dsa_window_attention",
                  "swa_window_attention"]),
])
def test_the_blocks_that_had_the_kernel_trace_the_programs_they_had(
        name, kernels, monkeypatch):
    """PR 47 gave every latent block's expanded attention a form of its own
    (``expanded_impl``); the blocks that select took the kernel by
    ``index_impl`` before, under the name ``dsa_window_attention``. Bound
    for a TPU, their continuation window traces to the program that rule
    gave, kernel for kernel."""
    import jax
    import jax.numpy as jnp

    cfg = bind(configs.get_config(name), platform="tpu").mcfg
    geom = pages.PageGeometry.for_engine(cfg, 2, 256)
    cache, _ = pages.alloc(geom)
    row = jnp.zeros((1, geom.max_blocks_per_seq), jnp.int32)
    cache = state.at_slots(cache, [0], *([row] if cfg.window_attn else []))
    params = jax.eval_shape(lambda k: mla.init_params(cfg, k),
                            jax.random.key(0))
    S, prior = 32, 128 // geom.block
    one = jnp.ones((1,), jnp.int32)

    def traced():
        return str(jax.make_jaxpr(
            lambda p, c: mla.prefill_with_prefix(
                p, cfg, jnp.zeros((1, S), jnp.int32), one * S, one * 128, c,
                None, row, row[:, :prior]))(params, cache))

    now = traced()
    for kernel in kernels:
        assert f"name={kernel}" in now, kernel
    assert "mla_window_attention" not in now
    was = mla.expanded_attention
    monkeypatch.setattr(
        mla, "expanded_attention",
        lambda c, *a, impl=None, name="dsa_window_attention": was(
            c, *a, impl=c.index_impl if impl is None else impl, name=name))
    assert traced() == now


def test_bind_model_for_is_the_moe_rule_shape_by_shape():
    from llm_d_inference_scheduler_tpu.ops.pallas_moe import GROUPED_MIN_TOKENS

    cfg = configs.get_config("tiny-moe")
    tpu = bind(cfg, platform="tpu")
    assert tpu.model_for(GROUPED_MIN_TOKENS) is tpu.grouped
    assert tpu.model_for(GROUPED_MIN_TOKENS - 1) is tpu.mcfg
    assert tpu.grouped.moe_impl == "grouped"
    for other in (bind(cfg, platform="cpu"),
                  bind(cfg, platform="tpu", sharded=True),
                  bind(configs.get_config("tiny"), platform="tpu")):
        assert other.model_for(4096) is other.mcfg
    assert bind(cfg, platform="cpu", interpret=True).model_for(
        4096).moe_impl == "grouped_interpret"


def _benchmark_configuration(name):
    """A configuration of chipbench/configs as the launcher maps it."""
    import json
    import types

    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf

    with open(PKG.parent / "chipbench" / "configs" / f"{name}.json") as f:
        doc = json.load(f)
    return config_from_hf(types.SimpleNamespace(**doc), name=name)


_ROWS = (2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)


@pytest.mark.parametrize("name, chosen_upto, grouped", [
    # No experts: one form.
    ("qwen3-4b", 0, False),
    # Every expert held (3 to 4 rows an expert at a full batch): the decode
    # buckets trace the parent's object, whatever their width.
    ("mixtral-8x7b-cut", 0, True),
    ("kimi-vl-a3b-cut", 0, True),
    # A range held: rows x choices / router outputs <= 3 reads the chosen.
    ("nemotron-3-super-cut", 64, True),       # 64 x 22 / 512 = 2.75
    ("longcat-flash-omni-cut", 128, True),    # 128 x 12 / 768 = 2.0
    ("deepseek-v3.2-exp-cut", 64, True),      # 64 x 8 / 256 = 2.0; 128: 4.0
    ("dots3-note-prev-cut", 64, True),
])
def test_the_moe_form_of_every_benchmark_configurations_programs(
        name, chosen_upto, grouped):
    """Which form each decode bucket and prefill bucket of the seven
    configurations traces on the chip, from its shapes alone."""
    cfg = _benchmark_configuration(name)
    bound = bind(cfg, platform="tpu")
    assert bound.mcfg.moe_impl == "dense"
    for rows in _ROWS:
        want = (bound.chosen if rows <= chosen_upto else
                bound.grouped if grouped and rows >= 512 else bound.mcfg)
        assert bound.model_for(rows) is want, rows
        assert bound.decode_expert_visits(rows) == (
            cfg.held_experts[1] * cfg.n_expert_layers
            if rows <= chosen_upto else 0)
        # The host's count of rows by form knows grouped and dense alone.
        if cfg.n_experts:
            assert bound.program_counts("decode", rows, 1)[0] == (
                "moe_ffn_tokens", "grouped" if want is bound.grouped
                else "dense", rows)
    assert bound.chosen.moe_impl == "chosen"
    assert bound.describe()["experts_chosen_max_rows"] == (
        3.0 if chosen_upto else None)
    # Off the chip, and where the weights span devices, the dense einsums.
    for other in (bind(cfg, platform="cpu"),
                  bind(cfg, platform="tpu", sharded=True)):
        assert all(other.model_for(rows) is other.mcfg for rows in _ROWS)
    assert bind(cfg, platform="cpu", interpret=True).model_for(2).moe_impl == (
        "chosen_interpret" if chosen_upto else "dense")


def test_bind_takes_a_forced_form_over_the_rule():
    cfg = configs.get_config("tiny-hybrid")
    bound = bind(cfg, platform="cpu", forced={"ssm_impl": "kernel_interpret"})
    assert bound.mcfg.ssm_impl == bound.grouped.ssm_impl == "kernel_interpret"


# ---------- counting a program ----------

TOPK = 32


def _by_hand(contexts):
    return {"selected": sum(c > TOPK for c in contexts),
            "all": sum(c <= TOPK for c in contexts),
            "scored": sum(contexts),
            "attended": sum(min(c, TOPK) for c in contexts)}


@pytest.mark.parametrize("kind, first, n, contexts", [
    # A decode chunk of 4 steps: lanes at positions 21, 40 and 30 (the third
    # crosses TOPK inside the chunk): each step's context is one longer.
    ("decode", [22, 41, 31], [4, 4, 4],
     [22, 23, 24, 25, 41, 42, 43, 44, 31, 32, 33, 34]),
    # Plain prefills of 40 and 7 prompt tokens: contexts 1..n.
    ("prefill", [1, 1], [40, 7], list(range(1, 41)) + list(range(1, 8))),
    # A continuation window of 12 tokens behind 16 written, one of 48 behind
    # 32, and one that holds nothing.
    ("prefix_prefill", [17, 33, 5], [12, 48, 0],
     list(range(17, 29)) + list(range(33, 81))),
])
def test_selection_counts_against_a_count_by_hand(kind, first, n, contexts):
    got = selection_counts(np.asarray(first, np.int32),
                           np.asarray(n, np.int32), TOPK)
    assert got == _by_hand(contexts)
    # The same through the family's answer for a program of that kind.
    bound = bind(dataclasses.replace(configs.get_config("tiny-dsa"),
                                     index_topk=TOPK), platform="cpu")
    counts = bound.program_counts(
        kind, 8, 4 if kind == "decode" else 1, real=len(first),
        queries=(np.asarray(first, np.int32), np.asarray(n, np.int32)))
    dsa = {label: amount for name, label, amount in counts
           if name.startswith("dsa_")}
    assert dsa == _by_hand(contexts)


def test_selection_counts_do_not_overflow_int32_positions():
    got = selection_counts(np.asarray([1], np.int32),
                           np.asarray([100_000], np.int32), 2048)
    assert got["scored"] == 100_000 * 100_001 // 2
    assert got["selected"] == 100_000 - 2048


@pytest.mark.parametrize("name, kind, rows, steps, real, want", [
    ("tiny", "decode", 4, 8, 3, []),
    ("tiny-moe", "decode", 4, 8, 3, [("moe_ffn_tokens", "dense", 32)]),
    ("tiny-moe", "prefill", 1024, 1, 1, [("moe_ffn_tokens", "dense", 1024)]),
    ("tiny-mla", "decode", 4, 2, 4,
     [("moe_ffn_tokens", "dense", 8), ("mla_attention_tokens", "absorbed", 8)]),
    ("tiny-mla", "prefix_prefill", 64, 1, 1,
     [("moe_ffn_tokens", "dense", 64),
      ("mla_attention_tokens", "expanded", 64),
      ("mla_window_attention_tokens", "xla", 64)]),
    ("tiny-longcat", "prefill", 2 * 32, 1, 2,
     [("moe_ffn_tokens", "dense", 64),
      ("mla_attention_tokens", "expanded", 64),
      ("mla_window_attention_tokens", "xla", 64)]),
    ("tiny-hybrid", "decode", 4, 2, 2,
     [("moe_ffn_tokens", "dense", 8), ("ssm_tokens", "step", 8),
      ("ssm_state_updates", "gathered", 8 * 2)]),
    ("tiny-hybrid", "prefill", 32, 1, 2,
     [("moe_ffn_tokens", "dense", 32), ("ssm_tokens", "scan", 32),
      ("ssm_slot_prefills", None, 2)]),
    # A warm-up prefill starts nobody's slot; a continuation window none.
    ("tiny-hybrid", "prefill", 32, 1, 0,
     [("moe_ffn_tokens", "dense", 32), ("ssm_tokens", "scan", 32)]),
    ("tiny-hybrid", "prefix_prefill", 32, 1, 1,
     [("moe_ffn_tokens", "dense", 32), ("ssm_tokens", "scan", 32)]),
    # A K/V model with window and full layers: a continuation window's rows
    # by the form its attention reads the pages with; a first window reads
    # none, and a decode step's walks are not a window's.
    ("tiny-swa-kv", "prefix_prefill", 16, 1, 1,
     [("moe_ffn_tokens", "dense", 16),
      ("kv_prefill_attention_tokens", "xla", 16)]),
    ("tiny-swa-kv", "prefill", 16, 1, 1, [("moe_ffn_tokens", "dense", 16)]),
    ("tiny-swa-kv", "decode", 4, 2, 3, [("moe_ffn_tokens", "dense", 8)]),
])
def test_program_counts_by_family_and_kind(name, kind, rows, steps, real,
                                           want):
    cfg = configs.get_config(name)
    assert cfg.n_state_layers in (0, 2)
    got = bind(cfg, platform="cpu").program_counts(kind, rows, steps,
                                                   real=real)
    assert got == want
    assert {n for n, _, _ in got} <= set(PROGRAM_COUNTERS)


def test_program_counts_name_the_form_the_program_traced_with():
    moe = bind(configs.get_config("tiny-moe"), platform="cpu", interpret=True)
    assert moe.program_counts("prefill", 512, 1) == [
        ("moe_ffn_tokens", "grouped", 512)]
    assert moe.program_counts("decode", 4, 8) == [
        ("moe_ffn_tokens", "dense", 32)]
    ssm = bind(dataclasses.replace(configs.get_config("tiny-hybrid"),
                                   **WIDE_SSM), platform="cpu", interpret=True)
    assert ("ssm_state_updates", "kernel", 16) in ssm.program_counts(
        "decode", 4, 2)
    for kw, form in ((dict(platform="cpu", interpret=True), "kernel"),
                     (dict(platform="tpu"), "xla")):   # head_dim 16: no DMA
        swa = bind(configs.get_config("tiny-swa-kv"), **kw)
        assert ("kv_prefill_attention_tokens", form, 24) in (
            swa.program_counts("prefix_prefill", 24, 1, real=1))


@pytest.mark.parametrize("name", ["tiny-mla", "tiny-longcat", "tiny-dsa",
                                  "tiny-swa"])
@pytest.mark.parametrize("kw, form", [
    (dict(platform="cpu"), "xla"), (dict(platform="tpu"), "kernel"),
    (dict(platform="cpu", interpret=True), "kernel")])
def test_a_latent_engines_windows_are_counted_by_their_form(name, kw, form):
    """Every latent engine, selecting or not: a prefill's and a continuation
    window's rows under the bound form's first word, a decode step's not at
    all; /health says the form whole."""
    bound = bind(configs.get_config(name), **kw)
    for kind in ("prefill", "prefix_prefill"):
        assert ("mla_window_attention_tokens", form, 96) in (
            bound.program_counts(kind, 96, 1, real=1))
    assert not [c for c in bound.program_counts("decode", 4, 2, real=4)
                if c[0] == "mla_window_attention_tokens"]
    assert bound.describe()["expanded_attention"] == (
        bound.mcfg.expanded_impl)
    assert bound.mcfg.expanded_impl.split("_")[0] == form


def test_telemetry_books_a_familys_answer():
    t = EngineTelemetry(block_size=16, num_blocks=8)
    value = t.registry.get_sample_value
    # Both series of the selection counters are there from the start.
    assert value("jetstream:dsa_rows_total", {"kind": "attended"}) == 0
    assert value("jetstream:dsa_query_tokens_total", {"form": "all"}) == 0
    assert value("jetstream:moe_ffn_tokens_total", {"form": "dense"}) is None
    t.book_program([("moe_ffn_tokens", "dense", 32),
                    ("dsa_rows", "scored", 7), ("dsa_rows", "scored", 5),
                    ("ssm_slot_prefills", None, 2)])
    t.book_program([])
    assert value("jetstream:moe_ffn_tokens_total", {"form": "dense"}) == 32
    assert value("jetstream:dsa_rows_total", {"kind": "scored"}) == 12
    assert value("jetstream:ssm_slot_prefills_total") == 2
    with pytest.raises(AssertionError):     # no family's counter
        t.book_program([("waiting", None, 1)])


class _Count:
    """A step's count as the telemetry takes it: ready or not, copied once."""

    def __init__(self, n, ready=True):
        self.n, self.ready, self.copies = n, ready, 0

    def copy_to_host_async(self):
        self.copies += 1

    def is_ready(self):
        return self.ready

    def __int__(self):
        return self.n


def test_pair_counts_are_booked_in_order_once_ready_and_never_waited_for():
    t = EngineTelemetry(block_size=16, num_blocks=8)

    def pairs():
        return {h: t.registry.get_sample_value(
            "jetstream:moe_routed_pairs_total", {"held": h})
            for h in ("yes", "no", "zero")}

    t.keep_pair_counts(None, None, 99)      # a cache that carries no counts
    first, late = _Count(5), _Count(7, ready=False)
    zero = _Count(2)
    t.keep_pair_counts(first, None, 12)
    t.keep_pair_counts(late, zero, 20)
    t.keep_pair_counts(_Count(1), None, 4)
    assert (first.copies, late.copies, zero.copies) == (1, 1, 1)
    t.book_pair_counts()
    # The second is not done: it and what was queued behind it wait.
    assert pairs() == {"yes": 5, "no": 7, "zero": None}
    late.ready = True
    t.book_pair_counts()
    assert pairs() == {"yes": 13, "no": 7 + 11 + 3, "zero": 2}
    t.book_pair_counts()
    assert pairs()["yes"] == 13


def test_pairs_per_row_come_from_the_bound_value():
    for name in ("tiny", "tiny-moe", "tiny-longcat", "tiny-hybrid"):
        cfg = configs.get_config(name)
        assert bind(cfg, platform="cpu").pairs_per_row == (
            cfg.experts_per_token * cfg.n_expert_layers)
    assert bind(configs.get_config("tiny"), platform="cpu").pairs_per_row == 0


# ---------- what a family keeps, in words ----------

# /health's settings as PR 43 served them (chipbench/ reads them), PR 47's
# ``expanded_attention`` and PR 57's ``weight_layouts``.
SETTINGS_KEYS = {
    "model", "n_layers", "dtype", "max_batch", "max_model_len", "kv_blocks",
    "kv_layers", "kv_token_bytes", "kv_pool_bytes", "kv_run_pages",
    "index_topk", "index_token_bytes", "index_pool_bytes", "index_scores",
    "expanded_attention", "experts_first", "experts_held", "zero_experts",
    "experts_chosen_max_rows", "state_slot_bytes",
    "state_pool_bytes", "state_update", "prefix_caching",
    "off_for_state_layers", "decode_chunk", "pallas_attention", "kv_wire",
    "kv_wire_error", "compile_cache_dir", "weight_layouts"}


@pytest.mark.parametrize("name, one_chip, want", [
    ("tiny", None, dict(
        kv_run_pages=8, index_topk=0, index_token_bytes=0,
        index_pool_bytes=0, index_scores=None, expanded_attention=None,
        experts_first=0,
        experts_held=0, zero_experts=0, state_slot_bytes=0,
        state_pool_bytes=0, state_update=None, off_for_state_layers=[])),
    ("tiny-mla", "a latent (MLA) page pool", dict(
        kv_run_pages=8, index_topk=0, index_scores=None,
        expanded_attention="xla", state_update=None)),
    ("tiny-longcat", "a latent (MLA) page pool", dict(
        kv_run_pages=8, zero_experts=configs.get_config(
            "tiny-longcat").n_zero_experts)),
    ("tiny-dsa", "a latent (MLA) page pool and its indexer's key pool "
     "beside it, under one block table", dict(
         kv_run_pages=8, index_topk=configs.get_config("tiny-dsa").index_topk,
         index_scores="xla", expanded_attention="xla", state_slot_bytes=0)),
    ("tiny-hybrid", "a recurrent state pool beside its pages", dict(
        kv_run_pages=8, state_update="gathered",
        off_for_state_layers=list(state.OFF_FOR_STATE_LAYERS))),
])
def test_settings_are_the_parents_keys_each_family_saying_its_own(
        name, one_chip, want):
    cfg = configs.get_config(name)
    geom = pages.PageGeometry.for_engine(cfg, 2, 64)
    bound = bind(cfg, platform="cpu")
    assert geom.one_chip_only == one_chip
    said = {**geom.describe(), **bound.describe()}
    assert len(said) == len(geom.describe()) + len(bound.describe()) == 17
    assert said["kv_pool_bytes"] == geom.pool_bytes
    assert set(said) < SETTINGS_KEYS
    assert {k: said[k] for k in want} == want
    held = cfg.held_experts
    assert (said["experts_first"], said["experts_held"]) == held
    if geom.state:
        assert said["state_slot_bytes"] == geom.state.slot_bytes
        assert said["state_pool_bytes"] == 3 * geom.state.slot_bytes
    if geom.index_dim:
        assert said["index_pool_bytes"] == (
            geom.n_layers * geom.n_blocks * 16 * said["index_token_bytes"])


def test_an_engines_health_has_the_parents_settings_keys_exactly():
    from llm_d_inference_scheduler_tpu.engine.config import EngineConfig
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    for name in ("tiny", "tiny-dsa", "tiny-hybrid"):
        eng = TpuEngine(EngineConfig(model=name, backend="tpu", max_batch=2,
                                     max_model_len=64, kv_events_port=0))
        settings = eng.describe()["settings"]
        assert set(settings) == SETTINGS_KEYS
        assert settings["prefix_caching"] is (name != "tiny-hybrid")
        assert settings["weight_layouts"] == {}     # (not on a CPU)


def test_the_cache_says_how_it_is_allocated_and_attended():
    """What the engine passed pages.alloc and picked by hand before."""
    for name, kind, counted, zero in (
            ("tiny", tuple, False, False), ("tiny-mla", tuple, False, False),
            ("tiny-longcat", state.Cache, True, True),
            ("tiny-dsa", state.Cache, True, False),
            ("tiny-hybrid", state.Cache, True, False)):
        cfg = configs.get_config(name)
        geom = pages.PageGeometry.for_engine(cfg, 2, 64)
        assert (geom.counted, geom.counts_zero) == (counted, zero)
        assert (geom.state is not None) == bool(cfg.n_state_layers)
        k, v = pages.alloc(geom)
        if kind is state.Cache:
            assert isinstance(k, state.Cache) and v is None
            assert k.counts_zero is zero
            assert (k.ssm is not None) == bool(cfg.n_state_layers)
            assert (k.idx is not None) == bool(cfg.index_topk)
        else:
            assert not isinstance(k, state.Cache)
        attend = pages.attention_for(geom, kernel=False, interpret=False)
        assert attend.func is (pages.latent_decode_attention
                               if cfg.kv_lora_rank else pages.decode_attention)
        assert attend.keywords == dict(kernel=False, interpret=False)
