"""Greedy tokens of the llama family's test configurations, pinned to what
the tree before PR 57 served (that PR made the layout in which a one-chip TPU
engine holds ``wq``, ``wk`` and ``wv`` the decode program's to choose, and
nothing a program computes): dense with and without QK-norm, Mixtral-style
experts, and the two kinds of layer of ``tiny-swa-kv``; each as the weights come and with
``wq`` / ``wk`` moved into another order of axes, through a plain
prefill (B's prompt fits one window), continuation windows (A's is written in
three) and decode chunks on two lanes. The literals were recorded from commit
f65280a with this very file; float32, so no tie is near."""

import dataclasses

import pytest

_PINNED = {
    "tiny": [[37, 219, 8, 325, 261, 219, 100, 481, 237, 371, 71, 162, 63],
             [244, 489, 325, 120, 489, 265, 103, 11, 360, 390]],
    "tiny-qwen": [[222, 356, 151, 151, 423, 410, 400, 188, 472, 162, 352, 99,
                   126],
                  [466, 466, 458, 224, 297, 390, 443, 224, 297, 465]],
    "tiny-moe": [[54, 105, 365, 8, 438, 237, 148, 438, 486, 266, 37, 162,
                  365],
                 [476, 4, 476, 282, 477, 220, 216, 74, 381, 209]],
    "tiny-swa-kv": [[384, 448, 304, 237, 260, 475, 475, 475, 475, 143, 237,
                     405, 405],
                    [507, 211, 102, 102, 102, 102, 153, 153, 153, 437]],
}


@pytest.fixture
def in_f32(request):
    from llm_d_inference_scheduler_tpu.models import configs

    name = request.param + "-f32-pinned"
    configs._REGISTRY[name] = dataclasses.replace(
        configs.get_config(request.param), name=name, dtype="float32")
    yield name
    del configs._REGISTRY[name]


def _served(model):
    from test_engine import _by_hand, _prompt, _req

    reqs = [_req("A", _prompt(5, 41), 13, 0.0), _req("B", _prompt(7, 9), 10, 0.0)]
    toks, why, eng = _by_hand(reqs, model=model, max_batch=2, prefill_chunk=16)
    assert why == {"A": "length", "B": "length"}
    # A's prompt went through a first window and continuation windows, B's
    # through one plain prefill; both were decoded in chunks.
    assert {op for op, _ in eng._seen_op_shapes} >= {
        "prefill", "prefix_prefill", "decode"}
    return [toks["A"], toks["B"]], eng


@pytest.mark.parametrize("in_f32", list(_PINNED), indirect=True)
def test_greedy_tokens_are_the_parents(in_f32):
    got, eng = _served(in_f32)
    assert got == _PINNED[in_f32.removesuffix("-f32-pinned")], got
    assert eng.weight_layouts == {}     # a CPU's weights lie as they come


@pytest.mark.parametrize("in_f32", list(_PINNED), indirect=True)
def test_greedy_tokens_are_the_parents_with_the_projections_moved(
        in_f32, monkeypatch):
    """The engine as a TPU's decode program has it: ``wq`` and ``wk`` held
    with their contracted axis minor (here because the test says so; on a
    TPU because the compile does: tests/test_chip_compile.py), ``wv`` and
    the rest as they come. Every program is built for the arrays as they
    lie and gives the tokens it always gave; /health says how they lie."""
    import jax
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    def formats(self, params):
        here = SingleDeviceSharding(self.device)
        return jax.tree_util.tree_map_with_path(
            lambda path, a: Format(Layout(
                (0, 2, 1) if path[-1].key in ("wq", "wk")
                else tuple(range(a.ndim))), here), params)

    monkeypatch.setattr(TpuEngine, "_param_formats", formats)
    got, eng = _served(in_f32)
    assert got == _PINNED[in_f32.removesuffix("-f32-pinned")], got
    assert eng.weight_layouts == {
        "wq": [0, 2, 1], "wk": [0, 2, 1], "wv": [0, 1, 2]}
    assert eng.describe()["settings"]["weight_layouts"] == eng.weight_layouts
    layers = eng.params["layers"]
    assert {n: a.format.layout.major_to_minor for n, a in layers.items()
            if a.ndim == 3 and n in ("wq", "wk", "wv", "wo")} == {
        "wq": (0, 2, 1), "wk": (0, 2, 1), "wv": (0, 1, 2), "wo": (0, 1, 2)}
    # The persistent cache is back on, as it was.
    assert jax.config.jax_enable_compilation_cache


def test_the_family_names_the_weights_the_decode_program_lays_out():
    """What the engine asks the decode program's compile to lay out
    (TpuEngine._param_formats) are stacked weights of the family, by name,
    whatever the configuration; no other family names any."""
    import jax

    from llm_d_inference_scheduler_tpu.models import (
        configs, hybrid, llama, mla)

    assert llama.LAID_BY_DECODE == ("wq", "wk", "wv")
    for name in _PINNED:
        layers = jax.eval_shape(
            lambda k, cfg=configs.get_config(name): llama.init_params(cfg, k),
            jax.random.key(0))["layers"]
        assert all(layers[w].ndim == 3 for w in llama.LAID_BY_DECODE)
    assert not any(hasattr(m, "LAID_BY_DECODE") for m in (mla, hybrid))
