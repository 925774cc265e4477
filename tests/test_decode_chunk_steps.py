"""The decode chunk's step count is an operand of ONE program a bucket
(TpuEngine._decode_chunk_impl): at any count it gives the first rows of the
longest chunk's tokens, writes the same pages and leaves its last step's
tokens on the device for the next chunk, in each family of block; and
everything the host counts by a chunk's steps counts the steps it asked for.
(Which length the loop asks for, and when, is tests/test_engine.py's.)"""

import functools

import jax
import numpy as np
import pytest

_K = 4
_MODELS = ["tiny", "tiny-hybrid", "tiny-mla"]


def _requests():
    from test_engine import _prompt, _req

    return [_req("A", _prompt(5, 9), 14, 0.0), _req("B", _prompt(7, 20), 14, 0.0)]


def _chunks(model, lengths):
    """Serve two greedy lanes by hand, the first chunks ``lengths`` steps long
    and the rest _K; for each of those first chunks, as it was dispatched:
    (its own rows of tokens, every slot's token on the device behind it, the
    cache behind it, leaf by leaf)."""
    from test_engine import _by_hand

    seen, todo = [], list(lengths)

    def watch(eng, step):
        if step is not None:
            return
        eng._chunk_steps = lambda shape: todo.pop(0) if todo else _K

        def dispatch(real=eng._dispatch_chunk):
            chunk = real()
            if chunk is not None and len(seen) < len(lengths):
                assert chunk.toks.shape == (_K, 2)
                seen.append((
                    np.asarray(chunk.toks)[:chunk.steps],
                    np.asarray(eng._slot_tokens),
                    [np.asarray(x) for x in jax.tree.leaves(
                        (eng.k_pages, eng.v_pages))]))
            return chunk

        eng._dispatch_chunk = dispatch

    toks, why, eng = _by_hand(_requests(), model=model, max_batch=2,
                              watch=watch)
    assert why == {"A": "length", "B": "length"}
    assert [len(toks[r]) for r in "AB"] == [14, 14]
    # Whatever the lengths, the one bucket that ran has ONE program.
    assert eng._jit_decode_chunk._cache_size() == 1
    return seen, toks


@functools.cache
def _step_by_step(model):
    """_K chunks of one step each: what a chunk of any length has to give."""
    return _chunks(model, [1] * _K)


@pytest.mark.parametrize("n", [1, _K // 2, _K])
@pytest.mark.parametrize("model", _MODELS)
def test_a_chunk_of_n_steps_is_the_first_n_of_the_longest(model, n):
    """A chunk of n steps, and the rest of _K behind it: the rows, the pages
    (state pool and latent pool alike) and the streams are those of _K chunks
    of one step; after the chunk, each lane's slot holds its row n - 1."""
    single, streams = _step_by_step(model)
    rows = np.concatenate([rows for rows, _, _ in single])
    assert rows.shape == (_K, 2)
    seen, toks = _chunks(model, [n, _K - n] if n < _K else [_K])
    assert toks == streams
    np.testing.assert_array_equal(
        np.concatenate([rows for rows, _, _ in seen]), rows)
    np.testing.assert_array_equal(seen[0][0], rows[:n])
    # Both lanes hold slots 0 and 1, in that order.
    np.testing.assert_array_equal(seen[0][1], rows[n - 1])
    np.testing.assert_array_equal(seen[0][1], single[n - 1][1])
    for got, want in zip(seen[-1][2], single[-1][2], strict=True):
        np.testing.assert_array_equal(got, want)


def _sums(eng, name, label):
    return {s.labels[label]: s.value
            for m in eng.telemetry.registry.collect() for s in m.samples
            if s.name == name}


@pytest.mark.parametrize("n", [1, _K // 2, _K])
@pytest.mark.parametrize("model", ["tiny-hybrid", "tiny-dsa"])
def test_a_chunk_counts_by_its_own_steps(model, n):
    """One chunk of n steps for two lanes at contexts 6.. and 31..: padded
    tokens through the expert FFN, latent attention and the state layers,
    state updates, and a selecting block's queries and rows, are n a lane."""
    from llm_d_inference_scheduler_tpu.engine import EngineConfig
    from llm_d_inference_scheduler_tpu.engine.core import _DUMMY_REQ, TpuEngine

    eng = TpuEngine(EngineConfig(
        model=model, backend="tpu", max_model_len=128, max_batch=2,
        decode_chunk=_K, seed=11, kv_events_port=0))
    positions = np.asarray([5, 30], np.int32)
    toks = eng._device_call(("decode",), dict(
        slots=np.asarray([0, 1], np.int32), positions=positions,
        tables=np.zeros((2, eng.max_blocks_per_seq), np.int32), steps=n,
        **eng._sample_np([_DUMMY_REQ] * 2)))
    assert toks.shape == (_K, 2)
    sums = functools.partial(_sums, eng)
    assert sums("jetstream:moe_ffn_tokens_total", "form") == {"dense": 2 * n}
    if model == "tiny-hybrid":
        assert sums("jetstream:ssm_tokens_total", "form") == {"step": 2 * n}
        assert sums("jetstream:ssm_state_updates_total", "form") == {
            "gathered": 2 * n * eng.geom.state.n_layers}
        return
    assert sums("jetstream:mla_attention_tokens_total", "form") == {
        "absorbed": 2 * n}
    contexts = [int(p) + 1 + i for p in positions for i in range(n)]
    topk = eng.mcfg.index_topk
    assert sums("jetstream:dsa_query_tokens_total", "form") == {
        "selected": sum(c > topk for c in contexts),
        "all": sum(c <= topk for c in contexts)}
    assert sums("jetstream:dsa_rows_total", "kind") == {
        "scored": sum(contexts),
        "attended": sum(min(c, topk) for c in contexts)}
