"""The one-step recurrence on the state pool in place (ops/pallas_ssm.py),
through the interpreter, against the plain form on gathered rows: the same
new state and y, nothing else in the pool touched, the rule that chooses the
form, and an engine that serves the same tokens either way."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine.request import EngineRequest
from llm_d_inference_scheduler_tpu.kvcache import state
from llm_d_inference_scheduler_tpu.models import configs
from llm_d_inference_scheduler_tpu.ops import pallas_ssm

LAYERS, HEADS, HEAD_DIM, STATE, GROUPS = 3, 8, 16, 128, 2


def _operands(lanes, seed=0):
    """A pool of ``lanes`` slots and nobody's, and a step's small operands."""
    keys = jax.random.split(jax.random.key(seed), 5)
    ssm = jax.random.normal(
        keys[0], (LAYERS, lanes + 1, HEADS, HEAD_DIM, STATE), jnp.float32)
    keep = jax.random.uniform(keys[1], (lanes, HEADS), jnp.float32, 0.3, 1.0)
    dtx = jax.random.normal(keys[2], (lanes, HEADS, HEAD_DIM), jnp.float32)
    b = jax.random.normal(keys[3], (lanes, GROUPS, STATE), jnp.float32)
    c = jax.random.normal(keys[4], (lanes, GROUPS, STATE), jnp.float32)
    return ssm, (keep, dtx, b, c)


def _slots(lanes, real, seed):
    """``real`` of the pool's slots in a shuffled order, the rest of the
    step's lanes padding: they all name nobody's slot, row ``lanes``."""
    order = np.random.default_rng(seed).permutation(lanes)[:real]
    return np.concatenate([order, np.full(lanes - real, lanes)]).astype(
        np.int32)


def _relative(got, want):
    return float(np.max(np.abs(np.asarray(got) - np.asarray(want)))
                 / np.max(np.abs(np.asarray(want))))


@pytest.mark.parametrize("lanes,real,head_block", [
    (2, 2, None), (2, 1, 4), (8, 8, None), (8, 5, 2), (8, 6, 1),
    (64, 64, None), (64, 41, 4)])
def test_the_kernel_equals_the_plain_form_and_touches_nothing_else(
        lanes, real, head_block):
    ssm, small = _operands(lanes, seed=lanes + real)
    slots = _slots(lanes, real, seed=real)
    layer = 1
    new, y = jax.jit(functools.partial(
        pallas_ssm.update_in_place, interpret=True, head_block=head_block))(
            ssm, jnp.asarray(layer, jnp.int32), jnp.asarray(slots), *small)
    rows, y_plain = pallas_ssm.update_rows(ssm[layer, slots], *small)
    # The real lanes: the new state and y to float32's rounding (the sum over
    # the state may associate differently, no more).
    assert _relative(new[layer, slots[:real]], rows[:real]) <= 1e-5
    assert _relative(y[:real], y_plain[:real]) <= 1e-5
    # Every other layer, and every slot of this layer that no lane named, bit
    # for bit as before; nobody's slot is nobody's concern.
    new, ssm = np.asarray(new), np.asarray(ssm)
    others = [i for i in range(LAYERS) if i != layer]
    assert np.array_equal(new[others], ssm[others])
    idle = sorted(set(range(lanes)) - set(slots[:real].tolist()))
    assert np.array_equal(new[layer, idle], ssm[layer, idle])


@pytest.mark.parametrize("rounded", ["state", "update"])
def test_the_tolerance_sees_a_bf16_round_trip(rounded):
    """What the comparison above is for (PERF.md section 7 (30)): a state
    that is float32 in the pool and rounded to bfloat16 on its way through
    the update reads three orders of magnitude past the tolerance."""
    ssm, (keep, dtx, b, c) = _operands(8, seed=3)
    slots = _slots(8, 8, seed=1)
    rows, y = pallas_ssm.update_rows(ssm[0, slots], keep, dtx, b, c)

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    if rounded == "state":
        bad_rows, bad_y = pallas_ssm.update_rows(bf16(ssm[0, slots]), keep,
                                                 dtx, b, c)
    else:
        bad_rows = bf16(rows)
        bad_y = jnp.einsum("bhpn,bhn->bhp", bad_rows,
                           jnp.repeat(c, HEADS // GROUPS, axis=1))
    assert _relative(bad_rows, rows) > 1e-4
    assert _relative(bad_y, y) > 1e-4


def test_state_recur_updates_the_cache_in_either_form():
    """kvcache/state.recur, the one door to both forms: the same cache comes
    out, a layer at a time, with the tails left alone."""
    ssm, small = _operands(4, seed=9)
    conv = jnp.arange(LAYERS * 5 * 6, dtype=jnp.float32).reshape(LAYERS, 5, 6)
    cache = state.at_slots(
        state.Cache(jnp.zeros((1, 1)), jnp.zeros((1, 1)), ssm, conv),
        [3, 0, 4, 4])
    got = {}
    for impl in ("gathered", "kernel_interpret"):
        stepped, ys = cache, []
        for layer in (0, 2):
            stepped, y = state.recur(stepped, layer, *small, impl=impl)
            ys.append(y[:2])
        got[impl] = (stepped, jnp.stack(ys))
        assert np.array_equal(stepped.conv, conv)
        assert np.array_equal(stepped.ssm[1], ssm[1])
        assert not np.array_equal(stepped.ssm[0, 3], ssm[0, 3])
    (a, ya), (b, yb) = got["gathered"], got["kernel_interpret"]
    assert _relative(b.ssm[:, :4], a.ssm[:, :4]) <= 1e-5
    assert _relative(yb, ya) <= 1e-5


@pytest.mark.parametrize("state_dim,head_dim,platform,sharded,interpret,want", [
    (128, 64, "tpu", False, False, True),      # the cell's shapes on the chip
    (256, 8, "tpu", False, False, True),
    (128, 64, "cpu", False, False, False),     # no TPU: the gathered form
    (128, 64, "cpu", False, True, True),       # ... but for the interpreter
    (128, 64, "tpu", True, False, False),      # a sharded pool
    (128, 64, "cpu", True, True, False),
    (64, 64, "tpu", False, False, False),      # a state that is half a lane tile
    (16, 16, "cpu", False, True, False),       # tiny-hybrid
    (128, 4, "tpu", False, False, False),      # a head that is half a sublane tile
    (128, 4, "cpu", False, True, False)])
def test_the_form_rule(state_dim, head_dim, platform, sharded, interpret, want):
    assert pallas_ssm.use_kernel(state_dim, head_dim, platform=platform,
                                 sharded=sharded, interpret=interpret) is want


@pytest.mark.parametrize("heads,head_dim,state_dim,want", [
    (128, 64, 128, 128),    # Nemotron-3-Super: a slot-layer's 4 MB whole, 16 MiB
    (256, 64, 128, 256),    # 8 MB a slot-layer: 32 MiB, still inside
    (128, 64, 512, 64),     # 16 MB a slot-layer: half of it a block
    (24, 128, 1024, 12),    # divisors only
    (8, 16, 128, 8)])
def test_the_head_block_comes_from_the_shapes(heads, head_dim, state_dim, want):
    hb = pallas_ssm.pick_head_block(heads, head_dim, state_dim)
    assert hb == want and heads % hb == 0
    assert 4 * hb * head_dim * state_dim * 4 <= pallas_ssm.VMEM_BUDGET_BYTES


def test_a_head_that_fits_no_budget_is_an_error():
    with pytest.raises(ValueError, match="do not fit"):
        pallas_ssm.pick_head_block(4, 1024, 4096)


# ---------- the engine ----------

@pytest.fixture
def served():
    """tiny-hybrid in float32 with a state of one lane tile a row, so that
    the rule hands its decode steps to the kernel where the engine is told
    to interpret kernels (tests/test_ssm.py's ``served``, widened)."""
    name = "tiny-hybrid-wide-state-f32"
    configs._REGISTRY[name] = dataclasses.replace(
        configs.get_config("tiny-hybrid"), name=name, dtype="float32",
        ssm_state=128)
    yield name
    del configs._REGISTRY[name]


def _req(rid, seed, n_prompt, max_tokens, stop=None):
    prompt = [1] + [(j * seed) % 450 + 3 for j in range(n_prompt)]
    return EngineRequest(request_id=rid, prompt_token_ids=prompt,
                         max_tokens=max_tokens, temperature=0.0,
                         ignore_eos=True,
                         stop_token_ids=(stop,) if stop is not None else ())


def _updates(eng):
    return {s.labels["form"]: s.value
            for m in eng.telemetry.registry.collect() for s in m.samples
            if s.name == "jetstream:ssm_state_updates_total"}


def test_an_engine_serves_the_same_tokens_with_the_kernel(served):
    """Greedy streams with the kernel (through the interpreter) and with the
    gathered form, token for token, through a slot reused under a chunk in
    flight: A ends on a stop token in the middle of a chunk, the chunk in
    flight overshoots and updates A's slot IN PLACE, and C, admitted into
    that slot, starts it afresh behind the overshoot."""
    from test_engine import _by_hand

    by_hand = functools.partial(_by_hand, model=served, max_batch=2)
    free, _, _ = by_hand([_req("A", 29, 40, 24)])
    stop = free["A"][6]
    assert stop not in free["A"][:6]
    reqs = [_req("A", 29, 40, 24, stop), _req("B", 31, 37, 27),
            _req("C", 37, 35, 13)]
    gathered, why, eng = by_hand(reqs)
    assert eng.mcfg.ssm_impl == "gathered"
    assert eng.describe()["settings"]["state_update"] == "gathered"
    counted = _updates(eng)
    assert set(counted) == {"gathered"} and counted["gathered"] > 0

    kernel, why_k, eng = by_hand(reqs, pallas_interpret=True)
    assert eng.mcfg.ssm_impl == "kernel_interpret"
    assert kernel == gathered and why_k == why
    assert why == {"A": "stop", "B": "length", "C": "length"}
    reg = eng.telemetry.registry
    assert reg.get_sample_value("jetstream:decode_lanes_discarded_total") == 1
    # Lanes (two, padding among them) x state layers x steps of every chunk
    # dispatched, all under the form the programs traced with.
    chunks = sum(s.value for m in reg.collect() for s in m.samples
                 if s.name == "jetstream:decode_chunks_total")
    n_state_layers = eng.mcfg.layer_pattern.count("M")
    assert _updates(eng) == {
        "kernel": chunks * eng.cfg.decode_chunk * 2 * n_state_layers}
    alone, _, _ = by_hand([_req("C", 37, 35, 13)], pallas_interpret=True)
    assert kernel["C"] == alone["C"]


# ---------- the second layout: a Mamba-1 layer's [state, channels] ----------

STATE1, CHANNELS = 16, 256


def _operands1(lanes, rows=None, seed=0):
    """A pool of ``lanes`` slots and nobody's in the second layout, the
    layer's ``A`` and ``D``, and a step's (``rows`` None) or a window's small
    operands."""
    keys = jax.random.split(jax.random.key(seed), 7)
    lead = (lanes,) if rows is None else (lanes, rows)
    ssm = jax.random.normal(keys[0], (LAYERS, lanes + 1, STATE1, CHANNELS),
                            jnp.float32)
    dt = jax.random.uniform(keys[1], (*lead, CHANNELS), jnp.float32, 1e-3, .2)
    x = jax.random.normal(keys[2], (*lead, CHANNELS), jnp.float32)
    b = jax.random.normal(keys[3], (*lead, STATE1), jnp.float32)
    c = jax.random.normal(keys[4], (*lead, STATE1), jnp.float32)
    a = -jax.random.uniform(keys[5], (STATE1, CHANNELS), jnp.float32, 1., 16.)
    d = jax.random.normal(keys[6], (CHANNELS,), jnp.float32)
    return ssm, (dt, x, b, c, a, d)


@pytest.mark.parametrize("lanes,real,channel_block", [
    (2, 2, None), (2, 1, 128), (8, 5, None), (64, 41, 128)])
def test_the_mamba1_update_equals_the_plain_form_and_touches_nothing_else(
        lanes, real, channel_block):
    ssm, small = _operands1(lanes, seed=lanes + real)
    slots = _slots(lanes, real, seed=real)
    layer = 2
    new, y = jax.jit(functools.partial(
        pallas_ssm.update1_in_place, interpret=True,
        channel_block=channel_block))(
            ssm, jnp.asarray(layer, jnp.int32), jnp.asarray(slots), *small)
    rows, y_plain = pallas_ssm.update1_rows(ssm[layer, slots], *small)
    assert _relative(new[layer, slots[:real]], rows[:real]) <= 1e-5
    assert _relative(y[:real], y_plain[:real]) <= 1e-5
    new, ssm = np.asarray(new), np.asarray(ssm)
    others = [i for i in range(LAYERS) if i != layer]
    assert np.array_equal(new[others], ssm[others])
    idle = sorted(set(range(lanes)) - set(slots[:real].tolist()))
    assert np.array_equal(new[layer, idle], ssm[layer, idle])


@pytest.mark.parametrize("rows,channel_block,time_block", [
    (16, None, None), (48, 128, None), (256, None, None), (256, 128, 128)])
def test_the_selective_scan_equals_a_scan_over_positions(rows, channel_block,
                                                         time_block):
    """The window's rows in order with the state tile resident, against
    ``lax.scan`` over positions from the same starting state: y of every row
    and the state after the last, over one and several blocks of rows and of
    channels."""
    ssm, small = _operands1(2, rows, seed=rows)
    s0 = ssm[0, :2]
    y, s1 = jax.jit(functools.partial(
        pallas_ssm.selective_scan, interpret=True,
        channel_block=channel_block, time_block=time_block))(*small, s0)
    y_plain, s1_plain = pallas_ssm.scan_rows(*small, s0)
    assert _relative(y, y_plain) <= 1e-5
    assert _relative(s1, s1_plain) <= 1e-5


def test_a_row_with_no_step_leaves_the_scans_state_alone():
    """What a bucket's padding rows are given: a step size of 0 is no decay
    and no input, in both forms."""
    ssm, (dt, x, b, c, a, d) = _operands1(1, 32, seed=5)
    s0 = ssm[0, :1]
    dt = dt.at[:, 20:].set(0.0)
    for scan in (pallas_ssm.scan_rows, functools.partial(
            pallas_ssm.selective_scan, interpret=True)):
        _, s1 = scan(dt, x, b, c, a, d, s0)
        _, s20 = scan(dt[:, :20], x[:, :20], b[:, :20], c[:, :20], a, d, s0)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s20))


def test_the_decay_is_a_channels_and_a_state_values():
    """Mamba-2's one decay a channel (the row mean of A) is another
    recurrence: the comparison's tolerance sees it."""
    ssm, (dt, x, b, c, a, d) = _operands1(4, seed=2)
    rows, y = pallas_ssm.update1_rows(ssm[0, :4], dt, x, b, c, a, d)
    flat = jnp.broadcast_to(jnp.mean(a, axis=0, keepdims=True), a.shape)
    bad_rows, bad_y = pallas_ssm.update1_rows(ssm[0, :4], dt, x, b, c, flat, d)
    assert _relative(bad_rows, rows) > 1e-2 and _relative(bad_y, y) > 1e-2


def test_state_recur1_updates_the_cache_in_either_form():
    ssm, small = _operands1(4, seed=9)
    conv = jnp.arange(LAYERS * 5 * 6, dtype=jnp.float32).reshape(LAYERS, 5, 6)
    cache = state.at_slots(
        state.Cache(jnp.zeros((1, 1)), jnp.zeros((1, 1)), ssm, conv),
        [3, 0, 4, 4])
    got = {}
    for impl in ("gathered", "kernel_interpret"):
        stepped, ys = cache, []
        for layer in (0, 2):
            stepped, y = state.recur1(stepped, layer, *small, impl=impl)
            ys.append(y[:2])
        got[impl] = (stepped, jnp.stack(ys))
        assert np.array_equal(stepped.conv, conv)
        assert np.array_equal(stepped.ssm[1], ssm[1])
        assert not np.array_equal(stepped.ssm[0, 3], ssm[0, 3])
    (a, ya), (b, yb) = got["gathered"], got["kernel_interpret"]
    assert _relative(b.ssm[:, :4], a.ssm[:, :4]) <= 1e-5
    assert _relative(yb, ya) <= 1e-5


@pytest.mark.parametrize("channels,state_dim,platform,interpret,want", [
    (5120, 16, "tpu", False, True),      # the cell's shapes on the chip
    (5120, 16, "cpu", False, False),
    (256, 8, "cpu", True, True),
    (96, 6, "cpu", True, False),         # tiny-jamba
    (5120, 12, "tpu", False, False)])    # a state of a sublane tile and a half
def test_the_form_rule_takes_the_second_layouts_tile(channels, state_dim,
                                                     platform, interpret,
                                                     want):
    assert pallas_ssm.use_kernel(channels, state_dim, platform=platform,
                                 sharded=False, interpret=interpret) is want


def test_the_mamba1_microbenchmark_rehearses_on_the_cpu(capsys, monkeypatch,
                                                        tmp_path):
    """scripts/microbench_decode.py --ssm1 on a small model of the family,
    kernels interpreted: a line a form, every kernel's state and y the plain
    form's, no share of a peak without a chip."""
    import importlib.util
    import json
    import pathlib

    repo = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "microbench_decode", repo / "scripts" / "microbench_decode.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setattr("llm_d_inference_scheduler_tpu.utils.compile_cache."
                        "configure_compile_cache", lambda: "")
    small = tmp_path / "small.json"
    small.write_text(json.dumps({
        "model_type": "jamba", "hidden_size": 128, "vocab_size": 512,
        "num_hidden_layers": 4, "attn_layer_period": 4,
        "attn_layer_offset": 1, "num_attention_heads": 4,
        "num_key_value_heads": 1, "intermediate_size": 72,
        "rms_norm_eps": 1e-06, "mamba_d_state": 8, "mamba_dt_rank": 5,
        "mamba_expand": 2, "mamba_d_conv": 4}))
    bench.main(["--ssm1", "--ssm1-config", str(small), "--ssm-interpret",
                "--ssm-lanes", "3", "--ssm-iters", "1",
                "--ssm1-channel-blocks", "128,", "--ssm1-rows", "32",
                "--ssm1-time-blocks", "32",
                "--ssm1-scan-channel-blocks", "128,256"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [ln["component"] for ln in lines] == [
        "ssm1_state_update gathered", "ssm1_state_update kernel cb=128",
        "ssm1_state_update kernel cb=256", "ssm1_selective_scan lax.scan",
        "ssm1_selective_scan kernel cb=128 tb=32",
        "ssm1_selective_scan kernel cb=256 tb=32"]
    for ln in lines:
        assert ln.get("share_of_peak_pct") is None
        assert max(ln.get("state_vs_gathered", 0), ln.get("y_vs_gathered", 0),
                   ln.get("state_vs_scan", 0), ln.get("y_vs_scan", 0)) < 1e-5
