"""The generators: the same plan for the same seed, the same WORK for every
seed, and the lengths and arrivals the mix states."""

import json
import os
import statistics

import pytest

import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    return traffic.load_mix(os.path.join(BENCH, "traffic", f"{name}.json"))


def flatten(plan, limit=4):
    return [(c.start_s, r) for c in plan.chains
            for r in list(_take(c.requests, limit))]


def _take(it, n):
    for _, x in zip(range(n), it):
        yield x


@pytest.mark.parametrize("name", ["chat-steady", "batch-full", "sessions-routed"])
def test_same_seed_same_plan_and_large_seeds_work(name):
    seed = 2 ** 31 + 12345            # more than 32 signed bits hold
    a = flatten(traffic.build(mix(name), seed, 20.0))
    b = flatten(traffic.build(mix(name), seed, 20.0))
    assert [(t, r.prompt, r.max_tokens) for t, r in a] == \
           [(t, r.prompt, r.max_tokens) for t, r in b]
    c = flatten(traffic.build(mix(name), seed + 1, 20.0))
    assert [r.prompt for _, r in a] != [r.prompt for _, r in c]


def _window(name, seed, seconds=30.0):
    plan = traffic.build(mix(name), seed, seconds)
    return [(c.start_s, list(_take(c.requests, 4))) for c in plan.chains
            if c.start_s >= 0]


def _is_rotation(a, b):
    return len(a) == len(b) and any(a == b[k:] + b[:k] for k in range(len(b)))


@pytest.mark.parametrize("name", ["chat-steady", "sessions-routed"])
def test_every_seed_sends_the_same_cycle_from_another_start(name):
    """Sizes AND neighbours are the same for every seed: one seed's window is
    a rotation of another's, gaps included."""
    def shape(seed):
        rows = _window(name, seed)
        sizes = [tuple((r.prompt_tokens, r.max_tokens, round(r.think_after_s, 6))
                       for r in turns) for _, turns in rows]
        starts = [t for t, _ in rows]
        gaps = [round(b - a, 6) for a, b in zip(starts, starts[1:])]
        return sizes, gaps, starts

    s1, g1, t1 = shape(1)
    s2, g2, t2 = shape(99)
    assert s1 != s2 and _is_rotation(s1, s2)
    assert t1[0] == t2[0] == 0.0
    # The one gap that closes the cycle falls after the window's last arrival.
    assert len(set(g1) ^ set(g2)) <= 2


def test_the_ramp_is_the_stretch_of_the_cycle_before_the_window():
    m = mix("chat-steady")
    plan = traffic.build(m, 5, 30.0)
    rows = [(c.start_s, next(c.requests)) for c in plan.chains]
    ramp = [r for t, r in rows if t < 0]
    window = [r for t, r in rows if t >= 0]
    assert ramp and all(-m["ramp_s"] <= t for t, _ in rows)
    k = len(ramp)
    assert [r.prompt_tokens for r in ramp] == [r.prompt_tokens for r in window[-k:]]


def test_chat_steady_hits_its_stated_distributions():
    m = mix("chat-steady")
    plan = traffic.build(m, 7, 50.0)
    window = [(c.start_s, next(c.requests)) for c in plan.chains if c.start_s >= 0]
    n = len(window)
    assert n == round(m["rate_rps"] * 50.0)
    prompts = [r.prompt_tokens for _, r in window]
    outs = [r.max_tokens for _, r in window]
    assert statistics.median(prompts) == pytest.approx(200, rel=0.05)
    assert statistics.median(outs) == pytest.approx(64, rel=0.05)
    assert min(prompts) >= 32 and max(prompts) <= 1500
    assert min(outs) >= 16 and max(outs) <= 256
    # Byte tokenizer: one token a character plus BOS; unique heads.
    assert all(len(r.prompt) + 1 == r.prompt_tokens for _, r in window)
    assert len({r.prompt[:16] for _, r in window}) == n
    # Exponential gaps: mean 1/rate, coefficient of variation near 1.
    starts = [t for t, _ in window]
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    assert statistics.mean(gaps) == pytest.approx(1 / m["rate_rps"], rel=0.05)
    assert 0.8 < statistics.pstdev(gaps) / statistics.mean(gaps) < 1.1
    assert all(0 <= t < 50.0 for t in starts)


def test_batch_full_is_a_closed_loop_over_one_shared_queue():
    m = mix("batch-full")
    plan = traffic.build(m, 3, 30.0)
    assert len(plan.chains) == m["clients"] == 32
    assert all(-m["ramp_s"] <= c.start_s < 0 for c in plan.chains)
    a = [next(plan.chains[0].requests), next(plan.chains[1].requests),
         next(plan.chains[0].requests)]
    assert len({r.rid for r in a}) == 3
    lens = [next(plan.chains[i % 32].requests).prompt_tokens for i in range(400)]
    assert min(lens) >= 256 and max(lens) <= 1024       # bucket 1024 at most
    assert statistics.median(lens) == pytest.approx(512, rel=0.1)  # log-uniform


def test_sessions_carry_their_history_and_fit_the_context():
    m = mix("sessions-routed")
    plan = traffic.build(m, 11, 40.0)
    assert len(plan.preload) == 48
    assert all(r.prompt_tokens == 1024 and r.max_tokens == 1 for r in plan.preload)
    systems = {r.prompt for r in plan.preload}
    longest = 0
    used = []
    for chain in plan.chains:
        turns = list(chain.requests)
        assert [t.turn for t in turns] == [0, 1, 2, 3]
        assert turns[0].prompt[:1023] in systems
        used.append(turns[0].prompt[:1023])
        for prev, nxt in zip(turns, turns[1:]):
            assert nxt.prompt.startswith(prev.prompt)
            grown = len(nxt.prompt) - len(prev.prompt) - prev.max_tokens
            assert 64 <= grown <= 160
            assert 1.0 <= prev.think_after_s <= 3.0
        longest = max(longest, max(t.prompt_tokens + t.max_tokens for t in turns))
    assert longest <= 2048                    # max_model_len of the cells
    # Zipf(1): the most used prompt about 1/H(48) = 22% of sessions.
    top = max(used.count(s) for s in set(used)) / len(used)
    assert 0.17 < top < 0.28


def test_zipf_counts_are_exact_and_ordered():
    counts = traffic.zipf_counts(48, 1.0, 175)
    assert sum(counts) == 175 and counts == sorted(counts, reverse=True)
    assert counts[0] == 39                    # 175 / H(48) = 39.2


def test_warmup_covers_prefix_shapes_and_probes_stay_under_a_block():
    groups = traffic.warmup_requests(mix("sessions-routed"), 5)
    prefix_groups = [g for g in groups if len(g) > 1]
    assert len(prefix_groups) == 2
    for g in prefix_groups:
        assert all(r.prompt.startswith(g[0].prompt) for r in g[1:])
    assert all(r.prompt_tokens < 16 for r in traffic.probe_requests(5))


def test_unknown_kind_and_distribution_are_errors():
    with pytest.raises(ValueError):
        traffic.build({"kind": "nope"}, 0, 1.0)
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "nope"}, 0.5)
