"""One general traffic generator, driven by a file of parameters.

A traffic mix is `chipbench/traffic/<mix>.json`: a `kind` (`open_poisson`,
`closed_clients`, `open_sessions`), the length distributions, the rate or the
client count, the ramp before the window, and the warm-up set. A later PR adds
a mix by adding a file; it needs no code here.

Every seed gets the same work in another order: lengths, arrival gaps and
think times are the quantile points of their distributions (a stratified
sample) in one fixed cyclic order per mix; the seed picks where in the cycle
the window starts and draws the text (see `build`).

Lengths are in tokens of the served tokenizer. The configurations serve the
program's `byte` tokenizer: one token per ASCII character plus one BOS, so a
prompt of n tokens is n - 1 characters.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import random
import statistics
from typing import Iterator

ALPHABET = "abcdefghijklmnopqrstuvwxyz ,."
BOS_TOKENS = 1


# ---- distributions as quantile grids ---------------------------------------

def quantile(dist: dict, q: float) -> float:
    """The q-quantile of a distribution given as {"dist": name, ...}."""
    kind = dist["dist"]
    if kind == "fixed":
        x = dist["value"]
    elif kind == "uniform":
        x = dist["lo"] + (dist["hi"] - dist["lo"]) * q
    elif kind == "loguniform":
        x = dist["lo"] * (dist["hi"] / dist["lo"]) ** q
    elif kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(q)
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif kind == "exponential":
        x = -dist["mean"] * math.log(1.0 - q)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(max(x, dist.get("lo", x)), dist.get("hi", x))


def grid(dist: dict, n: int, rng: random.Random, integer: bool = True) -> list:
    """n stratified draws: the mid-quantile points, shuffled by rng."""
    xs = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    if integer:
        xs = [int(round(x)) for x in xs]
    rng.shuffle(xs)
    return xs


def zipf_counts(n_items: int, s: float, total: int) -> list[int]:
    """How many of `total` draws each of n_items ranks gets under Zipf(s),
    by largest remainder: the same counts for every seed."""
    weights = [1.0 / (r + 1) ** s for r in range(n_items)]
    share = [w * total / sum(weights) for w in weights]
    counts = [int(x) for x in share]
    by_remainder = sorted(range(n_items), key=lambda i: share[i] - counts[i],
                          reverse=True)
    for i in by_remainder[: total - sum(counts)]:
        counts[i] += 1
    return counts


def text(rng: random.Random, n_chars: int) -> str:
    return "".join(rng.choices(ALPHABET, k=max(n_chars, 0)))


def prompt_of(head: str, n_tokens: int, rng: random.Random) -> str:
    """A prompt of exactly n_tokens under the byte tokenizer whose first
    characters are `head` (unique heads keep requests from sharing blocks)."""
    n_chars = n_tokens - BOS_TOKENS
    return (head + text(rng, n_chars - len(head)))[:n_chars]


# ---- the plan ---------------------------------------------------------------

@dataclasses.dataclass
class Req:
    rid: str
    prompt: str
    prompt_tokens: int
    max_tokens: int
    session: int = -1
    turn: int = 0
    think_after_s: float = 0.0   # wait after this answer before the next turn


@dataclasses.dataclass
class Chain:
    """Requests sent one after another: the first at start_s (seconds from the
    window's start, negative in the ramp), each next one think_after_s after
    the previous answer ended. An open-loop request is a chain of one."""
    start_s: float
    requests: Iterator[Req]


@dataclasses.dataclass
class Plan:
    chains: list[Chain]
    preload: list[Req]            # sent once in set-up, through the gateway
    temperature: float


def load_mix(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def mix_path(root: str, mix: str) -> str:
    return os.path.join(root, "chipbench", "traffic", f"{mix}.json")


def build(mix: dict, seed: int, seconds: float, rate: float | None = None,
          tag: str = "w") -> Plan:
    """The plan for one window. `rate` overrides the file's (sweep mode);
    `tag` keeps the prompts of successive windows in one process apart.

    The ORDER of sizes and gaps is fixed by the mix (one cyclic sequence per
    mix, rate and window length); the seed chooses where in the cycle the
    window starts, and draws the text. The window covers the cycle once and
    the ramp is the stretch of the cycle just before it, so every seed sends
    the same requests with the same neighbours, a different one first. Two
    seeds then differ by the system's own jitter, not by which burst met
    which long prompt - with some 200 requests in a window that sampling
    noise alone moved a median TTFT by 5% (PERF.md, Findings, PR 23)."""
    kind = mix["kind"]
    order = random.Random(f"chipbench/order/{kind}/{mix.get('order', 0)}")
    draw = random.Random(f"{seed}/{tag}/{kind}")
    builder = {"open_poisson": _open_poisson, "closed_clients": _closed_clients,
               "open_sessions": _open_sessions}.get(kind)
    if builder is None:
        raise ValueError(f"unknown traffic kind {kind!r}")
    chains, preload = builder(mix, order, draw, seed, seconds, rate, tag)
    return Plan(chains, preload, float(mix.get("temperature", 0.0)))


def _one(req: Req) -> Iterator[Req]:
    yield req


def cycle_times(rate: float, seconds: float, ramp_s: float,
                order: random.Random, draw: random.Random
                ) -> list[tuple[int, float]]:
    """(index into the cycle, arrival time) for the ramp and the window: n =
    rate x seconds arrivals whose gaps (an exponential grid in the mix's
    fixed order) fill the window exactly, read from a seed-chosen offset; the
    ramp walks the cycle backwards from there."""
    n = int(round(rate * seconds))
    if n <= 0:
        return []
    gaps = grid({"dist": "exponential", "mean": 1.0}, n, order, integer=False)
    scale = seconds / sum(gaps)
    gaps = [g * scale for g in gaps]
    offset = draw.randrange(n)
    out, t = [], 0.0
    for j in range(n):
        out.append(((offset + j) % n, t))
        t += gaps[(offset + j) % n]
    t, j = 0.0, 1
    while True:
        i = (offset - j) % n
        t -= gaps[i]
        if t < -ramp_s or j > n:
            break
        out.append((i, t))
        j += 1
    return sorted(out, key=lambda it: it[1])


def _open_poisson(mix, order, draw, seed, seconds, rate, tag):
    rate = rate if rate is not None else mix["rate_rps"]
    times = cycle_times(rate, seconds, mix.get("ramp_s", 0.0), order, draw)
    n = int(round(rate * seconds))
    p_lens = grid(mix["prompt_tokens"], n, order)
    o_lens = grid(mix["output_tokens"], n, order)
    chains = []
    for k, (i, t) in enumerate(times):
        rid = f"{tag}{k}-{seed}"
        chains.append(Chain(t, _one(Req(
            rid, prompt_of(rid + " ", p_lens[i], draw), p_lens[i], o_lens[i]))))
    return chains, []


def _closed_clients(mix, order, draw, seed, seconds, rate, tag):
    clients = int(rate if rate is not None else mix["clients"])
    pool = int(mix.get("pool", 512))
    p_lens = grid(mix["prompt_tokens"], pool, order)
    o_lens = grid(mix["output_tokens"], pool, order)
    offset = draw.randrange(pool)
    counter = iter(range(10 ** 9))

    def client() -> Iterator[Req]:
        while True:
            k = next(counter)   # one shared queue of work, taken in order
            i = (offset + k) % pool
            rid = f"{tag}c{k}-{seed}"
            r = random.Random(f"{seed}/{tag}/{k}")
            yield Req(rid, prompt_of(rid + " ", p_lens[i], r), p_lens[i],
                      o_lens[i])

    ramp = mix.get("ramp_s", 0.0)
    return [Chain(-ramp + ramp * c / clients, client())
            for c in range(clients)], []


def _open_sessions(mix, order, draw, seed, seconds, rate, tag):
    rate = rate if rate is not None else mix["session_rate_rps"]
    times = cycle_times(rate, seconds, mix.get("ramp_s", 0.0), order, draw)
    n, turns = int(round(rate * seconds)), int(mix["turns"])
    n_prefix = int(mix["system_prompts"])
    sys_tokens = int(mix["system_prompt_tokens"])
    # System prompts do not depend on the window's tag: a sweep's windows and
    # the set-up preload share them, as a deployment's sessions do.
    systems = [prompt_of(f"sys{p}-{seed} ", sys_tokens,
                         random.Random(f"{seed}/system/{p}"))
               for p in range(n_prefix)]
    which = [p for p, c in enumerate(zipf_counts(n_prefix, mix["zipf_s"], n))
             for _ in range(c)]
    order.shuffle(which)
    user = grid(mix["user_tokens"], n * turns, order)
    answer = grid(mix["answer_tokens"], n * turns, order)
    think = grid(mix["think_s"], n * turns, order, integer=False)

    def session(s: int, i: int) -> Iterator[Req]:
        r = random.Random(f"{seed}/{tag}/session/{s}")
        history = systems[which[i]]
        for t in range(turns):
            k = i * turns + t
            # The first message opens with the session's id, so two sessions
            # on one system prompt share that prompt and nothing after it.
            history += prompt_of(f"<{tag}{s}.{t}-{seed}>", user[k] + BOS_TOKENS, r)
            yield Req(f"{tag}s{s}t{t}-{seed}", history,
                      len(history) + BOS_TOKENS, answer[k], session=s, turn=t,
                      think_after_s=think[k])
            # What the next turn carries as the assistant's answer: text of
            # the answer's length from the seed, not the model's own output,
            # so the prompts are the same whatever the weights say.
            history += text(r, answer[k])

    chains = [Chain(t, session(s, i)) for s, (i, t) in enumerate(times)]
    preload = [Req(f"{tag}pre{p}-{seed}", systems[p], sys_tokens, 1)
               for p in range(n_prefix)]
    return chains, preload


# ---- warm-up ----------------------------------------------------------------

def warmup_requests(mix: dict, seed: int) -> list[list[Req]]:
    """The shapes this mix will use, as groups sent to every replica directly,
    one request after another within a group (a prefix, then the prompts that
    continue it). Prompts here share nothing with the window's."""
    spec = mix.get("warmup", {})
    max_tokens = int(spec.get("max_tokens", 4))
    groups = []
    for i, n in enumerate(spec.get("plain_prompt_tokens", [])):
        r = random.Random(f"{seed}/warm/plain/{i}")
        rid = f"warm-p{i}-{seed}"
        groups.append([Req(rid, prompt_of(rid + " ", n, r), n, max_tokens)])
    for j, item in enumerate(spec.get("prefix", [])):
        r = random.Random(f"{seed}/warm/prefix/{j}")
        rid = f"warm-x{j}-{seed}"
        base = prompt_of(rid + " ", item["prefix_tokens"], r)
        group = [Req(rid, base, item["prefix_tokens"], max_tokens)]
        for k, extra in enumerate(item["suffix_tokens"]):
            tail = prompt_of(f"<{k}>", extra + BOS_TOKENS, r)
            group.append(Req(f"{rid}-{k}", base + tail,
                             item["prefix_tokens"] + extra, max_tokens))
        groups.append(group)
    return groups


def burst_requests(mix: dict, seed: int) -> list[list[Req]]:
    """Requests sent together, so that the decode step runs for real at each
    lane count the window will reach: `warmup.bursts` lists
    {"concurrent": k, "prompt_tokens": n, "max_tokens": m}. The engine's own
    --warmup compiles the decode buckets with dummy inputs, and the first REAL
    dispatch of a bucket then loads the program again (seconds from the
    cache, a whole compile without it) - inside the window, if nothing real
    ran at that width before (PERF.md, Findings, PR 23)."""
    out = []
    for j, b in enumerate(mix.get("warmup", {}).get("bursts", [])):
        reqs = []
        for i in range(b["concurrent"]):
            rid = f"warm-b{j}.{i}-{seed}"
            r = random.Random(f"{seed}/warm/burst/{j}/{i}")
            reqs.append(Req(rid, prompt_of(rid + " ", b["prompt_tokens"], r),
                            b["prompt_tokens"], b["max_tokens"]))
        out.append(reqs)
    return out


def probe_requests(seed: int, n: int = 4) -> list[Req]:
    """Short prompts for the before/after comparison: under one cache block
    (16 tokens), so the second sending finds nothing cached and runs the very
    programs the first did."""
    out = []
    for i in range(n):
        r = random.Random(f"{seed}/probe/{i}")
        out.append(Req(f"probe{i}-{seed}", prompt_of("", 13, r), 13, 16))
    return out
