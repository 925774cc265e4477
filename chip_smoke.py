"""Prove that the served path runs on the chip.

    python chip_smoke.py            # one chip: gateway -> engine server -> TpuEngine
    python chip_smoke.py --chips 4  # four one-chip replicas + one P/D handoff

One chip (what the driver runs): the paged-attention kernel is compared with
its XLA reference on the chip, then `python -m ...engine.server --backend tpu
--model qwen3-4b` (36 layers, published widths, random weights from the
engine's seed) and `python -m ...router.gateway` are started as their own
processes and a client sends, at temperature 0: one unary completion, one
streamed, eight concurrent with prompts of about 100-1,500 tokens, and the
longest prompt again (which must hit the prefix cache and return the same
text). Four chips: four replicas behind one gateway serve sixteen requests in
four prefix-sharing sessions, one fixed prompt is compared across all four,
and one request is prefilled on replica 0 and decoded on replica 1 through
`python -m ...router.sidecar.proxy`.

One process per chip: this parent imports neither jax nor the engine — the
device's identity and everything the engine resolved come from the engine
process over /health and /metrics. Every line printed before the last is a
set-up fact of this run (what was bound, compiled, cached, answered), not a
benchmark number. The last line is the verdict:
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Any phase that fails ends the run non-zero with `ok` false; without a TPU the
engine refuses to start, so the run fails.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import random
import re
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PKG = "llm_d_inference_scheduler_tpu"
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
SERVED_HEADER = "x-gateway-destination-endpoint-served"
# Gauges the router's data layer scrapes from an engine (SURVEY §2.5).
ENGINE_GAUGES = ("jetstream:num_requests_running",
                 "jetstream:num_requests_waiting",
                 "jetstream:kv_cache_usage_perc")

# Kernel against reference, both in bf16 on the chip. Each side rounds its
# output to bf16 (8 mantissa bits: neighbouring values are 2^-8 apart
# relative, 2^-6 = 0.016 absolute for |x| in [2, 4), the largest outputs a
# one-token context gives), and each feeds the MXU bf16 operands at a
# different point: the kernel rounds q*scale and the softmax weights, the
# reference rounds q and scales after. So the two may land on neighbouring
# bf16 values, and on nothing further apart.
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2


@dataclasses.dataclass
class Settings:
    """What a run serves. The defaults are the chip run; tests drive the same
    phases at the `tiny` preset on the CPU they pin."""
    model: str = "qwen3-4b"
    max_batch: int = 16
    max_model_len: int = 2048
    platform: str | None = None     # None: the engine must find a TPU itself
    seed: int = 0
    gen_tokens: int = 16
    # Prompt lengths in characters (the byte tokenizer adds one BOS token).
    # Chosen to touch few prefill buckets (each a whole-model compile):
    # 128, 1024 and 2048.
    unary_len: int = 1100
    stream_len: int = 600
    concurrent_lens: tuple[int, ...] = (100, 120, 600, 800, 1000, 1100,
                                        1300, 1500)
    session_prefix_len: int = 800
    session_tail_len: int = 100
    base_port: int = 18700
    start_timeout_s: float = 420.0
    request_timeout_s: float = 280.0   # under the gateway's 300 s upstream cap


class SmokeFailure(Exception):
    pass


def say(**fact) -> None:
    print(json.dumps(fact), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def prompt_text(seed: int, tag: str, n_chars: int) -> str:
    rng = random.Random(f"{seed}/{tag}")
    return "".join(rng.choice("abcdefghijklmnopqrstuvwxyz ,.")
                   for _ in range(n_chars))


# ---- processes --------------------------------------------------------------

@dataclasses.dataclass
class Child:
    name: str
    proc: subprocess.Popen
    started: float

    def log_tail(self, n: int = 1500) -> str:
        path = os.path.join(LOG_DIR, f"{self.name}.log")
        with open(path, errors="replace") as f:
            return f.read()[-n:]


class Procs:
    """The child processes of one run; stop() ends every one of them."""

    def __init__(self):
        self.children: list[Child] = []
        os.makedirs(LOG_DIR, exist_ok=True)

    def start(self, name: str, module: str, *args: str) -> Child:
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        with open(os.path.join(LOG_DIR, f"{name}.log"), "w") as log:
            proc = subprocess.Popen(
                [sys.executable, "-m", f"{PKG}.{module}", *args], cwd=REPO,
                env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)
        child = Child(name, proc, time.monotonic())
        self.children.append(child)
        return child

    def stop(self) -> None:
        for child in self.children:
            if child.proc.poll() is None:
                try:
                    os.killpg(child.proc.pid, signal.SIGTERM)
                except ProcessLookupError:  # exited since the poll
                    pass
        deadline = time.monotonic() + 20.0
        for child in self.children:
            try:
                child.proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                os.killpg(child.proc.pid, signal.SIGKILL)
                child.proc.wait(timeout=10)
        self.children = []


async def wait_healthy(client, child: Child, url: str,
                       timeout_s: float) -> tuple[dict, float]:
    """Poll url until it answers 200; returns (body, seconds since the
    process was started). A process that exits first has refused to start."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise SmokeFailure(
                f"{child.name} exited with {child.proc.returncode}: "
                f"{child.log_tail()}")
        try:
            r = await client.get(url, timeout=5.0)
            if r.status_code == 200:
                return r.json(), time.monotonic() - child.started
        except Exception:  # not listening yet
            pass
        await asyncio.sleep(0.5)
    raise SmokeFailure(f"{url} not healthy after {timeout_s:.0f} s: "
                       f"{child.log_tail()}")


def engine_args(s: Settings, port: int, device_index: int | None) -> list[str]:
    args = ["--backend", "tpu", "--model", s.model, "--port", str(port),
            "--max-batch", str(s.max_batch),
            "--max-model-len", str(s.max_model_len)]
    if s.platform:
        args += ["--platform", s.platform]
    if device_index is not None:
        args += ["--device-index", str(device_index)]
    return args


def gateway_args(port: int, engine_ports: list[int]) -> list[str]:
    return ["--config-file", os.path.join(REPO, "examples", "monolithic.yaml"),
            "--port", str(port), "--endpoints",
            ",".join(f"127.0.0.1:{p}" for p in engine_ports)]


# ---- requests ---------------------------------------------------------------

def completion_body(s: Settings, prompt: str, **extra) -> dict:
    return {"model": s.model, "prompt": prompt, "max_tokens": s.gen_tokens,
            "temperature": 0, "ignore_eos": True, **extra}


def report_answer(s: Settings, name: str, status: int, headers, usage: dict,
                  text: str, **more) -> dict:
    """Print one answer's facts and hold it to 200 and the tokens asked."""
    details = usage.get("prompt_tokens_details") or {}
    fact = {"request": name, "status": status,
            "served_by": headers.get(SERVED_HEADER),
            "prompt_tokens": usage.get("prompt_tokens"),
            "completion_tokens": usage.get("completion_tokens"),
            "cached_tokens": details.get("cached_tokens", 0),
            "text": text}
    say(**fact, **more)
    check(status == 200, f"{name}: HTTP {status}")
    check(fact["completion_tokens"] == s.gen_tokens,
          f"{name}: {fact['completion_tokens']} tokens, asked {s.gen_tokens}")
    return fact


async def complete(client, s: Settings, url: str, name: str, prompt: str,
                   headers: dict | None = None) -> dict:
    r = await client.post(url + "/v1/completions",
                          json=completion_body(s, prompt), headers=headers,
                          timeout=s.request_timeout_s)
    ok = r.status_code == 200
    body = r.json() if ok else {}
    return report_answer(s, name, r.status_code, r.headers,
                         body.get("usage") or {},
                         (body.get("choices") or [{}])[0].get("text", ""),
                         **({} if ok else {"error": r.text[:300]}))


async def complete_streamed(client, s: Settings, url: str, name: str,
                            prompt: str) -> dict:
    text, usage, events, done = "", {}, 0, False
    async with client.stream(
            "POST", url + "/v1/completions",
            json=completion_body(s, prompt, stream=True),
            timeout=s.request_timeout_s) as r:
        status, headers = r.status_code, r.headers
        async for line in r.aiter_lines():
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                done = True
                continue
            chunk = json.loads(line[len("data: "):])
            events += 1
            text += chunk["choices"][0].get("text", "")
            usage = chunk.get("usage") or usage
    fact = report_answer(s, name, status, headers, usage, text,
                         sse_events=events, sse_done=done)
    check(done and events >= 2, f"{name}: SSE stream incomplete "
                                f"({events} events, done={done})")
    return fact


def parse_metrics(text: str) -> dict:
    """The engine's Prometheus text, reduced to what the smoke reports."""
    samples = re.findall(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})? (\S+)$", text,
                         re.MULTILINE)
    shapes = sorted(
        " ".join(re.search(rf'{key}="([^"]*)"', labels).group(1)
                 for key in ("op", "bucket"))
        for name, labels, _ in samples
        if name == "jetstream:compile_events_total")
    seconds = sum(float(v) for name, _, v in samples
                  if name == "jetstream:compile_duration_seconds_sum")
    return {"names": {name for name, _, _ in samples},
            "compiled_shapes": shapes,
            "first_dispatch_seconds_sum": round(seconds, 1)}


async def engine_facts(client, port: int, expect_tpu: bool) -> dict:
    """What one engine process reports after serving: the device it bound,
    what it resolved, what it compiled, its peak memory."""
    health = (await client.get(f"http://127.0.0.1:{port}/health",
                               timeout=10.0)).json()
    m = parse_metrics((await client.get(f"http://127.0.0.1:{port}/metrics",
                                        timeout=10.0)).text)
    missing = [g for g in ENGINE_GAUGES if g not in m["names"]]
    check(not missing, f"engine :{port} /metrics lacks {missing}")
    say(engine=port, set_up_fact=True, device=health["device"],
        settings=health["settings"], memory=health["memory"],
        decode_kernel_in_program=health["decode_kernel_in_program"],
        kv_imports=health["kv_imports"],
        compiled_shape_count=len(m["compiled_shapes"]),
        compiled_shapes=m["compiled_shapes"],
        first_dispatch_seconds_sum=m["first_dispatch_seconds_sum"],
        note="first_dispatch_seconds_sum is trace + compile (or cache load) "
             "of each shape's first call, as jetstream:compile_duration_"
             "seconds sums it")
    if expect_tpu:
        programs = health["decode_kernel_in_program"]
        check(health["settings"]["pallas_attention"] and programs
              and all(programs.values()),
              f"engine :{port}: Pallas call not in every decode program "
              f"served with: {programs}")
    return dict(health, shapes=m["compiled_shapes"])


# ---- one chip ---------------------------------------------------------------

async def serve_phases(client, s: Settings, url: str) -> None:
    """The four request phases against a gateway at url."""
    unary = await complete(client, s, url, "unary",
                           prompt_text(s.seed, "unary", s.unary_len))
    streamed = await complete_streamed(
        client, s, url, "streamed", prompt_text(s.seed, "stream", s.stream_len))
    prompts = [prompt_text(s.seed, f"concurrent-{i}", n)
               for i, n in enumerate(s.concurrent_lens)]
    firsts = await asyncio.gather(*[
        complete(client, s, url, f"concurrent-{i}", p)
        for i, p in enumerate(prompts)])
    longest = max(range(len(prompts)), key=lambda i: len(prompts[i]))
    again = await complete(client, s, url, "longest-again", prompts[longest])
    for fact in (unary, streamed, *firsts, again):
        check(fact["served_by"], f"{fact['request']}: no {SERVED_HEADER}")
    check(again["cached_tokens"] > 0,
          "longest-again reported no cached prompt tokens: the prefix-"
          "continuation prefill did not run")
    check(again["text"] == firsts[longest]["text"],
          f"longest-again answered {again['text']!r}, its first pass "
          f"{firsts[longest]['text']!r}")
    say(phase="prefix_repeat", cached_tokens=again["cached_tokens"],
        same_text_as_first_pass=True,
        note="text is the byte tokenizer's view of the tokens (id mod 256)")


async def run_one_chip(s: Settings, procs: Procs) -> dict:
    import httpx

    eport, gport = s.base_port + 10, s.base_port
    async with httpx.AsyncClient() as client:
        engine = procs.start("engine", "engine.server",
                             *engine_args(s, eport, None))
        health, up_s = await wait_healthy(
            client, engine, f"http://127.0.0.1:{eport}/health",
            s.start_timeout_s)
        say(phase="engine_start", set_up_fact=True,
            seconds_process_start_to_healthy=round(up_s, 1),
            device=health["device"], settings=health["settings"])
        gateway = procs.start("gateway", "router.gateway",
                              *gateway_args(gport, [eport]))
        await wait_healthy(client, gateway,
                           f"http://127.0.0.1:{gport}/health", 60.0)
        await serve_phases(client, s, f"http://127.0.0.1:{gport}")
        facts = await engine_facts(client, eport, s.platform is None)
        lanes = [int(sh.split()[1].split("x")[0]) for sh in facts["shapes"]
                 if sh.startswith("decode ")]
        check(max(lanes, default=0) >= 4,
              f"no decode program wider than {max(lanes, default=0)} lanes "
              "ran: the concurrent requests never shared a step")
        return facts["device"]


# ---- four chips -------------------------------------------------------------

async def start_replicas(client, s: Settings, procs: Procs,
                         eports: list[int], gport: int) -> None:
    """One engine process per chip, started side by side, and a gateway
    with the examples/monolithic.yaml profile in front of them all."""
    engines = [procs.start(f"engine{i}", "engine.server",
                           *engine_args(s, p, i))
               for i, p in enumerate(eports)]
    ups = await asyncio.gather(*[
        wait_healthy(client, e, f"http://127.0.0.1:{p}/health",
                     s.start_timeout_s)
        for e, p in zip(engines, eports)])
    for i, (health, up_s) in enumerate(ups):
        say(phase="replica_start", replica=i, set_up_fact=True,
            seconds_process_start_to_healthy=round(up_s, 1),
            device=health["device"], memory=health["memory"],
            settings=health["settings"])
    gateway = procs.start("gateway", "router.gateway",
                          *gateway_args(gport, eports))
    await wait_healthy(client, gateway,
                       f"http://127.0.0.1:{gport}/health", 60.0)


async def replica_phases(client, s: Settings, eports: list[int],
                         gport: int) -> dict:
    """Sessions through the gateway, one prompt across all replicas, and a
    different device under each; returns the device for the verdict."""
    n = len(eports)
    url = f"http://127.0.0.1:{gport}"

    # Four turns in each of n sessions; a session's turns share its long
    # prefix. First turns go one after another, so that each cold session
    # settles on a replica before the next is scheduled; the later turns of
    # all sessions then run side by side.
    def turn(sess: int, t: int) -> str:
        return (prompt_text(s.seed, f"session-{sess}", s.session_prefix_len)
                + prompt_text(s.seed, f"session-{sess}-turn-{t}",
                              s.session_tail_len))

    sessions = [[await complete(client, s, url, f"session-{k}-turn-0",
                                turn(k, 0))] for k in range(n)]

    async def rest_of(sess: int):
        for t in range(1, 4):
            sessions[sess].append(await complete(
                client, s, url, f"session-{sess}-turn-{t}", turn(sess, t)))

    await asyncio.gather(*[rest_of(k) for k in range(n)])
    served: dict[str, int] = {}
    for fact in (f for facts in sessions for f in facts):
        check(fact["served_by"], f"{fact['request']}: no {SERVED_HEADER}")
        served[fact["served_by"]] = served.get(fact["served_by"], 0) + 1
    say(phase="sessions", requests=sum(served.values()),
        served_by_replica=served,
        cached_tokens_by_session=[[f["cached_tokens"] for f in facts]
                                  for facts in sessions])
    check(len(served) == n, f"not every replica served a request: {served}")

    # One fixed prompt, sent to each replica directly, one after another:
    # the first compiles the bucket, the others load it from the cache.
    fixed = prompt_text(s.seed, "fixed", s.session_tail_len)
    same = [await complete(client, s, f"http://127.0.0.1:{p}",
                           f"fixed-prompt-replica-{i}", fixed)
            for i, p in enumerate(eports)]
    check(len({f["text"] for f in same}) == 1,
          f"replicas disagree on one prompt: {[f['text'] for f in same]}")
    say(phase="replica_parity", same_text_from_all=True)

    # A different device under each replica. A TPU process is shown one chip
    # only, so its device id says little; what no two replicas can share is
    # a chip's memory: each holds more than half of one, all at the same
    # time. CPU processes all see every virtual device, and there the ids
    # tell.
    devices = [await engine_facts(client, p, s.platform is None)
               for p in eports]
    say(phase="replica_devices", set_up_fact=True,
        devices=[d["device"] for d in devices],
        bytes_in_use=[d["memory"].get("bytes_in_use") for d in devices])
    if s.platform is None:
        check(all(d["device"]["count"] == 1 for d in devices),
              "a replica process sees more than its own chip")
        check(all(d["memory"]["bytes_in_use"] > d["memory"]["bytes_limit"] / 2
                  for d in devices),
              "replicas could be sharing a chip: none of them fills half "
              "of one")
    else:
        check(len({d["device"]["id"] for d in devices}) == n,
              "replicas share a device")
    first = devices[0]["device"]
    return {"platform": first["platform"], "kind": first["kind"], "count": n}


async def pd_phase(client, s: Settings, procs: Procs, eports: list[int],
                   sport: int) -> None:
    """One P/D handoff: replica 0 prefills, replica 1 decodes, through the
    sidecar in front of replica 1; compared with replica 2 alone (replica 1
    now holds the prompt's blocks: there the same prompt would be a
    prefix-cache hit, another program). Which wire carried the KV is
    reported, not required."""
    sidecar = procs.start("sidecar", "router.sidecar.proxy",
                          "--port", str(sport),
                          "--decoder", f"http://127.0.0.1:{eports[1]}")
    await wait_healthy(client, sidecar,
                       f"http://127.0.0.1:{sport}/health", 60.0)
    prompt = prompt_text(s.seed, "pd", s.stream_len)
    split = await complete(
        client, s, f"http://127.0.0.1:{sport}", "pd-split", prompt,
        headers={"x-prefiller-host-port": f"127.0.0.1:{eports[0]}"})
    alone = await complete(client, s, f"http://127.0.0.1:{eports[2]}",
                           "pd-monolithic", prompt)
    health = (await client.get(f"http://127.0.0.1:{eports[1]}/health",
                               timeout=10.0)).json()
    imports = health["kv_imports"]
    say(phase="pd_handoff", prefill_replica=0, decode_replica=1,
        decode_replica_kv_wire=health["settings"]["kv_wire"],
        kv_imports_on_decode_replica=imports,
        wire=("device" if imports["device"] else
              "host" if imports["host"] else "none: decoded locally"),
        same_text_as_one_replica=split["text"] == alone["text"])
    check(imports["device"] + imports["host"] >= 1,
          "the decode replica imported no KV: the handoff fell back to a "
          "local prefill")
    check(split["text"] == alone["text"],
          f"P/D split answered {split['text']!r}, one replica "
          f"{alone['text']!r}")


async def run_four_chips(s: Settings, procs: Procs, n: int = 4) -> dict:
    import httpx

    gport, sport = s.base_port, s.base_port + 5
    eports = [s.base_port + 10 + i for i in range(n)]
    async with httpx.AsyncClient() as client:
        await start_replicas(client, s, procs, eports, gport)
        device = await replica_phases(client, s, eports, gport)
        await pd_phase(client, s, procs, eports, sport)
        return device


# ---- the kernel against its reference (child process, on the chip) ----------

def kernel_check(seed: int) -> int:
    """Runs in its own process, before any engine holds the chip, and exits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, REPO)
    from llm_d_inference_scheduler_tpu.kvcache.pages import PageGeometry
    from llm_d_inference_scheduler_tpu.models.configs import QWEN3_4B as m
    from llm_d_inference_scheduler_tpu.ops.attention import (
        paged_decode_attention,
    )
    from llm_d_inference_scheduler_tpu.ops.pallas_paged_attention import (
        paged_decode_attention_pallas,
    )
    from llm_d_inference_scheduler_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        say(phase="kernel_check", error=f"needs a TPU; JAX opened {dev}")
        return 1
    # Qwen3-4B's decode shapes at max_batch 16 x max_model_len 2048: 32 Q /
    # 8 KV heads of 128, 2,049 bf16 pages of 16 tokens, table 128 wide; the
    # contexts run from one token to the full 2,048. The pools are stacked as
    # the engine holds them; two layers here, each its own draw, and the
    # kernel reads the second: the reference is handed that layer's pool
    # alone, so a kernel that ignored the index would not match.
    batch, width, layers, layer = 16, 128, 2, 1
    geom = PageGeometry.for_model(dataclasses.replace(m, n_layers=layers),
                                  1 + batch * width, width)
    n_pages, block = geom.n_blocks, geom.block
    dt = jnp.dtype(geom.dtype)
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (batch, m.n_heads, m.head_dim), dt)
    k_pages = jax.random.normal(ks[1], geom.shape, dt)
    v_pages = jax.random.normal(ks[2], geom.shape, dt)
    cur_k = jax.random.normal(ks[3], (batch, m.n_kv_heads, m.head_dim), dt)
    cur_v = jax.random.normal(ks[4], (batch, m.n_kv_heads, m.head_dim), dt)
    tables = jnp.arange(1, n_pages, dtype=jnp.int32).reshape(batch, width)
    seq_lens = jnp.asarray([1, 2, 16, 17, 33, 100, 257, 512, 777, 1024, 1300,
                            1500, 1777, 2000, 2047, 2048], jnp.int32)
    args = (q, k_pages, v_pages, layer, tables, seq_lens, cur_k, cur_v)
    in_program = ("tpu_custom_call"
                  in paged_decode_attention_pallas.lower(*args).as_text())
    out = np.asarray(paged_decode_attention_pallas(*args), np.float32)
    ref = np.asarray(jax.jit(paged_decode_attention)(
        q, k_pages[layer][None], v_pages[layer][None], 0, tables, seq_lens,
        cur_k=cur_k, cur_v=cur_v), np.float32)
    err = np.abs(out - ref)
    within = bool(np.all(np.isfinite(out))
                  and np.all(err <= KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)))
    say(phase="kernel_check", device_kind=dev.device_kind,
        compile_cache_dir=cache_dir, shapes={
            "batch": batch, "q_heads": m.n_heads, "kv_heads": m.n_kv_heads,
            "head_dim": m.head_dim, "layers": layers, "layer_read": layer,
            "pages": n_pages, "page_tokens": block,
            "table_width": width, "dtype": str(dt)},
        pallas_call_in_program=in_program,
        max_abs_diff=float(err.max()), max_abs_ref=float(np.abs(ref).max()),
        atol=KERNEL_ATOL, rtol=KERNEL_RTOL, within_tolerance=within)
    return 0 if in_program and within else 1


# ---- entry ------------------------------------------------------------------

def run(s: Settings, chips: int) -> dict:
    """All phases for `chips`; returns the device for the verdict line."""
    procs = Procs()
    try:
        if chips == 1:
            # Alone on the chip, and gone before the engine opens it.
            rc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--kernel-check",
                 "--seed", str(s.seed)], cwd=REPO, timeout=300).returncode
            check(rc == 0, f"kernel check exited {rc}")
            return asyncio.run(run_one_chip(s, procs))
        return asyncio.run(run_four_chips(s, procs))
    finally:
        procs.stop()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: four one-chip replicas and one P/D handoff "
                         "(and nothing of the one-chip run)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the prompts and the kernel check's inputs")
    ap.add_argument("--kernel-check", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.kernel_check:
        return kernel_check(args.seed)

    t0 = time.monotonic()
    try:
        device = run(Settings(seed=args.seed), args.chips)
        check(device["platform"] == "tpu" and device["count"] == args.chips,
              f"served from {device}, not from {args.chips} TPU chip(s)")
    except Exception as e:  # whatever broke, the verdict line says so
        say(seconds=round(time.monotonic() - t0, 1), logs=LOG_DIR)
        say(ok=False, failed=f"{type(e).__name__}: {e}"[-3000:])
        return 1
    say(seconds=round(time.monotonic() - t0, 1), logs=LOG_DIR)
    say(ok=True, device={k: device[k] for k in ("platform", "kind", "count")})
    return 0


if __name__ == "__main__":
    sys.exit(main())
