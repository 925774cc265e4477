"""Run one cell of the benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

A new process per run: it starts the cell's engine server(s) and gateway as
their own processes, warms the cell's shapes, measures for --seconds, checks
the answers, prints one JSON object as its last line, stops every child and
exits. This parent never imports JAX: a parent that touches JAX holds the
chip. Without a TPU the engine refuses to start and the run exits non-zero
with the reason on its last line; there is no fallback.

How a cell's files are found (PERF.md has the same list): BENCHMARK.json's
`workloads` entry names a `config` and a `traffic`;
`chipbench/configs/<config>.json` holds the model as published plus `serve`
(name served, replicas, engine arguments, gateway file by name in
`chipbench/gateways/`); `chipbench/traffic/<traffic>.json` holds the mix;
each per-layer metric is `chipbench/layer_metrics/<name>.json`, read by
`chipbench/readers/<kind>.py`.

`--trace 0` measures with the profiler off and prints the end-to-end metrics.
`--trace 2` does exactly that and then, on the servers still warm, sends a
short tail of the same traffic whose last part is traced (the engine server's
`POST /debug/profile/start|stop`, which exist only under its `--profile-dir`):
its line holds the end-to-end metrics of the measured window and the
per-layer metrics side by side, those a trace gives read over the tail and
all others over the measured window. `--trace 1` is the older form, a run
of its own whose window's last part is traced and whose line holds the
per-layer metrics only.

Other modes, not part of a check: `--sweep r1,r2,..` runs one window per rate
in one process (finding the knee); `--platform cpu` rehearses the phases on
the CPU, where the last line names the CPU and carries no device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

import httpx  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import client  # noqa: E402
import layer  # noqa: E402
import prom  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402
from procs import BenchFailure, Procs, http_get, wait_healthy  # noqa: E402

BASE_PORT = 18800
GAUGE_HZ = 5.0
# A traced slice is at most this share of its window, at the window's end.
TRACED_SHARE = 0.4


def say(**fact) -> None:
    print(json.dumps(fact), flush=True)


# ---- what a cell is ---------------------------------------------------------

def load_cell(bench_path: str, workload: str) -> dict:
    with open(bench_path) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        model = json.load(f)

    def for_cell(metrics):
        return [m["name"] for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {"cell": cell, "model": model,
            "config_file": configs[cell["config"]]["file"],
            "mix": traffic.load_mix(traffic.mix_path(ROOT, cell["traffic"])),
            "end_to_end": for_cell(bench["end_to_end"]),
            "per_layer": for_cell(bench["per_layer"]),
            "from_trace": {m["name"] for m in bench["per_layer"]
                           if m["source"] == "device_trace"},
            "units": {m["name"]: m["unit"]
                      for m in bench["end_to_end"] + bench["per_layer"]}}


# ---- servers ----------------------------------------------------------------

class Servers:
    def __init__(self, spec: dict, seed: int, platform: str | None,
                 trace: bool, out_dir: str):
        self.serve = spec["model"]["serve"]
        self.n = int(self.serve["replicas"])
        if self.n != spec["cell"]["chips"] and platform is None:
            raise BenchFailure("one replica per chip: serve.replicas "
                               f"{self.n} != chips {spec['cell']['chips']}")
        self.model_name = self.serve["model_name"]
        self.eports = [BASE_PORT + 10 + i for i in range(self.n)]
        self.gport = BASE_PORT
        self.gateway_url = f"http://127.0.0.1:{self.gport}"
        self.engine_urls = [f"http://127.0.0.1:{p}" for p in self.eports]
        self.trace_dirs = ([os.path.join(out_dir, f"trace{i}")
                            for i in range(self.n)] if trace else [])
        self.procs = Procs(out_dir)
        self.spec, self.seed, self.platform = spec, seed, platform

    def start(self) -> list[dict]:
        """Everything at once; returns each engine's /health once all answer."""
        engines = []
        for i, port in enumerate(self.eports):
            argv = [os.path.join(HERE, "launch_engine.py"), "--config",
                    os.path.join(ROOT, self.spec["config_file"]),
                    "--weights-seed", str(self.seed)]
            argv += ["--", "--backend", "tpu", "--model", self.model_name,
                     "--port", str(port), *self.serve["engine_args"]]
            if self.trace_dirs:
                shutil.rmtree(self.trace_dirs[i], ignore_errors=True)
                argv += ["--profile-dir", self.trace_dirs[i]]
            if self.platform:
                argv += ["--platform", self.platform]
            if self.n > 1:
                argv += ["--device-index", str(i)]
            engines.append(self.procs.start(f"engine{i}", argv))
        gateway = self.procs.start("gateway", [
            "-m", "llm_d_inference_scheduler_tpu.router.gateway",
            "--config-file", os.path.join(HERE, "gateways",
                                          self.serve["gateway"] + ".yaml"),
            "--port", str(self.gport), "--endpoints",
            ",".join(f"127.0.0.1:{p}" for p in self.eports)])
        healths = []
        for child, url in zip(engines, self.engine_urls):
            health, up_s = wait_healthy(child, url + "/health", 1100.0)
            healths.append(dict(health, start_to_healthy_s=up_s))
        wait_healthy(gateway, self.gateway_url + "/health", 120.0)
        return healths

    def scrape_engines(self) -> list[dict]:
        return [prom.parse(http_get(u + "/metrics", 10.0)[1].decode())
                for u in self.engine_urls]

    def healths(self) -> list[dict]:
        return [json.loads(http_get(u + "/health", 10.0)[1])
                for u in self.engine_urls]

    async def profiler(self, http: httpx.AsyncClient, verb: str) -> list[dict]:
        """`start` or `stop` on every replica at once; each one's answer.
        A stop answers when the trace file is written, seconds later."""
        answers = await asyncio.gather(*[
            http.post(f"{u}/debug/profile/{verb}", timeout=180.0)
            for u in self.engine_urls])
        bad = [a for a in answers if a.status_code != 200]
        if bad:
            raise BenchFailure(f"profiler {verb}: {bad[0].status_code} "
                               f"{bad[0].text[:300]}")
        return [a.json() for a in answers]


def device_of(healths: list[dict]) -> dict:
    first = healths[0]["device"]
    peaks = [h["memory"].get("peak_bytes_in_use") for h in healths]
    # Each replica process reports the devices IT sees: one chip each.
    return {"platform": first["platform"], "kind": first["kind"],
            "count": sum(h["device"]["count"] for h in healths),
            "memory_peak_bytes": max((p for p in peaks if p), default=None)}


def require_ok(records: list, what: str) -> None:
    bad = [r for r in records if not r.ok]
    if bad:
        raise BenchFailure(f"{what} failed: {bad[0]}")


def compiled_shapes(samples: dict) -> list[str]:
    return sorted(labels for (name, labels), _ in samples.items()
                  if name == "jetstream:compile_events_total")


# ---- phases -----------------------------------------------------------------

async def warm_up(srv: Servers, mix: dict, seed: int, temperature: float):
    """Every shape of the mix on every replica, engine-direct; then what the
    mix wants in the caches, through the gateway."""
    groups = traffic.warmup_requests(mix, seed)
    flat = [r for g in groups for r in g]
    results = await asyncio.gather(*[
        client.send_all(u, srv.model_name, flat, temperature)
        for u in srv.engine_urls])
    sent = len(flat)
    for burst in traffic.burst_requests(mix, seed):
        results += await asyncio.gather(*[
            client.send_all(u, srv.model_name, burst, temperature,
                            concurrency=len(burst))
            for u in srv.engine_urls])
        sent += len(burst)
        # The decode bucket for k lanes is the next power of two (the engine's
        # rule); it must have run, or the window would be the first to use it.
        lanes = 2
        while lanes < len(burst):
            lanes *= 2
        for i, samples in enumerate(srv.scrape_engines()):
            if not any(f'bucket="{lanes}x' in labels and 'op="decode"' in labels
                       for labels in compiled_shapes(samples)):
                raise BenchFailure(
                    f"warm-up burst of {len(burst)} never decoded {lanes} "
                    f"lanes wide on replica {i}: {compiled_shapes(samples)}")
    require_ok([r for rs in results for r in rs], "warm-up request")
    return sent * len(srv.engine_urls)


async def probe(srv: Servers, seed: int) -> dict[str, list[str]]:
    """Four short prompts at temperature 0, alone: through the gateway, and
    engine-direct to every replica."""
    reqs = traffic.probe_requests(seed)
    out = {}
    for name, url in [("gateway", srv.gateway_url)] + [
            (f"engine{i}", u) for i, u in enumerate(srv.engine_urls)]:
        recs = await client.send_all(url, srv.model_name, reqs, 0.0)
        require_ok(recs, f"probe via {name}")
        out[name] = [r.text for r in recs]
    return out


async def window(srv: Servers, plan: traffic.Plan, seconds: float,
                 trace_spec: dict | None, ramp_s: float = 0.0) -> dict:
    """One measured window. Scrapes at its start and end, gauges at 5 Hz in
    between, and with trace_spec a profiler slice inside it."""
    side: dict = {"seconds": seconds, "gauges": [], "trace_span": None,
                  "profiler": []}

    async def scrape(http, urls):
        texts = await asyncio.gather(*[http.get(u + "/metrics", timeout=10.0)
                                       for u in urls])
        return [prom.parse(t.text) for t in texts]

    async def on_start(t0: float):
        # The slice is the window's last part: the profiler writes its file
        # when it stops, which takes seconds and can stall the engine's host
        # loop; so the counters are read at the window's end BEFORE the stop,
        # and the stall falls into the drain.
        trace_len = (min(trace_spec["seconds"], TRACED_SHARE * seconds)
                     if trace_spec else 0)
        trace_at = seconds - trace_len if trace_spec else None
        async with httpx.AsyncClient() as http:
            await asyncio.sleep(max(0.0, t0 - time.monotonic()))
            side["engines_before"] = await scrape(http, srv.engine_urls)
            side["gateway_before"] = (await scrape(http, [srv.gateway_url]))[0]
            tick = 0
            while True:
                now = time.monotonic() - t0
                if now >= seconds:
                    break
                if trace_at is not None and side["trace_span"] is None \
                        and now >= trace_at:
                    await srv.profiler(http, "start")
                    side["trace_span"] = [time.monotonic() - t0, None]
                side["gauges"].append((now, await scrape(http, srv.engine_urls)))
                tick += 1
                await asyncio.sleep(max(0.0, t0 + tick / GAUGE_HZ
                                        - time.monotonic()))
            side["engines_after"] = await scrape(http, srv.engine_urls)
            side["gateway_after"] = (await scrape(http, [srv.gateway_url]))[0]
            if side["trace_span"]:
                side["trace_span"][1] = time.monotonic() - t0
                side["profiler"] = await srv.profiler(http, "stop")

    records, t0 = await client.run_window(
        srv.gateway_url, srv.model_name, plan.chains, plan.temperature,
        seconds, lead_s=0.5, on_start=on_start, ramp_s=ramp_s)
    side["records"] = records
    side["t0"] = t0
    return side


async def traced_tail(srv: Servers, mix: dict, seed: int) -> dict:
    """--trace 2, after the measured window is closed: the profiler is
    started and stopped once for nothing (the first start costs most, and
    should fall into no number), then a short window of the same traffic
    with the mix's own ramp, just long enough that the mix's slice is its
    last part, is traced. Returns that window's side."""
    async with httpx.AsyncClient() as http:
        await srv.profiler(http, "start")
        first = await srv.profiler(http, "stop")
    for d in srv.trace_dirs:
        shutil.rmtree(os.path.join(d, "plugins"), ignore_errors=True)
    say(profiler_first_start_and_stop=first)
    tail_s = mix["trace"]["seconds"] / TRACED_SHARE
    plan = traffic.build(mix, seed, tail_s, tag="t")
    return await window(srv, plan, tail_s, mix["trace"],
                        ramp_s=mix.get("ramp_s", 0.0))


def run_trace_reduce(trace_dirs: list[str], profiler: list[dict]) -> list[dict]:
    """trace_reduce.py on each replica's directory, as a program pinned to
    the CPU; each result with what that replica's profiler said of its stop."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"), *trace_dirs],
        env=env, capture_output=True, text=True, timeout=240)
    if done.returncode != 0:
        raise BenchFailure(f"trace reduction failed: {done.stderr[-1500:]}")
    traces = [json.loads(line) for line in done.stdout.splitlines()
              if line.startswith("{")]
    return [dict(t, **said) for t, said in zip(traces, profiler)]


def breakdown_of(traces: list[dict]) -> dict | None:
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for t in traces:
        for dev in t.get("devices", []):
            for name, row in dev["ops"].items():
                if not row.get("control_flow"):
                    label = f"{name} {row['detail'][:80]}"
                    ops[label] = ops.get(label, 0.0) + row["seconds"]
            for name, s in dev["idle_by_next_program"].items():
                gaps[name] = gaps.get(name, 0.0) + s
    if not ops:
        return None

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"device_ops": top(ops), "idle_gaps": top(gaps)}


# ---- one run ----------------------------------------------------------------

def run(args) -> dict:
    spec = load_cell(args.bench, args.workload)
    mix, cell = spec["mix"], spec["cell"]
    mode = "sweep" if args.sweep else ("run", "trace", "trace2")[args.trace]
    out_dir = os.path.join(ROOT, "chiprun_out", "chipbench", args.workload, mode)
    srv = Servers(spec, args.seed, args.platform, bool(args.trace), out_dir)
    try:
        healths = srv.start()
        device = device_of(healths)
        if args.platform is None and (device["platform"] != "tpu"
                                      or device["count"] < cell["chips"]):
            raise BenchFailure(f"needs {cell['chips']} TPU chip(s); the "
                               f"engines report {device}")
        t_healthy = time.monotonic()
        plan = traffic.build(mix, args.seed, args.seconds)
        n_warm = asyncio.run(warm_up(srv, mix, args.seed, plan.temperature))
        if plan.preload:
            # One after another: sent together, cold prompts all see the same
            # empty pool and pile onto one replica (PERF.md, Findings, PR 23).
            pre = asyncio.run(client.send_all(
                srv.gateway_url, srv.model_name, plan.preload,
                plan.temperature))
            require_ok(pre, "preload")
        before = asyncio.run(probe(srv, args.seed))
        shapes = compiled_shapes(srv.scrape_engines()[0])
        say(set_up_fact=True, workload=args.workload,
            settings=healths[0]["settings"],
            start_to_healthy_s=[h["start_to_healthy_s"] for h in healths],
            warmup_requests=n_warm, preload_requests=len(plan.preload),
            warm_s=time.monotonic() - t_healthy,
            compiled_shapes_replica0=shapes)

        if args.sweep:
            return sweep(args, srv, spec, device)

        side = asyncio.run(window(
            srv, plan, args.seconds, mix.get("trace") if args.trace == 1 else None,
            ramp_s=mix.get("ramp_s", 0.0)))
        after = asyncio.run(probe(srv, args.seed))
        # Up to here --trace 2 has done what --trace 0 does, and nothing else.
        tail = (asyncio.run(traced_tail(srv, mix, args.seed))
                if args.trace == 2 else None)
        device = device_of(srv.healths())
    finally:
        stopped = srv.procs.stop()

    # setup_s: process start to the first measured request being due.
    setup_s = side["t0"] - T_PROCESS_START
    records = side["records"]
    rows = stats.measured(records, args.seconds)
    failed = [r for r in rows if not r.ok]
    checks = correctness(before, after, rows, mix)
    say(generator=stats.generator_report(records, args.seconds),
        children_stopped=stopped,
        checks=checks, first_failure=str(failed[0])[:600] if failed else None)

    with open(os.path.join(out_dir, "records.json"), "w") as f:
        json.dump([{k: v for k, v in vars(r).items() if k != "text"}
                   for r in records], f)
    e2e = stats.end_to_end(records, args.seconds, cell["chips"])
    e2e["setup_s"] = setup_s
    line = {"correct": not failed and all(checks.values()),
            "attempted": len(rows), "failed": len(failed), "metrics": {},
            "device": device}
    if not args.trace:
        names, values = spec["end_to_end"], e2e
    else:
        def context(side: dict, traces: list) -> layer.Context:
            return layer.Context(
                records=side["records"], seconds=side["seconds"],
                chips=cell["chips"],
                engine_scrapes=list(zip(side["engines_before"],
                                        side["engines_after"])),
                gateway_scrape=(side["gateway_before"], side["gateway_after"]),
                gauge_samples=side["gauges"], traces=traces,
                trace_span=tuple(side["trace_span"]) if side["trace_span"] else None,
                model=spec["model"], device_kind=device["kind"])

        traced = tail or side
        traces = run_trace_reduce(srv.trace_dirs, traced["profiler"])
        if device["platform"] == "tpu" and not all(t["devices"] for t in traces):
            raise BenchFailure(f"a replica's trace has no device plane: {traces}")
        traced_ctx = context(traced, traces)
        names, values = spec["per_layer"], {}
        if tail:
            # What a trace gives is read over the traced tail; counters,
            # histograms and client records over the measured window, where
            # nothing was traced and there are 51 s of samples.
            window_ctx = context(side, [])
            values = dict(e2e)
            names = spec["end_to_end"] + names
            say(tail=stats.generator_report(tail["records"], tail["seconds"]),
                tail_failed=sum(not r.ok for r in tail["records"]),
                counted_over_the_traced_tail={
                    n: layer.read_metric(n, traced_ctx)
                    for n in spec["per_layer"] if n not in spec["from_trace"]})
        else:
            window_ctx = traced_ctx
            say(end_to_end_in_traced_run=e2e)
        for n in spec["per_layer"]:
            values[n] = layer.read_metric(
                n, traced_ctx if n in spec["from_trace"] else window_ctx)
        devices = [d for t in traces for d in t.get("devices", [])]
        if devices:
            line["device"]["busy_s"] = sum(d["busy_s"] for d in devices) / len(devices)
            line["device"]["window_s"] = sum(d["window_s"] for d in devices) / len(devices)
            line["breakdown"] = breakdown_of(traces)
        say(layer_notes={**window_ctx.notes, **traced_ctx.notes},
            trace_span_s=traced["trace_span"],
            trace_files=[{k: t.get(k) for k in ("dir", "bytes", "traced_s", "start_trace_s",
                                              "stop_trace_s", "error")}
                         for t in traces])
        if args.dump_trace:
            with open(os.path.join(out_dir, "trace_reduced.json"), "w") as f:
                json.dump(traces, f)
        else:
            for d in srv.trace_dirs:
                shutil.rmtree(d, ignore_errors=True)
    missing = [n for n in names if values.get(n) is None]
    if missing and not (args.trace and device["platform"] != "tpu"):
        say(note="metrics with nothing to read, left out", metrics=missing)
    line["metrics"] = {n: {"value": values[n], "unit": spec["units"][n]}
                       for n in names if values.get(n) is not None}
    return line


def correctness(before: dict, after: dict, rows: list, mix: dict) -> dict:
    """Beyond every request's own status and token counts: the probes answer
    the same before the window and after it, and the same from every path."""
    texts = [tuple(v) for v in before.values()] + [tuple(v) for v in after.values()]
    checks = {"probes_same_before_after_and_across_replicas": len(set(texts)) == 1}
    if mix["kind"] == "open_sessions":
        later = [r for r in rows if r.turn > 0 and r.ok]
        hit = sum(r.cached_tokens > 0 for r in later)
        # A turn sent where its history is not cached is a routing miss, a
        # matter of speed; that MOST later turns hit shows the path is live.
        checks["most_later_turns_hit_the_prefix_cache"] = (
            bool(later) and hit / len(later) > 0.5)
    return checks


def sweep(args, srv: Servers, spec: dict, device: dict) -> dict:
    """One window per rate, one after another on the servers already warm."""
    table = []
    for i, rate in enumerate(float(x) for x in args.sweep.split(",")):
        plan = traffic.build(spec["mix"], args.seed, args.seconds, rate=rate,
                             tag=f"s{i}")
        side = asyncio.run(window(srv, plan, args.seconds, None,
                                  ramp_s=spec["mix"].get("ramp_s", 0.0)))
        row = {"rate": rate,
               **stats.end_to_end(side["records"], args.seconds,
                                  spec["cell"]["chips"]),
               **stats.generator_report(side["records"], args.seconds)}
        rows = stats.measured(side["records"], args.seconds)
        row["failed"] = sum(not r.ok for r in rows)
        waits = [r.done_s for r in rows if r.done_s is not None]
        row["drain_s"] = max(waits, default=args.seconds) - args.seconds
        table.append(row)
        say(sweep_row=row)
    return {"sweep": table, "device": device}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated rates (clients, for a closed loop): "
                         "one window each, a table, no contract line")
    ap.add_argument("--platform", default=None,
                    help="'cpu' rehearses the phases on the CPU")
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--dump-trace", action="store_true",
                    help="keep the trace and its reduction under chiprun_out/")
    args = ap.parse_args(argv)
    # Killed from outside, the run still stops its children (run()'s finally).
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        if args.seconds is None:
            with open(args.bench) as f:
                args.seconds = float(json.load(f)["run_seconds"])
        line = run(args)
    except BenchFailure as e:
        print(json.dumps({"error": str(e)[-3000:]}), flush=True)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
