"""Stop one process of a benchmark run for a moment, and see where it shows.

    python scripts/provoke_stream_stop.py --stop engine|gateway|client \
        [--at 20] [--for 2] -- --workload <cell> --seed <n> --trace 2

Runs `chipbench/run.py` with the arguments after `--`, and `--at` seconds
into its measured window sends SIGSTOP to the engine's process, the
gateway's, or the benchmark's own client (run.py itself), and SIGCONT
`--for` seconds later. Prints the run's own lines as they come, then one
JSON object: when the stop was sent, the per-layer metrics that say where a
stream stopped (docs/observability.md, "Where a stream stopped"), the
end-to-end tail beside them, and the engine's `engine loop stall` log lines
(its /debug/stalls records). `--stop none` is the quiet run to read against.
The builder's proof that the instrument tells the places apart (PERF.md
section 6, PR 36); never part of a check. This parent never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATTERNS = {"engine": "chipbench/launch_engine[.]py", "gateway": "router[.]gateway"}
WHERE = ("eng_longest_chunk_ms", "eng_stall_device_wait_s", "eng_stall_host_s",
         "eng_event_loop_lag_ms", "eng_event_loop_lag_max_ms",
         "gw_event_loop_lag_max_ms", "gw_stream_gap_max_ms",
         "client_stream_gap_max_ms", "tpot_p95_ms", "out_tokens_per_s",
         "decode_chunk_ms", "eng_loop_host_pct")


def ramp_of(workload: str) -> float:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           cells[workload]["traffic"] + ".json")) as f:
        return float(json.load(f).get("ramp_s", 0.0))


def pids_of(target: str, run: subprocess.Popen) -> list[int]:
    if target == "client":
        return [run.pid]
    out = subprocess.run(["pgrep", "-f", PATTERNS[target]],
                         capture_output=True, text=True).stdout
    return [int(p) for p in out.split()]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--stop", choices=("engine", "gateway", "client", "none"),
                    required=True)
    ap.add_argument("--at", type=float, default=20.0)
    ap.add_argument("--for", dest="for_s", type=float, default=2.0)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    run_args = [a for a in args.run_args if a != "--"]
    workload = run_args[run_args.index("--workload") + 1]
    mode = {"0": "run", "1": "trace", "2": "trace2"}[
        run_args[run_args.index("--trace") + 1] if "--trace" in run_args else "0"]
    # run.py says `set_up_fact` right before it opens the window, whose first
    # request is due 0.5 s plus the mix's ramp later.
    lead = 0.5 + ramp_of(workload) + args.at
    run = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), *run_args],
        cwd=ROOT, stdout=subprocess.PIPE, text=True)
    sent: dict = {}

    def stop_and_go():
        time.sleep(lead)
        pids = pids_of(args.stop, run)
        sent.update(stopped=args.stop, pids=pids, unix=round(time.time(), 3))
        for pid in pids:
            os.kill(pid, signal.SIGSTOP)
        time.sleep(args.for_s)
        for pid in pids:
            os.kill(pid, signal.SIGCONT)
        sent["held_s"] = round(time.time() - sent["unix"], 3)

    last = ""
    for line in run.stdout:
        line = line.rstrip("\n")
        if not line:
            continue
        last = line
        print(line, flush=True)
        if '"set_up_fact"' in line and args.stop != "none" and not sent:
            sent["armed"] = True
            threading.Thread(target=stop_and_go, daemon=True).start()
    rc = run.wait()
    try:
        result = json.loads(last)
    except ValueError:
        result = {}
    metrics = result.get("metrics", {})
    stalls = []
    log_path = os.path.join(ROOT, "chiprun_out", "chipbench", workload, mode,
                            "engine0.log")
    if os.path.exists(log_path):
        with open(log_path, errors="replace") as f:
            stalls = [ln[ln.index("{"):].strip() for ln in f
                      if "engine loop stall {" in ln]
    print(json.dumps({
        "provoked": sent, "rc": rc, "correct": result.get("correct"),
        "failed": result.get("failed"),
        "where": {n: metrics[n]["value"] for n in WHERE if n in metrics},
        "stall_records": [json.loads(s) for s in stalls]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
