"""run.py as a program: refusal without a TPU, and one rehearsal of its
phases at the `tiny` size on the CPU, in a temporary copy of the benchmark
whose cells were ADDED as new files and new entries only."""

import json
import os
import subprocess
import sys

import pytest

import rehearsal

REPO = rehearsal.REPO
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_py(root, *args, timeout=240, **env):
    """run.py stops its own children in a `finally`; the timeout bounds the
    run itself, and a run that passes it fails the test."""
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                XLA_FLAGS="--xla_force_host_platform_device_count=2", **env)
    done = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"), *args],
        cwd=root, env=full, capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    return done.returncode, lines


def end_to_end_names(root, workload):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}


def no_leftovers():
    out = subprocess.run(["pgrep", "-f", "chipbench/launch_engine[.]py"],
                         capture_output=True, text=True).stdout
    return not out.strip()


def test_without_a_tpu_the_run_exits_non_zero_with_the_reason_last():
    rc, lines = run_py(REPO, "--workload", "qwen3-4b.chat-steady", "--seed", "1",
                       "--seconds", "2", "--trace", "0", timeout=120)
    assert rc != 0
    last = json.loads(lines[-1])
    assert set(last) == {"error"} and "TPU" in last["error"]
    assert no_leftovers()


def test_unknown_workload_is_an_error():
    rc, lines = run_py(REPO, "--workload", "nope", timeout=60)
    assert rc != 0 and "nope" in json.loads(lines[-1])["error"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    rehearsal.make_copy(root)
    return root


def test_added_cells_are_new_files_only(copy):
    """What make_copy did is what a later PR may do: every file of the real
    benchmark is still there, byte for byte."""
    for base, _, files in os.walk(os.path.join(REPO, "chipbench")):
        if "__pycache__" in base:
            continue
        for name in files:
            src = os.path.join(base, name)
            dst = os.path.join(copy, os.path.relpath(src, REPO))
            with open(src, "rb") as a, open(dst, "rb") as b:
                assert a.read() == b.read(), src


@pytest.mark.parametrize("workload, trace", [
    ("tiny.tiny-chat", "0"), ("tiny.tiny-batch", "1"),
    ("tiny-x2.tiny-sessions", "1"), ("tiny.tiny-chat", "2"),
    ("tiny-x2.tiny-sessions", "2")])
def test_rehearsal_on_the_cpu_named_as_such(copy, workload, trace):
    rc, lines = run_py(copy, "--workload", workload, "--seed", str(2 ** 31 + 7),
                       "--seconds", "4", "--trace", trace, "--platform", "cpu")
    assert rc == 0, lines[-3:]
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS            # and no breakdown off a TPU
    assert last["device"]["platform"] == "cpu"
    assert "busy_s" not in last["device"] and "window_s" not in last["device"]
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0
    names = set(last["metrics"])
    assert not names & {"device_idle_share", "paged_attention_roofline"}
    if trace in "02":
        assert "setup_s" in names and "tpot_p95_ms" in names
        assert all(last["metrics"][n]["value"] > 0 for n in end_to_end_names(copy, workload))
    if trace in "12":
        assert {"decode_chunk_ms", "eng_loop_host_pct"} <= names
        assert 0 < last["metrics"]["eng_loop_host_pct"]["value"] <= 100
    if trace == "1":
        assert not names & end_to_end_names(copy, workload)
    if trace == "2":
        # Both kinds side by side, and every end-to-end key of a --trace 0 line.
        assert end_to_end_names(copy, workload) <= names
        tail = [json.loads(ln) for ln in lines if '"tail_failed"' in ln][0]
        assert tail["tail_failed"] == 0 and tail["tail"]["requests_in_window"] > 0
        assert "decode_chunk_ms" in tail["counted_over_the_traced_tail"]
        files = [json.loads(ln) for ln in lines if '"trace_files"' in ln][0]
        assert all(f["bytes"] > 0 and f["traced_s"] > 0 for f in files["trace_files"])
        # The trace is deleted once it is reduced.
        assert not any(os.path.exists(f["dir"]) for f in files["trace_files"])
    if "chat" in workload and trace == "2":
        m = last["metrics"]
        assert m["eng_queue_ms"]["value"] > 0 and m["xla_builds_in_window"]["value"] == 0
        assert m["gw_queue_ms"]["value"] == 0 and m["gw_sched_ms"]["value"] > 0
    if "sessions" in workload and trace in "12":
        assert 0 <= last["metrics"]["gw_prefix_route_share"]["value"] <= 100
        assert 0 < last["metrics"]["eng_cached_token_share"]["value"] <= 100
    generator = [json.loads(ln) for ln in lines if '"generator"' in ln][0]
    assert generator["generator"]["requests_in_window"] == last["attempted"]
    assert generator["generator"]["late_p99_ms"] is not None
    assert no_leftovers()
