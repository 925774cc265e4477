"""BENCHMARK.json against the contract's limits that a typo would break, and
every file a cell names."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units(bench):
    assert set(bench) - {"trace_in_run"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    # With the key, a check traces inside its measuring runs (--trace 2).
    assert bench.get("trace_in_run", True) is True
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_has_its_files_and_its_metrics(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert len({c["file"] for c in configs.values()}) == len(configs)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in bench["workloads"]:
        cfg = configs[w["config"]]
        assert cfg["file"].startswith("chipbench/")
        with open(os.path.join(REPO, cfg["file"])) as f:
            doc = json.load(f)
        assert doc["source"] == cfg["source"] and doc["reduced"] == cfg["reduced"]
        assert doc["serve"]["replicas"] == w["chips"]
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "gateways", doc["serve"]["gateway"] + ".yaml"))
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "configs", f"reference_{doc['reference']}.py"))
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "traffic", w["traffic"] + ".json"))

        def reported(ms):
            return {m["name"] for m in ms
                    if "workloads" not in m or w["name"] in m["workloads"]}

        assert len(reported(bench["end_to_end"])) >= 2
        layer_metrics = reported(bench["per_layer"])
        assert layer_metrics
        for m in bench["per_layer"]:
            if m["name"] in layer_metrics:
                assert m["moves"] in reported(bench["end_to_end"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(configs)


def test_every_layer_metric_has_a_file_and_a_reader(bench):
    base = os.path.join(REPO, "chipbench")
    for m in bench["per_layer"]:
        with open(os.path.join(base, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(base, "readers", spec["kind"] + ".py"))
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers)


def test_width_keys_are_never_reduced(bench):
    forbidden = re.compile(r"(hidden|intermediate|latent|state|proj|head).*size"
                           r"|_dim$|_rank$|experts_per_tok|expansion")
    for c in bench["configs"]:
        assert not [k for k in c["reduced"] if forbidden.search(k)]


def test_model_keys_map_to_the_programs_config(bench):
    from launch_engine import model_config_from_file

    got = {c["name"]: model_config_from_file(os.path.join(REPO, c["file"]))
           for c in bench["configs"]}
    q = got["qwen3-4b"]
    assert (q.n_layers, q.d_model, q.n_heads, q.n_kv_heads, q.head_dim, q.d_ff,
            q.vocab_size, q.qk_norm, q.rope_theta, q.norm_eps) == \
        (36, 2560, 32, 8, 128, 9728, 151936, True, 1e6, 1e-6)
    m = got["mixtral-8x7b-cut"]
    assert (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads, m.head_dim, m.d_ff,
            m.vocab_size, m.n_experts, m.experts_per_token, m.moe_impl) == \
        (4, 4096, 32, 8, 128, 14336, 32000, 8, 2, "dense")
    # Prepared for the four-replica cell (PERF.md, Open questions): the same
    # model, served four times.
    x4 = model_config_from_file(
        os.path.join(REPO, "chipbench", "configs", "qwen3-4b-x4.json"))
    assert x4 == q
