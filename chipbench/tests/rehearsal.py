"""A temporary copy of the benchmark at the `tiny` size, for tests.

Copies `chipbench/` and writes beside it a BENCHMARK.json whose cells use new
configuration and traffic FILES only: adding them edits no file that is
there, which is what a later PR has to be able to do.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_MODEL = {
    "source": "the program's `tiny` preset (tests only, never a cell)",
    "model_type": "llama", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
    "max_position_embeddings": 256, "reduced": [], "reference": "llama_family",
}


def tiny_config(replicas: int) -> dict:
    return dict(TINY_MODEL, serve={
        "model_name": "tiny-bench", "replicas": replicas,
        "gateway": "monolithic", "tokenizer": "byte",
        "engine_args": ["--max-batch", "4", "--max-model-len", "256",
                        "--decode-chunk", "4"]})


TINY_TRAFFIC = {
    "tiny-chat": {
        "kind": "open_poisson", "rate_rps": 6.0, "ramp_s": 1.0,
        "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                          "lo": 20, "hi": 120},
        "output_tokens": {"dist": "uniform", "lo": 4, "hi": 12},
        "trace": {"seconds": 0.5},
        "warmup": {"plain_prompt_tokens": [30, 60, 120], "max_tokens": 2,
                   "bursts": [{"concurrent": k, "prompt_tokens": 30,
                               "max_tokens": 12} for k in (2, 4)]}},
    "tiny-batch": {
        "kind": "closed_clients", "clients": 6, "ramp_s": 1.0, "pool": 32,
        "prompt_tokens": {"dist": "loguniform", "lo": 20, "hi": 100},
        "output_tokens": {"dist": "uniform", "lo": 4, "hi": 12},
        "trace": {"seconds": 0.5},
        "warmup": {"plain_prompt_tokens": [30, 60, 100], "max_tokens": 2,
                   "bursts": [{"concurrent": k, "prompt_tokens": 30,
                               "max_tokens": 12} for k in (2, 4)]}},
    "tiny-sessions": {
        "kind": "open_sessions", "session_rate_rps": 2.0, "ramp_s": 2.0,
        "turns": 3, "system_prompts": 4, "system_prompt_tokens": 64,
        "zipf_s": 1.0,
        "user_tokens": {"dist": "uniform", "lo": 10, "hi": 20},
        "answer_tokens": {"dist": "uniform", "lo": 4, "hi": 8},
        "think_s": {"dist": "uniform", "lo": 0.1, "hi": 0.3},
        "trace": {"seconds": 0.5},
        "warmup": {"plain_prompt_tokens": [100], "max_tokens": 2,
                   "bursts": [{"concurrent": k, "prompt_tokens": 100,
                               "max_tokens": 12} for k in (2, 4)],
                   "prefix": [{"prefix_tokens": 64, "suffix_tokens": [12, 30]},
                              {"prefix_tokens": 96, "suffix_tokens": [30]}]}},
}


def make_copy(dst: str) -> str:
    """dst/chipbench + dst/BENCHMARK.json with three tiny cells added to the
    real ones; returns the path of that BENCHMARK.json."""
    shutil.copytree(os.path.join(REPO, "chipbench"), os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, replicas in (("tiny", 1), ("tiny-x2", 2)):
        path = os.path.join(dst, "chipbench", "configs", f"{name}.json")
        with open(path, "x") as f:       # "x": a new file, never an edit
            json.dump(tiny_config(replicas), f)
        bench["configs"].append({
            "name": name, "source": TINY_MODEL["source"],
            "file": f"chipbench/configs/{name}.json", "reduced": [],
            "why": "rehearsal"})
    for mix, doc in TINY_TRAFFIC.items():
        with open(os.path.join(dst, "chipbench", "traffic", f"{mix}.json"), "x") as f:
            json.dump(doc, f)
    cells = [("tiny.tiny-chat", "tiny", "tiny-chat", 1),
             ("tiny.tiny-batch", "tiny", "tiny-batch", 1),
             ("tiny-x2.tiny-sessions", "tiny-x2", "tiny-sessions", 2)]
    for name, config, mix, chips in cells:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": chips,
                                   "why": "rehearsal"})
    # The new cells join the metrics of the cell they resemble, and the
    # sessions cell brings the one metric no cell reported yet: new entries.
    like = {"tiny.tiny-chat": "qwen3-4b.chat-steady",
            "tiny.tiny-batch": "mixtral-8x7b-cut.batch-full",
            "tiny-x2.tiny-sessions": "qwen3-4b.chat-steady"}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += [t for t, real in like.items()
                                    if real in metric["workloads"]]
    bench["per_layer"].append({
        "name": "gw_prefix_route_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Gateway",
        "moves": "ttft_p50_ms", "workloads": ["tiny-x2.tiny-sessions"]})
    path = os.path.join(dst, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path
