"""What the chip smoke rests on, as far as a CPU can show it: without a TPU
nothing serves and nothing says ok; the parent stays off JAX; the smoke's
phases run (at the `tiny` preset, on the CPU this suite pins) the control
flow they run on the chip; replicas land on different devices; the compile
cache can be placed from outside; a warm-up that raises ends the process."""

import asyncio
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (stdlib-only at import, like its parent run)

from llm_d_inference_scheduler_tpu.engine import EngineConfig  # noqa: E402
from llm_d_inference_scheduler_tpu.engine.core import TpuEngine  # noqa: E402

SERVER = "llm_d_inference_scheduler_tpu.engine.server"


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


def _tiny(base_port: int) -> chip_smoke.Settings:
    return chip_smoke.Settings(
        model="tiny", platform="cpu", max_batch=8, max_model_len=256,
        gen_tokens=8, unary_len=150, stream_len=90,
        concurrent_lens=(20, 30, 90, 100, 120, 150, 170, 200),
        session_prefix_len=120, session_tail_len=20, base_port=base_port,
        start_timeout_s=120.0, request_timeout_s=120.0)


def test_smoke_fails_without_tpu():
    """As the driver runs it, in a sandbox like this one: non-zero, and the
    last line is not the ok verdict."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=_env(), capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last.get("ok") is not True
    assert '"ok": true' not in r.stdout


def test_smoke_parent_never_imports_jax():
    """One process per chip: the parent that starts the engine must not hold
    the device itself, whichever way the run ends."""
    code = ("import sys, chip_smoke; rc = chip_smoke.main([]); "
            "print('RC', rc, 'JAX', 'jax' in sys.modules, 'CORE', "
            "'llm_d_inference_scheduler_tpu.engine.core' in sys.modules)")
    r = subprocess.run([sys.executable, "-c", code], env=_env(), cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.stdout.strip().splitlines()[-1] == "RC 1 JAX False CORE False"


def test_engine_cli_refuses_cpu_unless_asked():
    """--backend tpu with no --platform does not serve from a CPU it was not
    asked for, and says how to ask; --platform cpu starts."""
    args = [sys.executable, "-m", SERVER, "--backend", "tpu", "--model",
            "tiny", "--port", "18991", "--max-model-len", "64"]
    r = subprocess.run(args, env=_env(), capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "--platform cpu" in r.stderr and "listening" not in r.stderr

    async def starts():
        import httpx

        procs = chip_smoke.Procs()
        try:
            proc = procs.start("cli-cpu", "engine.server", *args[3:],
                               "--platform", "cpu")
            async with httpx.AsyncClient() as client:
                health, _ = await chip_smoke.wait_healthy(
                    client, proc, "http://127.0.0.1:18991/health", 120)
            return health
        finally:
            procs.stop()

    health = asyncio.run(starts())
    assert health["status"] == "ok"
    assert health["device"]["platform"] == "cpu"
    assert health["settings"]["pallas_attention"] is False


def test_server_cli_cannot_interpret_kernels():
    """Interpret mode is for tests: no server flag reaches it."""
    r = subprocess.run([sys.executable, "-m", SERVER, "--help"], env=_env(),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "interpret" not in r.stdout.lower()


def test_smoke_phases_on_cpu_tiny():
    """Unary, streamed, eight concurrent, prefix repeat — through a gateway
    process and an engine server process, as on the chip."""
    procs = chip_smoke.Procs()
    try:
        device = asyncio.run(chip_smoke.run_one_chip(_tiny(18900), procs))
    finally:
        procs.stop()
    assert device["platform"] == "cpu"


def test_four_replicas_on_four_devices():
    """Four engine server processes behind one gateway, each bound to its
    own (virtual) device; every one serves, all agree on one prompt, and
    one request is prefilled on one and decoded on another."""
    procs = chip_smoke.Procs()
    try:
        device = asyncio.run(chip_smoke.run_four_chips(_tiny(18920), procs))
    finally:
        procs.stop()
    assert device == {"platform": "cpu", "kind": "cpu", "count": 4}


def test_engines_in_one_process_bind_their_own_device():
    """device_index puts weights, pages and the step on that device."""
    devs = jax.local_devices()
    engines = [TpuEngine(EngineConfig(model="tiny", max_batch=2,
                                      max_model_len=64, kv_events_port=0,
                                      device_index=i)) for i in (0, 3)]
    for eng, dev in zip(engines, (devs[0], devs[3])):
        assert eng.describe()["device"]["id"] == dev.id
        assert eng.k_pages.devices() == {dev}
        assert all(leaf.devices() == {dev}
                   for leaf in jax.tree.leaves(eng.params))
        tok = eng._op_prefill(16, **_prefill_args(eng))
        assert tok.devices() == {dev}
    with pytest.raises(ValueError, match="device_index"):
        TpuEngine(EngineConfig(model="tiny", kv_events_port=0,
                               device_index=len(devs)))


def _prefill_args(eng):
    import numpy as np

    return dict(tokens=np.ones((1, 16), np.int32),
                seq_len=np.asarray([4], np.int32),
                row=np.zeros((1, eng.max_blocks_per_seq), np.int32),
                slots=np.full((1,), eng.cfg.max_batch, np.int32),  # nobody's
                temps=np.zeros((1,), np.float32),
                top_k=np.zeros((1,), np.int32),
                top_p=np.ones((1,), np.float32))


def test_pallas_attention_by_name_on_unaligned_heads_is_an_error():
    """Asked for explicitly where Mosaic cannot serve it (head_dim 32): the
    engine says so instead of quietly taking the other path."""
    with pytest.raises(ValueError, match="lane-aligned"):
        TpuEngine(EngineConfig(model="tiny", kv_events_port=0,
                               pallas_attention=True))


def test_warmup_that_raises_ends_the_server(monkeypatch):
    from llm_d_inference_scheduler_tpu.engine import server

    def refuse(self):
        raise RuntimeError("the compiler refused a program")

    monkeypatch.setattr(TpuEngine, "_warmup", refuse)
    cfg = EngineConfig(model="tiny", max_model_len=64, port=18992,
                       kv_events_port=0, warmup=True)
    with pytest.raises(SystemExit, match="compiler refused"):
        asyncio.run(asyncio.wait_for(server.run_server(cfg), timeout=60))


@pytest.mark.parametrize("placed", [None, "/tmp/placed-from-outside"])
def test_compile_cache_dir(placed):
    """Placed from outside: no directory is set in code (JAX reads the
    variable itself). Otherwise <checkout>/.jax_cache, whatever the working
    directory. Either way a program's metadata is part of its cache key, so
    a cached executable names its blocks as the tree that runs it does."""
    code = ("from llm_d_inference_scheduler_tpu.utils.compile_cache import "
            "configure_compile_cache as c; d = c(); import jax; "
            "print(d, jax.config.jax_compilation_cache_include_metadata_in_key,"
            " jax.config.jax_compilation_cache_dir)")
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = placed
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd="/",
                       capture_output=True, text=True, timeout=120)
    want = placed or os.path.join(REPO, ".jax_cache")
    assert r.stdout.split() == [want, "True", want], r.stderr
