"""Device-to-device KV handoff (VERDICT r1 item 6): jax.experimental.transfer
pull replaces the host-staged copy for P/D pairs; HTTP stays as fallback."""

import asyncio
import json

import httpx
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig
from llm_d_inference_scheduler_tpu.engine.server import EngineServer


def _cfg(port, role="both", **kw):
    return EngineConfig(backend="tpu", model="tiny", port=port, max_batch=4,
                        max_model_len=256, role=role, kv_events_port=0, **kw)


def _device_transfer_available() -> bool:
    """True when jax.experimental.transfer can actually start a transfer
    server on this backend. On CPU images the module is absent (or the
    server refuses to start), so the device-pull tests below cannot
    exercise their subject — skip them cleanly instead of failing (the
    same precedent as test_tls's ``importorskip("cryptography")``)."""
    try:
        from llm_d_inference_scheduler_tpu.engine.core import (
            _get_transfer_server,
        )

        _get_transfer_server("127.0.0.1")
        return True
    except Exception:
        return False


requires_device_transfer = pytest.mark.skipif(
    not _device_transfer_available(),
    reason="jax.experimental.transfer server unavailable on this backend "
           "(CPU image): device-to-device KV pull cannot run")


PROMPT = [1] + [(i * 11) % 400 + 3 for i in range(40)]


async def _pd_pair(pre_port, dec_port, **kw):
    pre = EngineServer(_cfg(pre_port, role="prefill", **kw))
    dec = EngineServer(_cfg(dec_port, role="decode", **kw))
    await pre.start()
    await dec.start()
    return pre, dec


async def _run_pd(pre_port, dec_port, mutate_ktp=None):
    async with httpx.AsyncClient(timeout=60) as c:
        r1 = await c.post(f"http://127.0.0.1:{pre_port}/v1/completions", json={
            "prompt": PROMPT, "max_tokens": 1, "stream": False,
            "temperature": 0,
            "kv_transfer_params": {"do_remote_decode": True}})
        assert r1.status_code == 200
        ktp = r1.json()["kv_transfer_params"]
        if mutate_ktp:
            ktp = mutate_ktp(ktp)
        r2 = await c.post(f"http://127.0.0.1:{dec_port}/v1/completions", json={
            "prompt": PROMPT, "max_tokens": 6, "temperature": 0,
            "ignore_eos": True, "kv_transfer_params": ktp})
        assert r2.status_code == 200
        return ktp, r2.json()


@requires_device_transfer
def test_device_path_used_and_matches_monolithic():
    async def body():
        mono = EngineServer(_cfg(18731))
        await mono.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                r = await c.post("http://127.0.0.1:18731/v1/completions",
                                 json={"prompt": PROMPT, "max_tokens": 6,
                                       "temperature": 0, "ignore_eos": True})
                mono_text = r.json()["choices"][0]["text"]
        finally:
            await mono.stop()

        pre, dec = await _pd_pair(18732, 18733)
        try:
            ktp, doc = await _run_pd(18732, 18733)
            # The prefiller advertised the device pull route...
            assert "transfer_address" in ktp and "transfer_uuid" in ktp
            assert ktp["kv_shape"][2] == 16  # block size sanity
            # ...and the decode engine actually pulled device-to-device.
            assert dec.engine.kv_import_device_count == 1
            assert dec.engine.kv_import_host_count == 0
            assert doc["choices"][0]["text"] == mono_text
        finally:
            await pre.stop()
            await dec.stop()

    asyncio.run(body())


def test_host_path_when_transfer_disabled():
    async def body():
        pre, dec = await _pd_pair(18734, 18735, kv_transfer="host")
        try:
            ktp, doc = await _run_pd(18734, 18735)
            assert "transfer_address" not in ktp
            assert dec.engine.kv_import_host_count == 1
            assert dec.engine.kv_import_device_count == 0
            assert len(doc["choices"][0]["text"]) > 0
        finally:
            await pre.stop()
            await dec.stop()

    asyncio.run(body())


def test_device_pull_failure_falls_back_to_http():
    async def body():
        pre, dec = await _pd_pair(18736, 18737)
        try:
            def poison(ktp):
                # Unreachable transfer address: the pull must fail fast and
                # the decode engine degrade to the host-staged HTTP path.
                return {**ktp, "transfer_address": "127.0.0.1:1"}

            ktp, doc = await _run_pd(18736, 18737, mutate_ktp=poison)
            assert dec.engine.kv_import_device_count == 0
            assert dec.engine.kv_import_host_count == 1
            assert len(doc["choices"][0]["text"]) > 0
        finally:
            await pre.stop()
            await dec.stop()

    asyncio.run(body())


@requires_device_transfer
def test_sharded_pull_tp_pair_matches_monolithic():
    """tp-sharded P/D pair (VERDICT r2 missing #6, single-process half):
    the prefiller registers one descriptor per unique page shard
    (kv_shards.py) and the tp decode engine pulls + assembles them under
    its own page sharding — device path, token parity with monolithic."""
    async def body():
        mono = EngineServer(_cfg(18981, tp_size=2))
        await mono.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                r = await c.post("http://127.0.0.1:18981/v1/completions",
                                 json={"prompt": PROMPT, "max_tokens": 6,
                                       "temperature": 0, "ignore_eos": True})
                mono_text = r.json()["choices"][0]["text"]
        finally:
            await mono.stop()

        pre, dec = await _pd_pair(18982, 18983, tp_size=2)
        try:
            ktp, doc = await _run_pd(18982, 18983)
            assert "transfer_shards" in ktp and "kv_mesh" in ktp
            assert ktp["kv_mesh"]["n_procs"] == 1
            assert dec.engine.kv_import_device_count == 1
            assert dec.engine.kv_import_host_count == 0
            assert doc["choices"][0]["text"] == mono_text
        finally:
            await pre.stop()
            await dec.stop()

    asyncio.run(body())


@requires_device_transfer
def test_sharded_pull_pp_pair_matches_monolithic():
    """pp-sharded P/D pair: pages shard the LAYER axis over pp stages
    (pp_serve.PAGE_SPEC); the prefiller stages one descriptor per unique
    page shard and the pp decode engine pulls + scatters under its own
    stage layout — device path, token parity with a monolithic pp engine.
    (Round-5 follow-on to the tp pair: proves the kv_shards staging is
    mesh-shape-agnostic, the precondition for disagg under the host-
    spanning pp ring.)"""
    async def body():
        mono = EngineServer(_cfg(18761, pp_size=2))
        await mono.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                r = await c.post("http://127.0.0.1:18761/v1/completions",
                                 json={"prompt": PROMPT, "max_tokens": 6,
                                       "temperature": 0, "ignore_eos": True})
                mono_text = r.json()["choices"][0]["text"]
        finally:
            await mono.stop()

        pre, dec = await _pd_pair(18762, 18763, pp_size=2)
        try:
            ktp, doc = await _run_pd(18762, 18763)
            assert "transfer_shards" in ktp and "kv_mesh" in ktp
            assert dec.engine.kv_import_device_count == 1
            assert dec.engine.kv_import_host_count == 0
            assert doc["choices"][0]["text"] == mono_text
        finally:
            await pre.stop()
            await dec.stop()

    asyncio.run(body())


@requires_device_transfer
def test_sharded_geometry_mismatch_falls_back_to_host():
    """tp=2 exporter, unsharded importer: geometry mismatch must degrade to
    the host-staged path (numpy resharding), not fail the request."""
    async def body():
        pre = EngineServer(_cfg(18984, role="prefill", tp_size=2))
        dec = EngineServer(_cfg(18985, role="decode"))
        await pre.start()
        await dec.start()
        try:
            ktp, doc = await _run_pd(18984, 18985)
            assert "transfer_shards" in ktp
            assert dec.engine.kv_import_device_count == 0
            assert dec.engine.kv_import_host_count == 1
            assert len(doc["choices"][0]["text"]) > 0
        finally:
            await pre.stop()
            await dec.stop()

    asyncio.run(body())
