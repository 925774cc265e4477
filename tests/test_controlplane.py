"""Leader election + config reconciler (VERDICT r1 item 8): the standalone
analogues of the reference's lease election (runner.go:306-316) and CRD
reconcilers (pkg/epp/controller), including the disruption-test shape
(test/e2e/disruption_test.go:86-316): leader serves, follower not-ready,
leader death → takeover."""

import asyncio
import json

import httpx

from llm_d_inference_scheduler_tpu.engine import EngineConfig
from llm_d_inference_scheduler_tpu.engine.server import EngineServer
from llm_d_inference_scheduler_tpu.router.controlplane import (
    ConfigReconciler,
    LeaseConfig,
    LeaseElector,
)
from llm_d_inference_scheduler_tpu.router.datalayer.datastore import Datastore
from llm_d_inference_scheduler_tpu.router.gateway import build_gateway


def _lease(path, holder, dur=0.6, renew=0.1):
    return LeaseConfig(path=str(path), holder_id=holder,
                       lease_duration_s=dur, renew_interval_s=renew)


def test_lease_acquire_and_follower_blocked(tmp_path):
    async def body():
        a = LeaseElector(_lease(tmp_path / "lease", "a"))
        b = LeaseElector(_lease(tmp_path / "lease", "b"))
        await a.start()
        await asyncio.sleep(0.3)
        await b.start()
        await asyncio.sleep(0.3)
        assert a.is_leader and not b.is_leader
        await a.stop()
        await b.stop()

    asyncio.run(body())


def test_graceful_release_hands_over_fast(tmp_path):
    async def body():
        a = LeaseElector(_lease(tmp_path / "lease", "a"))
        b = LeaseElector(_lease(tmp_path / "lease", "b"))
        await a.start()
        await asyncio.sleep(0.25)
        await b.start()
        await asyncio.sleep(0.25)
        assert a.is_leader
        await a.stop(graceful=True)  # zeroes the expiry
        for _ in range(30):
            await asyncio.sleep(0.1)
            if b.is_leader:
                break
        assert b.is_leader
        await b.stop()

    asyncio.run(body())


def test_crash_takeover_after_expiry(tmp_path):
    async def body():
        a = LeaseElector(_lease(tmp_path / "lease", "a"))
        b = LeaseElector(_lease(tmp_path / "lease", "b"))
        await a.start()
        await asyncio.sleep(0.25)
        await b.start()
        assert not b.is_leader
        # Simulate a crash: the renew loop dies WITHOUT releasing the lease.
        await a.stop(graceful=False)
        took = None
        for i in range(40):
            await asyncio.sleep(0.1)
            if b.is_leader:
                took = i * 0.1
                break
        assert b.is_leader, "follower never took over"
        assert took >= 0.2  # not before the lease expired
        await b.stop()

    asyncio.run(body())


def test_config_reconciler_converges_datastore(tmp_path):
    cfg_path = tmp_path / "config.yaml"
    cfg_path.write_text("""
pool:
  endpoints:
    - {address: 10.0.0.1, port: 8200}
objectives:
  - {name: premium, priority: 5}
modelRewrites:
  - {source: old-model, targets: [{model: new-model, weight: 1}]}
""")
    ds = Datastore()
    rec = ConfigReconciler(str(cfg_path), ds)
    assert rec.reconcile_once()
    assert [e.metadata.address_port for e in ds.endpoint_list()] == ["10.0.0.1:8200"]
    assert ds.objective_get("premium").priority == 5
    assert ds.rewrite_for("old-model") is not None

    # Declarative update: endpoint replaced, objective changed, rewrite gone.
    cfg_path.write_text("""
pool:
  endpoints:
    - {address: 10.0.0.2, port: 8200}
    - {address: 10.0.0.3, port: 8200}
objectives:
  - {name: batch, priority: -1}
""")
    assert rec.reconcile_once()
    assert sorted(e.metadata.address_port for e in ds.endpoint_list()) == [
        "10.0.0.2:8200", "10.0.0.3:8200"]
    assert ds.objective_get("premium") is None
    assert ds.objective_get("batch").priority == -1
    assert ds.rewrite_for("old-model") is None

    # Unchanged mtime → no-op; malformed content → keep last good state.
    assert not rec.reconcile_once()
    cfg_path.write_text("pool: [broken")
    assert not rec.reconcile_once()
    assert len(ds.endpoint_list()) == 2


def test_ha_gateway_failover_e2e(tmp_path):
    """Two gateway replicas sharing a lease: leader 200, follower 503 on
    /health; kill the leader → the follower takes over and serves."""
    ENG, GW_A, GW_B = 18976, 18977, 18978
    lease = str(tmp_path / "lease")

    async def body():
        eng = EngineServer(EngineConfig(backend="sim", model="tiny", port=ENG,
                                        sim_decode_ms_per_token=1.0))
        await eng.start()
        cfg = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {ENG}}}
"""
        gw_a = build_gateway(cfg, port=GW_A, poll_interval=0.02, lease_path=lease)
        gw_b = build_gateway(cfg, port=GW_B, poll_interval=0.02, lease_path=lease)
        # Fast elections for the test.
        for gw in (gw_a, gw_b):
            gw.elector.cfg.lease_duration_s = 0.6
            gw.elector.cfg.renew_interval_s = 0.1
        await gw_a.start()
        await asyncio.sleep(0.3)
        await gw_b.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                await asyncio.sleep(0.4)
                ra = await c.get(f"http://127.0.0.1:{GW_A}/health")
                rb = await c.get(f"http://127.0.0.1:{GW_B}/health")
                assert ra.status_code == 200
                assert rb.status_code == 503
                assert rb.json()["status"] == "follower"

                # Leader serves inference; the follower (not-ready) is what a
                # health-checking LB would skip.
                r = await c.post(f"http://127.0.0.1:{GW_A}/v1/completions",
                                 json={"model": "tiny", "prompt": "x",
                                       "max_tokens": 2})
                assert r.status_code == 200

                # Disruption: stop the leader (graceful release).
                await gw_a.stop()
                for _ in range(30):
                    await asyncio.sleep(0.1)
                    if gw_b.elector.is_leader:
                        break
                rb = await c.get(f"http://127.0.0.1:{GW_B}/health")
                assert rb.status_code == 200
                r = await c.post(f"http://127.0.0.1:{GW_B}/v1/completions",
                                 json={"model": "tiny", "prompt": "y",
                                       "max_tokens": 2})
                assert r.status_code == 200
        finally:
            await gw_b.stop()
            await eng.stop()

    asyncio.run(body())
