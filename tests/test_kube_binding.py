"""k8s watch binding (router/kube.py): a fake API server speaking the real
list+watch protocol (resourceVersions, streaming JSON events, bookmarks,
410 Gone) drives the four reconcilers into the datastore — the hermetic
analogue of the reference's envtest-based controller tests."""

from __future__ import annotations

import asyncio
import json

import pytest
from aiohttp import web

from llm_d_inference_scheduler_tpu.router.datalayer.datastore import Datastore
from llm_d_inference_scheduler_tpu.router.kube import (
    KubeApiClient,
    KubeBinding,
)

NS = "llmd"
PODS = f"/api/v1/namespaces/{NS}/pods"
POOLS = f"/apis/llm-d.ai/v1alpha2/namespaces/{NS}/inferencepools"
OBJS = f"/apis/llm-d.ai/v1alpha2/namespaces/{NS}/inferenceobjectives"
REWRITES = f"/apis/llm-d.ai/v1alpha2/namespaces/{NS}/inferencemodelrewrites"


class FakeKube:
    """Tiny API server: per-collection object store + watch event history;
    watches replay events after the requested resourceVersion then stream
    live. ``force_gone`` makes the next watch on a path return 410."""

    def __init__(self):
        self.rv = 0
        self.store: dict[str, dict[str, dict]] = {}
        self.history: dict[str, list[tuple[int, str, dict]]] = {}
        self.subscribers: dict[str, list[asyncio.Queue]] = {}
        self.force_gone: set[str] = set()
        self.app = web.Application()
        self.app.router.add_get("/{tail:.*}", self.handle)
        self.app.router.add_post("/{tail:.*}", self.handle_create)
        self.app.router.add_put("/{tail:.*}", self.handle_replace)
        self.runner = None
        self.port = None
        # Optional failure injection for write verbs (lease tests).
        self.fail_writes = False

    def _bump(self) -> int:
        self.rv += 1
        return self.rv

    def upsert(self, path: str, obj: dict):
        rv = self._bump()
        obj = json.loads(json.dumps(obj))
        obj.setdefault("metadata", {})["resourceVersion"] = str(rv)
        obj["metadata"].setdefault("namespace", NS)
        name = obj["metadata"]["name"]
        etype = "MODIFIED" if name in self.store.get(path, {}) else "ADDED"
        self.store.setdefault(path, {})[name] = obj
        self._emit(path, rv, etype, obj)

    def delete(self, path: str, name: str):
        rv = self._bump()
        obj = self.store.get(path, {}).pop(name, None)
        if obj is None:
            return
        obj["metadata"]["resourceVersion"] = str(rv)
        self._emit(path, rv, "DELETED", obj)

    def _emit(self, path: str, rv: int, etype: str, obj: dict):
        self.history.setdefault(path, []).append((rv, etype, obj))
        for q in self.subscribers.get(path, []):
            q.put_nowait((rv, etype, obj))

    async def handle_create(self, request: web.Request) -> web.Response:
        """POST to a collection: 409 when the named object exists (k8s
        AlreadyExists), else store with a fresh resourceVersion."""
        if self.fail_writes:
            return web.Response(status=500)
        path = "/" + request.match_info["tail"]
        obj = await request.json()
        name = (obj.get("metadata") or {}).get("name")
        if name in self.store.get(path, {}):
            return web.json_response({"reason": "AlreadyExists"}, status=409)
        self.upsert(path, obj)
        return web.json_response(self.store[path][name], status=201)

    async def handle_replace(self, request: web.Request) -> web.Response:
        """PUT an object: resourceVersion must match the stored one (k8s
        optimistic concurrency), else 409 Conflict."""
        if self.fail_writes:
            return web.Response(status=500)
        tail = request.match_info["tail"]
        path, _, name = ("/" + tail).rpartition("/")
        obj = await request.json()
        current = self.store.get(path, {}).get(name)
        if current is None:
            return web.Response(status=404)
        sent_rv = (obj.get("metadata") or {}).get("resourceVersion")
        if sent_rv != current["metadata"]["resourceVersion"]:
            return web.json_response({"reason": "Conflict"}, status=409)
        self.upsert(path, obj)
        return web.json_response(self.store[path][name])

    # Paths whose LAST segment is one of these are collection list/watch
    # requests; anything deeper is a single-object GET.
    COLLECTIONS = ("pods", "inferencepools", "inferenceobjectives",
                   "inferencemodelrewrites", "leases")

    async def handle(self, request: web.Request) -> web.StreamResponse:
        path = "/" + request.match_info["tail"]
        if request.query.get("watch") != "true":
            if path.rsplit("/", 1)[-1] not in self.COLLECTIONS:
                # Single-object GET (e.g. …/leases/<name>).
                coll, _, name = path.rpartition("/")
                obj = self.store.get(coll, {}).get(name)
                if obj is None:
                    return web.Response(status=404)
                return web.json_response(obj)
            items = list(self.store.get(path, {}).values())
            return web.json_response({
                "items": items,
                "metadata": {"resourceVersion": str(self.rv)}})
        if path in self.force_gone:
            self.force_gone.discard(path)
            return web.Response(status=410)
        since = int(request.query.get("resourceVersion") or 0)
        resp = web.StreamResponse()
        resp.content_type = "application/json"
        await resp.prepare(request)
        q: asyncio.Queue = asyncio.Queue()
        for rv, etype, obj in self.history.get(path, []):
            if rv > since:
                q.put_nowait((rv, etype, obj))
        self.subscribers.setdefault(path, []).append(q)
        try:
            while True:
                rv, etype, obj = await q.get()
                frame = json.dumps({"type": etype, "object": obj}) + "\n"
                await resp.write(frame.encode())
        except (asyncio.CancelledError, ConnectionResetError):
            pass
        finally:
            self.subscribers.get(path, []).remove(q)
        return resp

    async def start(self):
        # Watch handlers block in q.get(); don't let cleanup wait 60s for
        # them (aiohttp's default shutdown_timeout) — cancel quickly.
        self.runner = web.AppRunner(self.app, shutdown_timeout=0.25)
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]

    async def stop(self):
        if self.runner:
            await self.runner.cleanup()


def pod(name: str, ip: str, labels: dict, phase: str = "Running",
        ready: bool = True) -> dict:
    return {"metadata": {"name": name, "labels": labels},
            "status": {"podIP": ip, "phase": phase,
                       "conditions": [{"type": "Ready",
                                       "status": "True" if ready
                                       else "False"}]}}


async def eventually(predicate, timeout=5.0, what=""):
    deadline = asyncio.get_event_loop().time() + timeout
    while True:
        if predicate():
            return
        if asyncio.get_event_loop().time() > deadline:
            raise AssertionError(f"condition never held: {what}")
        await asyncio.sleep(0.02)


@pytest.fixture()
def fake():
    return FakeKube()


def test_kube_binding_converges_and_tracks_watches(fake):
    async def run():
        await fake.start()
        fake.upsert(POOLS, {
            "metadata": {"name": "pool"},
            "spec": {"selector": {"matchLabels": {"app": "llmd"}},
                     "targetPort": 8200, "metricsPort": 9090}})
        fake.upsert(PODS, pod("d0", "10.0.0.1", {"app": "llmd",
                                                 "llm-d.ai/role": "decode"}))
        fake.upsert(PODS, pod("d1", "10.0.0.2", {"app": "llmd"}))
        fake.upsert(PODS, pod("other", "10.9.9.9", {"app": "unrelated"}))
        fake.upsert(PODS, pod("pending", "", {"app": "llmd"},
                              phase="Pending"))
        # Running but NOT Ready (still loading weights / failing its
        # readiness probe) — must not receive traffic (pod_reconciler.go:92).
        fake.upsert(PODS, pod("warming", "10.0.0.7", {"app": "llmd"},
                              ready=False))
        fake.upsert(OBJS, {"metadata": {"name": "premium"},
                           "spec": {"priority": 10}})
        fake.upsert(REWRITES, {
            "metadata": {"name": "canary"},
            "spec": {"sourceModel": "base",
                     "targets": [{"model": "base-v2", "weight": 1}]}})

        ds = Datastore()
        client = KubeApiClient(f"http://127.0.0.1:{fake.port}")
        binding = KubeBinding(ds, client, NS, pool_name="pool")
        await binding.start()
        try:
            await binding.wait_synced()
            # Initial convergence: matching Running pods only, pool ports.
            await eventually(lambda: len(ds.endpoint_list()) == 2,
                             what="initial pod sync")
            eps = {e.metadata.address_port: e for e in ds.endpoint_list()}
            assert set(eps) == {"10.0.0.1:8200", "10.0.0.2:8200"}
            assert eps["10.0.0.1:8200"].metadata.labels["llm-d.ai/role"] == "decode"
            assert eps["10.0.0.1:8200"].metadata.metrics_port == 9090
            assert ds.objective_get("premium").priority == 10
            assert ds.rewrite_for("base") is not None

            # Watch: pod add / delete propagate; a pod turning Ready joins.
            fake.upsert(PODS, pod("warming", "10.0.0.7", {"app": "llmd"}))
            await eventually(lambda: len(ds.endpoint_list()) == 3,
                             what="pod turning Ready via watch")
            fake.upsert(PODS, pod("warming", "10.0.0.7", {"app": "llmd"},
                                  ready=False))
            await eventually(lambda: len(ds.endpoint_list()) == 2,
                             what="pod turning unready via watch")
            fake.upsert(PODS, pod("d2", "10.0.0.3", {"app": "llmd"}))
            await eventually(lambda: len(ds.endpoint_list()) == 3,
                             what="pod add via watch")
            fake.delete(PODS, "d1")
            await eventually(
                lambda: {e.metadata.address_port for e in ds.endpoint_list()}
                == {"10.0.0.1:8200", "10.0.0.3:8200"},
                what="pod delete via watch")

            # Objective delete propagates.
            fake.delete(OBJS, "premium")
            await eventually(lambda: ds.objective_get("premium") is None,
                             what="objective delete")

            # 410 Gone forces a relist; changes made meanwhile are found.
            # Kill the live pod stream so the informer reconnects and is
            # served the 410 (otherwise the healthy watch never ends).
            fake.force_gone.add(PODS)
            for q in list(fake.subscribers.get(PODS, [])):
                q.put_nowait(None)  # poison → handler errors → stream ends
            fake.upsert(PODS, pod("d3", "10.0.0.4", {"app": "llmd"}))
            await eventually(lambda: len(ds.endpoint_list()) == 3,
                             what="recovery after 410 relist")
            assert not fake.force_gone, "410 was never served to a watch"

            # Pool retarget: selector + port change re-derives endpoints
            # from the cached pods without a watch restart.
            fake.upsert(POOLS, {
                "metadata": {"name": "pool"},
                "spec": {"selector": {"matchLabels": {"app": "llmd",
                                                      "llm-d.ai/role": "decode"}},
                         "targetPort": 9000}})
            await eventually(
                lambda: {e.metadata.address_port for e in ds.endpoint_list()}
                == {"10.0.0.1:9000"},
                what="pool selector/port change")
        finally:
            await binding.stop()
            await fake.stop()

    asyncio.run(run())


def test_kube_binding_watch_resumes_from_resource_version(fake):
    """A dropped connection resumes from the last seen version — no events
    lost, no duplicate full resync (history replay path)."""
    async def run():
        await fake.start()
        ds = Datastore()
        client = KubeApiClient(f"http://127.0.0.1:{fake.port}")
        binding = KubeBinding(ds, client, NS, pool_name=None)
        binding.pool.selector = {"app": "llmd"}
        binding.pool.target_port = 8000
        await binding.start()
        try:
            await binding.wait_synced()
            fake.upsert(PODS, pod("a", "10.1.0.1", {"app": "llmd"}))
            await eventually(lambda: len(ds.endpoint_list()) == 1,
                             what="first pod")
            # Kill every live watch stream (simulates LB idle reset);
            # mutate while disconnected — the replay-from-rv path must
            # deliver the missed event.
            for qs in fake.subscribers.values():
                for q in list(qs):
                    q.put_nowait(None)  # poison → TypeError → stream ends
            fake.upsert(PODS, pod("b", "10.1.0.2", {"app": "llmd"}))
            await eventually(lambda: len(ds.endpoint_list()) == 2,
                             what="missed event recovered on resume")
        finally:
            await binding.stop()
            await fake.stop()

    asyncio.run(run())


def test_gateway_routes_to_kube_discovered_endpoints(fake):
    """Full path: gateway + kube binding against the fake API server; pods
    appear as endpoints and serve a real completion via a sim engine."""
    async def run():
        from llm_d_inference_scheduler_tpu.engine.config import EngineConfig
        from llm_d_inference_scheduler_tpu.engine.server import EngineServer
        from llm_d_inference_scheduler_tpu.router.gateway import build_gateway

        eng = EngineServer(EngineConfig(model="tiny", backend="sim",
                                        port=18951, kv_events_port=0))
        await eng.start()
        await fake.start()
        fake.upsert(POOLS, {
            "metadata": {"name": "pool"},
            "spec": {"selector": {"matchLabels": {"app": "llmd"}},
                     "targetPort": 18951}})
        fake.upsert(PODS, pod("sim0", "127.0.0.1", {"app": "llmd"}))

        gw = build_gateway(
            "plugins: [{type: queue-scorer}]\n"
            "schedulingProfiles: [{name: default, plugins: "
            "[{pluginRef: queue-scorer}]}]\n",
            port=18950,
            kube={"api_url": f"http://127.0.0.1:{fake.port}",
                  "namespace": NS, "pool_name": "pool"})
        await gw.start()
        try:
            await gw.kube_binding.wait_synced()
            await eventually(
                lambda: len(gw.datastore.endpoint_list()) == 1,
                what="kube-discovered endpoint")

            import json as _json
            import urllib.request

            def post():
                body = _json.dumps({"model": "tiny", "prompt": "hi there",
                                    "max_tokens": 3}).encode()
                r = urllib.request.urlopen(urllib.request.Request(
                    "http://127.0.0.1:18950/v1/completions", data=body,
                    headers={"Content-Type": "application/json"}), timeout=30)
                return r.headers.get("x-gateway-destination-endpoint-served")

            dest = await asyncio.get_running_loop().run_in_executor(None, post)
            assert dest == "127.0.0.1:18951"
        finally:
            await gw.stop()
            await fake.stop()
            await eng.stop()

    asyncio.run(run())


# ---- coordination.k8s.io/v1 Lease leader election -----------------------


def make_lease_elector(fake, holder, **kw):
    from llm_d_inference_scheduler_tpu.router.kube import KubeLeaseElector

    client = KubeApiClient(f"http://127.0.0.1:{fake.port}")
    return KubeLeaseElector(client, NS, "epp-llmd-pool.llm-d.ai",
                            holder_id=holder,
                            lease_duration_s=kw.pop("lease_duration_s", 0.6),
                            renew_interval_s=kw.pop("renew_interval_s", 0.1),
                            **kw)


LEASES = f"/apis/coordination.k8s.io/v1/namespaces/{NS}/leases"


def test_kube_lease_acquire_renew_and_follower(fake):
    """First claimant creates the Lease and leads; a second stays follower
    while the lease is live; renewTime advances on the wire."""
    async def run():
        await fake.start()
        a = make_lease_elector(fake, "epp-a")
        b = make_lease_elector(fake, "epp-b")
        try:
            await a.start()
            await eventually(lambda: a.is_leader, what="a acquires")
            lease = fake.store[LEASES]["epp-llmd-pool.llm-d.ai"]
            assert lease["spec"]["holderIdentity"] == "epp-a"
            assert lease["spec"]["leaseTransitions"] == 0
            first_renew = lease["spec"]["renewTime"]
            await b.start()
            await asyncio.sleep(0.4)
            assert not b.is_leader and a.is_leader
            lease = fake.store[LEASES]["epp-llmd-pool.llm-d.ai"]
            assert lease["spec"]["renewTime"] > first_renew  # renewing
        finally:
            await a.stop()
            await b.stop()
            await fake.stop()

    asyncio.run(run())


def test_kube_lease_expiry_takeover_and_transitions(fake):
    """Killing the leader non-gracefully lets the follower take over after
    leaseDurationSeconds, bumping leaseTransitions (client-go takeover)."""
    async def run():
        await fake.start()
        a = make_lease_elector(fake, "epp-a")
        b = make_lease_elector(fake, "epp-b")
        try:
            await a.start()
            await eventually(lambda: a.is_leader, what="a acquires")
            await b.start()
            await asyncio.sleep(0.25)
            assert not b.is_leader
            # Crash a: no graceful release — b must wait out the expiry.
            await a.stop(graceful=False)
            await eventually(lambda: b.is_leader, timeout=5.0,
                             what="takeover after expiry")
            lease = fake.store[LEASES]["epp-llmd-pool.llm-d.ai"]
            assert lease["spec"]["holderIdentity"] == "epp-b"
            assert lease["spec"]["leaseTransitions"] == 1
        finally:
            await a.stop()
            await b.stop()
            await fake.stop()

    asyncio.run(run())


def test_kube_lease_graceful_release_fast_handoff(fake):
    """Graceful stop shortens the lease so the follower takes over on its
    next tick instead of waiting a full leaseDuration."""
    async def run():
        await fake.start()
        a = make_lease_elector(fake, "epp-a", lease_duration_s=30.0)
        b = make_lease_elector(fake, "epp-b", lease_duration_s=30.0)
        try:
            await a.start()
            await eventually(lambda: a.is_leader, what="a acquires")
            await b.start()
            await asyncio.sleep(0.25)
            assert not b.is_leader
            await a.stop(graceful=True)  # release: 30 s lease would block b
            await eventually(lambda: b.is_leader, timeout=3.0,
                             what="fast handoff after release")
        finally:
            await a.stop()
            await b.stop()
            await fake.stop()

    asyncio.run(run())


def test_kube_lease_demotes_when_api_unreachable(fake):
    """A leader that cannot renew must drop leadership (its lease may have
    been taken over) — readiness flips, the pair cannot split-brain."""
    async def run():
        await fake.start()
        a = make_lease_elector(fake, "epp-a")
        try:
            await a.start()
            await eventually(lambda: a.is_leader, what="a acquires")
            fake.fail_writes = True
            await eventually(lambda: not a.is_leader, timeout=3.0,
                             what="demote on renew failure")
            fake.fail_writes = False
            await eventually(lambda: a.is_leader, timeout=3.0,
                             what="re-acquire after API recovers")
        finally:
            await a.stop()
            await fake.stop()

    asyncio.run(run())


def test_gateway_ha_pair_via_kube_lease(fake):
    """Two gateways with lease-only kube config (endpoints from static
    config): only the Lease holder reports ready; killing it promotes the
    follower — the reference's HA disruption semantics without any shared
    volume (controller_manager.go:84-91)."""
    from llm_d_inference_scheduler_tpu.router.gateway import build_gateway
    from llm_d_inference_scheduler_tpu.router.kube import KubeLeaseElector

    async def run():
        await fake.start()
        cfg = """
pool:
  endpoints:
    - {address: 127.0.0.1, port: 19999}
"""
        gws = []
        for port in (18880, 18881):
            gw = build_gateway(
                cfg, port=port, poll_interval=0.05,
                kube={"api_url": f"http://127.0.0.1:{fake.port}",
                      "namespace": NS,
                      "lease_name": "epp-llmd-pool.llm-d.ai"})
            assert isinstance(gw.elector, KubeLeaseElector)
            assert gw.kube_binding is None  # lease-only: config owns pool
            gw.elector.lease_duration_s = 0.6
            gw.elector.renew_interval_s = 0.1
            await gw.start()
            gws.append(gw)
        try:
            import aiohttp

            async def ready(port):
                async with aiohttp.ClientSession() as s:
                    async with s.get(f"http://127.0.0.1:{port}/health") as r:
                        return r.status == 200

            await eventually(
                lambda: sum(gw.elector.is_leader for gw in gws) == 1,
                what="exactly one leader")
            leader = next(gw for gw in gws if gw.elector.is_leader)
            follower = next(gw for gw in gws if not gw.elector.is_leader)
            assert await ready(leader.port)
            assert not await ready(follower.port)
            # Disruption: leader dies without a graceful release.
            await leader.elector.stop(graceful=False)
            leader.elector = None  # detach so gw.stop() doesn't double-stop
            await eventually(lambda: follower.elector.is_leader, timeout=5.0,
                             what="follower promoted after leader loss")
            assert await ready(follower.port)
        finally:
            for gw in gws:
                await gw.stop()
            await fake.stop()

    asyncio.run(run())


def test_kube_lease_skewed_holder_clock_no_spurious_takeover(fake):
    """A live holder whose wall clock is far behind (renewTime 'expired' by
    local reckoning) must NOT be stolen from while its renews keep landing:
    expiry is timed from the local observation of lease changes (client-go
    observedTime), not from comparing remote timestamps to the local
    clock."""
    import time as _time

    from llm_d_inference_scheduler_tpu.router.kube import _micro_time

    async def run():
        await fake.start()
        name = "epp-llmd-pool.llm-d.ai"
        skew = -3600.0  # holder's clock is an hour behind

        def skewed_renew():
            lease = fake.store.get(LEASES, {}).get(name)
            spec = {"holderIdentity": "epp-skewed",
                    "leaseDurationSeconds": 1,
                    "renewTime": _micro_time(_time.time() + skew),
                    "leaseTransitions": 0}
            if lease is None:
                fake.upsert(LEASES, {"metadata": {"name": name},
                                     "spec": spec})
            else:
                lease["spec"].update(spec)
                fake.upsert(LEASES, lease)

        skewed_renew()
        b = make_lease_elector(fake, "epp-b", lease_duration_s=1.0,
                               renew_interval_s=0.1)
        try:
            await b.start()
            # Keep the skewed holder renewing faster than its 1 s lease.
            for _ in range(10):
                await asyncio.sleep(0.2)
                skewed_renew()
                assert not b.is_leader, "stole a live (skewed) lease"
            holder = fake.store[LEASES][name]["spec"]["holderIdentity"]
            assert holder == "epp-skewed"
            # Once the skewed holder really stops, b takes over on the
            # locally-observed expiry.
            await eventually(lambda: b.is_leader, timeout=5.0,
                             what="takeover after real death")
        finally:
            await b.stop()
            await fake.stop()

    asyncio.run(run())
