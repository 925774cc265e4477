"""SLO-ledger terminal-path check: every way a request can end must stamp
an outcome.

The ledger's value is completeness — attainment ratios and goodput are only
honest if error/abort paths record ``slo_met: false`` instead of silently
dropping the row (ISSUE 6 satellite: "otherwise attainment ratios
overcount"). This check drives a real gateway + sim engine through each
terminal shape and fails unless ``/debug/decisions/<id>`` carries an
outcome block with a verdict:

- **success** — served 200, generous SLO → ``slo_met: true``;
- **shed** — flow-control capacity 0 → 429 at admission;
- **retry-exhausted** — every candidate connect-fails → 502;
- **deadline** — budget expires mid-walk after a slow upstream attempt → 504;
- **abort** — client disconnects mid-stream → the record still closes;
- **overload shed** — the overload controller (router/overload.py) refuses
  a predictively-hopeless request: the ledger must stamp the distinct
  ``shed`` verdict EXACTLY once and the 429 must carry a finite
  ``Retry-After``.

Run via ``make verify-slo``; tests/test_slo.py hooks it into the pytest run.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GW, ENG, DEAD, GW_SHED, GW_OVL = 18710, 18711, 18712, 18713, 18714

CFG = f"""
featureGates: {{flowControl: true}}
resilience: {{maxAttempts: 2, defaultTimeoutSeconds: 0}}
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {ENG}}}
    - {{address: 127.0.0.1, port: {DEAD}}}
"""

SHED_CFG = f"""
featureGates: {{flowControl: true}}
flowControl: {{maxGlobalRequests: 0}}
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {ENG}}}
"""

OVL_CFG = f"""
featureGates: {{flowControl: true}}
overload: {{enabled: true}}
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {ENG}}}
plugins:
  - {{type: predicted-latency-producer}}
  - {{type: queue-scorer}}
schedulingProfiles:
  - name: default
    plugins:
      - {{pluginRef: queue-scorer}}
"""


async def _outcome(client, port: int, rid: str) -> dict | None:
    r = await client.get(f"http://127.0.0.1:{port}/debug/decisions/{rid}")
    if r.status_code != 200:
        return None
    return r.json().get("outcome") or None


async def _drive() -> list[str]:
    import asyncio

    import httpx

    from llm_d_inference_scheduler_tpu.engine import EngineConfig
    from llm_d_inference_scheduler_tpu.engine.server import EngineServer
    from llm_d_inference_scheduler_tpu.router.gateway import build_gateway

    errors: list[str] = []
    eng = EngineServer(EngineConfig(backend="sim", model="tiny", port=ENG,
                                    sim_decode_ms_per_token=15.0))
    await eng.start()
    gw = build_gateway(CFG, port=GW, poll_interval=0.02)
    await gw.start()
    gw_shed = build_gateway(SHED_CFG, port=GW_SHED, poll_interval=0.02)
    await gw_shed.start()
    gw_ovl = build_gateway(OVL_CFG, port=GW_OVL, poll_interval=0.02)
    await gw_ovl.start()

    def expect(name: str, outcome: dict | None, *, met: bool) -> None:
        if outcome is None:
            errors.append(f"{name}: no outcome block on the decision record")
            return
        if "slo_met" not in outcome:
            errors.append(f"{name}: outcome block missing slo_met")
            return
        if outcome["slo_met"] is not met:
            errors.append(f"{name}: slo_met={outcome['slo_met']}, "
                          f"expected {met} ({outcome.get('reason')})")
        if not met and not outcome.get("reason"):
            errors.append(f"{name}: slo_met=false without a reason")

    try:
        async with httpx.AsyncClient(timeout=60) as c:
            # 1. success — generous SLO, served by the live sim engine.
            rid = "verify-slo-success"
            r = await c.post(
                f"http://127.0.0.1:{GW}/v1/completions",
                json={"model": "tiny", "prompt": "ok", "max_tokens": 4},
                headers={"x-request-id": rid, "x-slo-ttft-ms": "60000",
                         "x-gateway-destination-endpoint-subset":
                             f"127.0.0.1:{ENG}"})
            if r.status_code != 200:
                errors.append(f"success: expected 200, got {r.status_code}")
            expect("success", await _outcome(c, GW, rid), met=True)

            # 2. shed — flow control has zero capacity: 429 at admission.
            rid = "verify-slo-shed"
            r = await c.post(
                f"http://127.0.0.1:{GW_SHED}/v1/completions",
                json={"model": "tiny", "prompt": "ok", "max_tokens": 2},
                headers={"x-request-id": rid})
            if r.status_code != 429:
                errors.append(f"shed: expected 429, got {r.status_code}")
            expect("shed", await _outcome(c, GW_SHED, rid), met=False)

            # 3. retry-exhausted — only the dead endpoint is eligible, every
            # attempt connect-fails, the reschedule finds nothing new.
            rid = "verify-slo-retry-exhausted"
            r = await c.post(
                f"http://127.0.0.1:{GW}/v1/completions",
                json={"model": "tiny", "prompt": "ok", "max_tokens": 2},
                headers={"x-request-id": rid,
                         "x-gateway-destination-endpoint-subset":
                             f"127.0.0.1:{DEAD}"})
            if r.status_code != 502:
                errors.append(f"retry-exhausted: expected 502, "
                              f"got {r.status_code}")
            expect("retry-exhausted", await _outcome(c, GW, rid), met=False)

            # 4. deadline — the budget expires while the only candidate's
            # attempt times out, so the failover walk ends on the deadline.
            rid = "verify-slo-deadline"
            r = await c.post(
                f"http://127.0.0.1:{GW}/v1/completions",
                json={"model": "tiny", "prompt": "ok", "max_tokens": 64},
                headers={"x-request-id": rid, "x-request-timeout": "0.2",
                         "x-gateway-destination-endpoint-subset":
                             f"127.0.0.1:{ENG}"})
            if r.status_code != 504:
                errors.append(f"deadline: expected 504, got {r.status_code}")
            expect("deadline", await _outcome(c, GW, rid), met=False)

            # 5. abort — client walks away mid-stream; the ledger must still
            # close the record (slo_met=false, not an absent row).
            rid = "verify-slo-abort"
            # On a bare socket, so that hanging up is a close() and nothing
            # else: httpx's early exit from a streamed response can fail
            # inside anyio (seen once torch is imported in the process) and
            # leave the connection open — the stream then ends normally and
            # nothing was aborted.
            body = json.dumps({"model": "tiny", "prompt": "ok",
                               "max_tokens": 256, "stream": True}).encode()
            reader, writer = await asyncio.open_connection("127.0.0.1", GW)
            writer.write(
                b"POST /v1/completions HTTP/1.1\r\nhost: verify\r\n"
                b"content-type: application/json\r\n"
                + f"x-request-id: {rid}\r\n".encode()
                + b"x-gateway-destination-endpoint-subset: "
                + f"127.0.0.1:{ENG}\r\n".encode()
                + f"content-length: {len(body)}\r\n\r\n".encode() + body)
            await writer.drain()
            while True:  # up to the first chunk, then hang up
                chunk = await reader.read(4096)
                if not chunk or b"data:" in chunk:
                    break
            writer.close()
            # Give the gateway a few relay ticks to notice the disconnect.
            outcome = None
            for _ in range(100):
                await asyncio.sleep(0.05)
                outcome = await _outcome(c, GW, rid)
                if outcome is not None:
                    break
            expect("abort", outcome, met=False)

            # 6. overload shed-at-admission — train the predictor past its
            # sample floor, then a 0.01ms TTFT SLO is predictively
            # hopeless: the 429 must carry a finite Retry-After and the
            # ledger must stamp the distinct shed verdict EXACTLY once.
            for i in range(7):
                r = await c.post(
                    f"http://127.0.0.1:{GW_OVL}/v1/completions",
                    json={"model": "tiny", "prompt": f"t{i}",
                          "max_tokens": 2})
                if r.status_code != 200:
                    errors.append(f"overload-shed: training request {i} "
                                  f"got {r.status_code}")
            rid = "verify-slo-overload-shed"
            r = await c.post(
                f"http://127.0.0.1:{GW_OVL}/v1/completions",
                json={"model": "tiny", "prompt": "ok", "max_tokens": 2},
                headers={"x-request-id": rid, "x-slo-ttft-ms": "0.01"})
            if r.status_code != 429:
                errors.append(f"overload-shed: expected 429, "
                              f"got {r.status_code}")
            ra = r.headers.get("retry-after")
            try:
                if ra is None or not (1 <= int(ra) <= 86400):
                    errors.append(f"overload-shed: 429 without a finite "
                                  f"Retry-After (got {ra!r})")
            except ValueError:
                errors.append(f"overload-shed: non-integer Retry-After "
                              f"{ra!r}")
            outcome = await _outcome(c, GW_OVL, rid)
            expect("overload-shed", outcome, met=False)
            if outcome is not None and not outcome.get("shed"):
                errors.append("overload-shed: outcome block missing the "
                              "shed verdict marker")
            totals = (await c.get(
                f"http://127.0.0.1:{GW_OVL}/debug/slo")).json()["totals"]
            if totals.get("shed") != 1:
                errors.append(f"overload-shed: ledger shed count "
                              f"{totals.get('shed')} != 1 (stamp must land "
                              "exactly once)")
            if totals.get("requests") != 8:
                errors.append(f"overload-shed: ledger requests "
                              f"{totals.get('requests')} != 8")
    finally:
        await gw_ovl.stop()
        await gw_shed.stop()
        await gw.stop()
        await eng.stop()
    return errors


def check() -> list[str]:
    import asyncio

    return asyncio.run(_drive())


def main() -> int:
    errors = check()
    for e in errors:
        print(f"verify-slo: {e}", file=sys.stderr)
    if errors:
        return 1
    print("verify-slo: all 6 terminal paths (success, shed, retry-exhausted, "
          "deadline, abort, overload-shed+Retry-After) stamp an SLO outcome")
    return 0


if __name__ == "__main__":
    sys.exit(main())
