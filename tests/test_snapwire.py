"""Binary zero-copy snapshot wire (ISSUE 19): router/snapwire.py framing,
the AttrSanitizer probe cache, corrupt-frame robustness (counted and
skipped, never a subscriber crash), direct column install on the follower
datastore, delta base-matching, promotion-time materialization, and the
publisher's delta-eligibility logic — plus an end-to-end binary
publisher→subscriber round trip with a corrupt frame injected mid-stream.
"""

import asyncio
import pickle
import threading

import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.router import snapwire
from llm_d_inference_scheduler_tpu.router.datalayer.datastore import Datastore
from llm_d_inference_scheduler_tpu.router.fleet import (
    _FRAME_LEN,
    SnapshotPublisher,
    SnapshotSubscriber,
)
from llm_d_inference_scheduler_tpu.router.framework.datalayer import (
    EndpointMetadata,
)
from llm_d_inference_scheduler_tpu.router.metrics import SNAPSHOT_FRAME_ERRORS
from llm_d_inference_scheduler_tpu.router.snapshot import (
    NUMERIC_FIELDS,
    ColumnMetrics,
)


def run(coro):
    return asyncio.run(coro)


def mk_leader(n=4, epoch_bump=0):
    ds = Datastore()
    ds.SNAPSHOT_MIN_REFRESH_S = 0.0  # tests re-snapshot immediately
    for i in range(n):
        meta = EndpointMetadata(
            name=f"pod-{i}", address=f"10.1.0.{i}", port=8000 + i,
            namespace="infer", metrics_port=9090 if i % 2 else None,
            labels={"llm-d.ai/role": "decode", "zone": f"z{i % 2}"})
        ds.endpoint_add_or_update(meta)
        ep = ds.endpoint_get(meta.address_port)
        ep.metrics.waiting_queue_size = i * 3
        ep.metrics.kv_cache_usage_percent = i / 10.0
        ep.metrics.running_requests_size = i
        ep.attributes.put("warm", True)
        ep.attributes.put("tier", i)
    for _ in range(epoch_bump):
        ds.mark_snapshot_dirty()
        ds.snapshot()  # mint an epoch per bump
    return ds


def encode_snapshot(snap):
    cols = snap.columns()
    blob = snapwire.AttrSanitizer().blob(cols.attrs, cols.models)
    return cols, snapwire.encode_full(snap.epoch, cols, blob)


# ---- framing round trips --------------------------------------------------


def test_full_frame_round_trip():
    snap = mk_leader().snapshot()
    cols, frame = encode_snapshot(snap)
    kind, epoch, got = snapwire.decode(frame)
    assert kind == "full" and epoch == snap.epoch
    assert got.n == cols.n and got.base_id == snap.epoch
    assert list(got.keys) == list(cols.keys)
    for f in NUMERIC_FIELDS:
        np.testing.assert_array_equal(got.num[f], cols.num[f])
    np.testing.assert_array_equal(got.role_code, cols.role_code)
    np.testing.assert_array_equal(got.draining, cols.draining)
    for a, b in zip(got.metas, cols.metas):
        assert (a.name, a.address, a.port, a.namespace, a.metrics_port,
                a.scheme, a.labels) == (b.name, b.address, b.port,
                                        b.namespace, b.metrics_port,
                                        b.scheme, b.labels)
    assert got.attrs == cols.attrs and got.models == cols.models
    # Zero-copy contract: decoded numeric columns are read-only views over
    # the frame buffer, not copies.
    assert not got.num[NUMERIC_FIELDS[0]].flags.writeable


def test_full_frame_handles_nan_and_none_metrics_port():
    ds = mk_leader(n=3)
    ep = ds.endpoint_get("10.1.0.0:8000")
    ep.metrics.kv_cache_usage_percent = float("nan")
    ds.mark_snapshot_dirty()
    snap = ds.snapshot()
    cols, frame = encode_snapshot(snap)
    _, _, got = snapwire.decode(frame)
    np.testing.assert_array_equal(
        got.num["kv_cache_usage_percent"], cols.num["kv_cache_usage_percent"])
    assert got.metas[0].metrics_port is None
    assert got.metas[1].metrics_port == 9090


def test_delta_frame_round_trip():
    snap = mk_leader().snapshot()
    cols = snap.columns()
    frame = snapwire.encode_delta(snap.epoch + 1, snap.epoch, cols.num)
    kind, epoch, base_id, num = snapwire.decode(frame)
    assert kind == "delta"
    assert epoch == snap.epoch + 1 and base_id == snap.epoch
    assert set(num) == set(NUMERIC_FIELDS)
    for f in NUMERIC_FIELDS:
        np.testing.assert_array_equal(num[f], cols.num[f])
    # Delta is the steady-state frame: numeric columns only, far smaller
    # than the full frame with its string table and attr blob.
    _, full = encode_snapshot(snap)
    assert len(frame) < len(full)


# ---- corruption: every reason, always FrameError --------------------------


def _corrupt(frame, reason):
    buf = bytearray(frame)
    if reason == "truncated":
        return bytes(buf[:20])  # shorter than the fixed header
    if reason == "truncated-body":
        return bytes(buf[:-7])  # header intact, payload short of its claim
    if reason == "version":
        buf[4] = snapwire.VERSION + 1
        return bytes(buf)
    if reason == "checksum":
        buf[-1] ^= 0xFF
        return bytes(buf)
    if reason == "malformed-kind":
        # Valid header + checksum, unknown frame kind.
        kind, epoch, _, num = ("x", 0, 0, None)
        body = frame[snapwire._HEADER.size:]
        return snapwire._pack_frame(9, 1, body)
    raise AssertionError(reason)


@pytest.mark.parametrize("mutation, reason", [
    ("truncated", "truncated"),
    ("truncated-body", "truncated"),
    ("version", "version"),
    ("checksum", "checksum"),
    ("malformed-kind", "malformed"),
])
def test_corrupt_frames_raise_typed_frame_error(mutation, reason):
    snap = mk_leader().snapshot()
    _, frame = encode_snapshot(snap)
    with pytest.raises(snapwire.FrameError) as ei:
        snapwire.decode(_corrupt(frame, mutation))
    assert ei.value.reason == reason


def test_garbage_payload_inside_valid_envelope_is_malformed():
    # Checksum passes (it covers whatever bytes are there) but the payload
    # doesn't parse: decode must degrade to FrameError, not raise raw
    # struct/pickle errors at the subscriber.
    frame = snapwire._pack_frame(snapwire.KIND_FULL, 7, b"\x00" * 11)
    with pytest.raises(snapwire.FrameError) as ei:
        snapwire.decode(frame)
    assert ei.value.reason == "malformed"


# ---- attr sanitizer probe cache -------------------------------------------


def test_sanitizer_drops_unpicklable_and_caches_verdicts():
    san = snapwire.AttrSanitizer()
    lock = threading.Lock()
    attrs = [{"warm": True, "lock": lock}, {"warm": False}]
    models = [("m",), ("m",)]
    blob = san.blob(attrs, models)
    got_attrs, got_models = pickle.loads(blob)
    assert got_attrs == [{"warm": True}, {"warm": False}]
    assert got_models == models
    # Verdicts memoized by (key, id(value)): steady-state frames skip the
    # probe pass entirely.
    assert san.probe("lock", lock) is False
    assert ("lock", id(lock)) in san._verdicts
    assert san._verdicts[("lock", id(lock))] is False
    assert san.probe("warm", True) is True


# ---- follower datastore: direct column install ----------------------------


def test_apply_remote_columns_and_delta():
    snap = mk_leader().snapshot()
    cols, frame = encode_snapshot(snap)
    _, epoch, got = snapwire.decode(frame)
    follower = Datastore()
    follower.apply_remote_columns(epoch, got)
    assert follower.snapshot().epoch == epoch
    ep = follower.endpoint_get("10.1.0.2:8002")
    assert ep is not None
    assert isinstance(ep.metrics, ColumnMetrics)
    assert ep.metrics.waiting_queue_size == 6
    assert ep.attributes.get("warm") is True and ep.attributes.get("tier") == 2

    # Metrics-only delta: live endpoint proxies see the new values through
    # one columns-pointer swap — no per-endpoint re-marshal.
    num = {f: snap.columns().num[f].copy() for f in NUMERIC_FIELDS}
    num["waiting_queue_size"] = num["waiting_queue_size"] + 100
    dframe = snapwire.encode_delta(epoch + 1, epoch, num)
    _, depoch, base_id, dnum = snapwire.decode(dframe)
    assert follower.apply_remote_delta(depoch, base_id, dnum) is True
    assert follower.snapshot().epoch == depoch
    assert ep.metrics.waiting_queue_size == 106  # same proxy object

    # A delta whose base is NOT the installed columns is dropped (False):
    # the next full frame re-anchors.
    assert follower.apply_remote_delta(depoch + 1, base_id + 999, dnum) is False
    assert follower.snapshot().epoch == depoch

    # A pickle-path snapshot clears the columns anchor: deltas no longer
    # apply until the next binary full frame.
    follower.apply_remote_snapshot(
        depoch + 1, [(e.metadata, e.metrics, dict(e.attributes._data))
                     for e in mk_leader(n=4).snapshot().view()])
    assert follower.apply_remote_delta(depoch + 2, epoch, dnum) is False


def test_resume_local_snapshots_materializes_column_metrics():
    snap = mk_leader().snapshot()
    _, frame = encode_snapshot(snap)
    _, epoch, got = snapwire.decode(frame)
    follower = Datastore()
    follower.apply_remote_columns(epoch, got)
    ep = follower.endpoint_get("10.1.0.1:8001")
    assert isinstance(ep.metrics, ColumnMetrics)
    # Promotion to leader: column-backed proxies must become plain mutable
    # Metrics so local scrape collectors can write in place (the decoded
    # arrays are read-only frame views).
    follower.resume_local_snapshots()
    assert not isinstance(ep.metrics, ColumnMetrics)
    before = ep.metrics.waiting_queue_size
    ep.metrics.waiting_queue_size = before + 1
    assert ep.metrics.waiting_queue_size == before + 1
    assert follower._columns_ref is None


# ---- subscriber robustness: count + skip, never crash ---------------------


def _frame_errors(reason):
    return SNAPSHOT_FRAME_ERRORS.labels(reason=reason)._value.get()


def test_subscriber_counts_and_skips_corrupt_frames():
    snap = mk_leader().snapshot()
    _, frame = encode_snapshot(snap)
    follower = Datastore()
    sub = SnapshotSubscriber(follower, "/nonexistent")
    for mutation, reason in [("truncated", "truncated"),
                             ("checksum", "checksum"),
                             ("version", "version"),
                             ("malformed-kind", "malformed")]:
        before = _frame_errors(reason)
        sub._handle_binary(_corrupt(frame, mutation))
        assert _frame_errors(reason) == before + 1, reason
        assert sub.applied_epoch == 0  # nothing applied
        assert follower.endpoint_get("10.1.0.0:8000") is None
    # The very next good frame still applies — the subscriber survived.
    sub._handle_binary(frame)
    assert sub.applied_epoch == snap.epoch
    assert follower.endpoint_get("10.1.0.0:8000") is not None


# ---- publisher: delta eligibility + wire selection ------------------------


def _inner_kind(frame):
    inner = frame[_FRAME_LEN.size:]
    assert inner[:4] == snapwire.MAGIC
    return inner[5]


def test_publisher_delta_eligibility(tmp_path):
    ds = mk_leader()
    pub = SnapshotPublisher(ds, str(tmp_path / "s.sock"))
    f1 = pub._encode_snapshot(ds.snapshot())
    assert _inner_kind(f1) == snapwire.KIND_FULL

    # Metrics-only change → delta riding the cached full frame.
    ds.endpoint_get("10.1.0.0:8000").metrics.waiting_queue_size = 99
    ds.mark_snapshot_dirty()
    f2 = pub._encode_snapshot(ds.snapshot())
    assert _inner_kind(f2) == snapwire.KIND_DELTA
    assert pub._delta_frame == f2 and pub._frame == f1

    # Attr change breaks blob equality → full again.
    ds.endpoint_get("10.1.0.0:8000").attributes.put("tier", 77)
    ds.mark_snapshot_dirty()
    f3 = pub._encode_snapshot(ds.snapshot())
    assert _inner_kind(f3) == snapwire.KIND_FULL
    assert pub._delta_frame is None

    # Membership change → full.
    ds.endpoint_add_or_update(EndpointMetadata(
        name="new", address="10.1.0.9", port=8009))
    f4 = pub._encode_snapshot(ds.snapshot())
    assert _inner_kind(f4) == snapwire.KIND_FULL


def test_publisher_pickle_wire_opt_out(tmp_path):
    ds = mk_leader()
    pub = SnapshotPublisher(ds, str(tmp_path / "s.sock"), wire="pickle")
    frame = pub._encode_snapshot(ds.snapshot())
    inner = frame[_FRAME_LEN.size:]
    assert not snapwire.is_binary_frame(inner)
    kind, epoch, entries = pickle.loads(inner)
    assert kind == "snap" and epoch == ds.snapshot().epoch
    assert len(entries) == 4


# ---- end-to-end over a unix socket ----------------------------------------


def test_binary_ipc_end_to_end(tmp_path):
    async def body():
        path = str(tmp_path / "snap.sock")
        leader, follower = mk_leader(), Datastore()
        pub = SnapshotPublisher(leader, path, interval_s=0.01)
        await pub.start()
        sub = SnapshotSubscriber(follower, path, retry_s=0.02)
        sub.start()
        try:
            for _ in range(300):
                if follower.endpoint_get("10.1.0.3:8003") is not None:
                    break
                await asyncio.sleep(0.01)
            fep = follower.endpoint_get("10.1.0.3:8003")
            assert fep is not None and fep.metrics.waiting_queue_size == 9
            assert fep.attributes.get("warm") is True
            assert follower.snapshot().epoch == leader.snapshot().epoch
            # Metrics-only scrape → delta frame updates the same proxies.
            leader.endpoint_get("10.1.0.3:8003").metrics.waiting_queue_size = 42
            leader.mark_snapshot_dirty()
            for _ in range(300):
                if fep.metrics.waiting_queue_size == 42:
                    break
                await asyncio.sleep(0.01)
            assert fep.metrics.waiting_queue_size == 42
            # Membership deletion → full frame drops the endpoint.
            leader.endpoint_delete("10.1.0.0:8000")
            for _ in range(300):
                if follower.endpoint_get("10.1.0.0:8000") is None:
                    break
                await asyncio.sleep(0.01)
            assert follower.endpoint_get("10.1.0.0:8000") is None
        finally:
            await sub.stop()
            await pub.stop()

    run(body())


def test_subscriber_survives_corrupt_frame_mid_stream(tmp_path):
    """A hand-rolled publisher sends good → corrupt → newer-epoch good over
    one connection: the subscriber must count + skip the corrupt frame and
    apply the follow-up, without ever reconnecting or crashing."""

    async def body():
        path = str(tmp_path / "snap.sock")
        snap1 = mk_leader().snapshot()
        leader2 = mk_leader(epoch_bump=3)
        leader2.endpoint_get("10.1.0.1:8001").metrics.waiting_queue_size = 77
        leader2.mark_snapshot_dirty()
        snap2 = leader2.snapshot()
        assert snap2.epoch > snap1.epoch
        _, good1 = encode_snapshot(snap1)
        _, good2 = encode_snapshot(snap2)
        bad = _corrupt(good1, "checksum")
        conns = []

        async def on_client(reader, writer):
            conns.append(writer)
            for inner in (good1, bad, good2):
                writer.write(_FRAME_LEN.pack(len(inner)) + inner)
            await writer.drain()

        server = await asyncio.start_unix_server(on_client, path=path)
        follower = Datastore()
        sub = SnapshotSubscriber(follower, path, retry_s=0.02)
        before = _frame_errors("checksum")
        sub.start()
        try:
            for _ in range(300):
                if sub.applied_epoch == snap2.epoch:
                    break
                await asyncio.sleep(0.01)
            assert sub.applied_epoch == snap2.epoch
            assert (follower.endpoint_get("10.1.0.1:8001")
                    .metrics.waiting_queue_size) == 77
            assert _frame_errors("checksum") == before + 1
            # One connection: the corrupt frame caused a skip, NOT a
            # reconnect (the length prefix already re-aligned the stream).
            assert len(conns) == 1
        finally:
            await sub.stop()
            # Python 3.12's wait_closed() waits for accepted connections.
            for w in conns:
                w.close()
            server.close()
            await server.wait_closed()

    run(body())
