"""Disaggregated input-data service: wire protocol, loopback end-to-end
parity with the in-process pipeline, and reconnect-resumes-at-cursor.

All fast (`not slow`): the loopback server runs in-thread on 127.0.0.1 with
tiny 32px JPEG batches — no jit, no process pool.
"""

import socket

import numpy as np
import pytest

from lance_distributed_training_tpu.data import ImageClassificationDecoder
from lance_distributed_training_tpu.data.pipeline import make_train_pipeline
from lance_distributed_training_tpu.service import (
    DataService,
    RemoteLoader,
    ServeConfig,
)
from lance_distributed_training_tpu.service import protocol as P


# -- protocol unit tests ----------------------------------------------------


def test_batch_roundtrip_dtypes():
    batch = {
        "image": np.arange(2 * 4 * 4 * 3, dtype=np.uint8).reshape(2, 4, 4, 3),
        "label": np.array([3, -7], dtype=np.int32),
        "weight": np.array([0.5, 1.0], dtype=np.float32),
        "empty": np.empty((0, 5), dtype=np.float64),
    }
    step, out = P.decode_batch(P.encode_batch(17, batch))
    assert step == 17
    assert set(out) == set(batch)
    for k in batch:
        assert out[k].dtype == batch[k].dtype
        np.testing.assert_array_equal(out[k], batch[k])


def test_batch_decode_rejects_truncation():
    payload = P.encode_batch(0, {"x": np.ones((4, 4), np.float32)})
    with pytest.raises(P.ProtocolError, match="truncated"):
        P.decode_batch(payload[:-8])


def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        P.send_msg(a, P.MSG_ACK, {"step": 5})
        msg_type, msg = P.recv_msg(b)
        assert msg_type == P.MSG_ACK and msg["step"] == 5
        a.close()
        with pytest.raises(ConnectionError):
            P.recv_msg(b)
    finally:
        b.close()


# -- loopback service fixtures ---------------------------------------------


@pytest.fixture()
def service(image_dataset):
    svc = DataService(ServeConfig(
        dataset_path=image_dataset.uri, host="127.0.0.1", port=0,
        image_size=32, queue_depth=2,
    )).start()
    yield svc
    svc.stop()


def _loader(svc, **kw):
    kw.setdefault("connect_retries", 2)
    kw.setdefault("backoff_s", 0.01)
    return RemoteLoader(f"127.0.0.1:{svc.port}", 16, 0, 1, **kw)


# -- end-to-end -------------------------------------------------------------


def test_remote_matches_inprocess_pipeline(image_dataset, service):
    """Acceptance: RemoteLoader batches element-wise identical to the
    DataPipeline's for the same dataset/seed/epoch/shard."""
    local = list(make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        ImageClassificationDecoder(image_size=32),
    ))
    loader = _loader(service)
    assert len(loader) == len(local) == 240 // 16
    remote = list(loader)
    assert len(remote) == len(local)
    for a, b in zip(remote, local):
        np.testing.assert_array_equal(a["image"], b["image"])
        np.testing.assert_array_equal(a["label"], b["label"])


def test_remote_shards_disjoint_and_equal_steps(image_dataset, service):
    streams = []
    for p in range(2):
        loader = RemoteLoader(
            f"127.0.0.1:{service.port}", 16, p, 2,
            connect_retries=2, backoff_s=0.01,
        )
        streams.append([tuple(b["label"].tolist()) for b in loader])
    assert len(streams[0]) == len(streams[1]) > 0  # deadlock invariant
    assert not (set(streams[0]) & set(streams[1]))  # disjoint coverage


def test_remote_shuffle_parity_across_epochs(image_dataset, service):
    """set_epoch reshuffles exactly like the local iterable pipeline."""
    def local(epoch):
        pipe = make_train_pipeline(
            image_dataset, "batch", 16, 0, 1,
            ImageClassificationDecoder(image_size=32),
            shuffle=True, seed=7, epoch=epoch,
        )
        return [tuple(b["label"].tolist()) for b in pipe]

    loader = _loader(service, shuffle=True, seed=7)
    e0 = [tuple(b["label"].tolist()) for b in loader]
    loader.set_epoch(1)
    e1 = [tuple(b["label"].tolist()) for b in loader]
    assert e0 == local(0)
    assert e1 == local(1)
    assert e0 != e1


def test_reconnect_resumes_at_cursor(image_dataset, service):
    """Acceptance: a mid-epoch disconnect resumes from the acked cursor —
    no duplicated, no skipped step."""
    local = list(make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        ImageClassificationDecoder(image_size=32),
    ))
    loader = _loader(service, prefetch=1)
    it = iter(loader)
    got = [next(it), next(it)]
    # Kill the live connection out from under the receiver thread.
    conn = loader._conn
    assert conn is not None
    conn.close()
    got.extend(it)
    assert loader.counters.snapshot().get("svc_reconnects", 0) >= 1
    assert len(got) == len(local)  # nothing skipped, nothing duplicated
    for a, b in zip(got, local):
        np.testing.assert_array_equal(a["label"], b["label"])
        np.testing.assert_array_equal(a["image"], b["image"])


def test_fresh_client_resumes_from_explicit_cursor(image_dataset, service):
    """A brand-new client (crashed trainer) can hand the server a start_step
    and receive exactly the plan's tail."""
    local = list(make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        ImageClassificationDecoder(image_size=32),
    ))
    sock, reply = _loader(service)._connect(start_step=3)
    try:
        assert reply["num_steps"] == len(local) and reply["start_step"] == 3
        steps = []
        while True:
            msg_type, payload = P.recv_msg(sock)
            if msg_type == P.MSG_END:
                break
            assert msg_type == P.MSG_BATCH
            step, batch = P.decode_batch(payload["raw"])
            steps.append(step)
            np.testing.assert_array_equal(batch["label"], local[step]["label"])
    finally:
        sock.close()
    assert steps == [3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14]


def test_device_put_contract(image_dataset, service):
    """Behind the placement plane, the trainer-visible contract is the
    same sharded global jax.Array as every other loader."""
    import jax
    from jax.sharding import PartitionSpec as JP

    from lance_distributed_training_tpu.data import PlacementPlane
    from lance_distributed_training_tpu.parallel import get_mesh

    loader = PlacementPlane(get_mesh()).wrap(_loader(service))
    batch = next(iter(loader))
    assert isinstance(batch["image"], jax.Array)
    assert batch["image"].sharding.spec == JP("data")
    # 16 rows over 8 devices -> shard of 2 per device.
    assert batch["image"].addressable_shards[0].data.shape[0] == 2


def test_early_stop_drains_cleanly(image_dataset, service):
    loader = _loader(service, prefetch=1)
    it = iter(loader)
    next(it)
    it.close()  # must not hang the receiver thread or the server session
    # The server must still serve new clients afterwards.
    assert len(list(_loader(service))) == 240 // 16


# -- batch lineage over the wire --------------------------------------------


def test_lineage_survives_the_wire(image_dataset, service):
    """Acceptance: every received batch carries its birth certificate —
    client-observed batch_seq monotonic per shard, batch_age_ms > 0, and
    the stage timings (decode/queue-wait/wire) land in lineage_* histograms
    on the loader's registry."""
    from lance_distributed_training_tpu.obs import MetricsRegistry

    for p in range(2):
        reg = MetricsRegistry()
        loader = RemoteLoader(
            f"127.0.0.1:{service.port}", 16, p, 2,
            connect_retries=2, backoff_s=0.01, registry=reg,
        )
        n = len(list(loader))
        seqs = [lin["batch_seq"] for lin in loader.recent_lineage]
        assert seqs == list(range(n))  # monotonic, gap-free, per shard
        assert all(
            lin["batch_age_ms"] > 0 for lin in loader.recent_lineage
        )
        # The producer's host-local monotonic stamp never rides the wire.
        assert all(
            "created_mono_ns" not in lin for lin in loader.recent_lineage
        )
        assert loader.last_lineage["batch_seq"] == n - 1
        for name in ("lineage_batch_age_ms", "lineage_wire_ms",
                     "lineage_queue_wait_ms", "lineage_decode_ms"):
            assert reg.get(name).count == n, name


def test_lineage_field_absent_still_interops(image_dataset, service):
    """Mixed-version loopback: a v1 client gets lineage-less frames (the
    server gates the field on the peer's HELLO version) and still receives
    the identical batch stream — the field is optional, not load-bearing."""
    local = list(make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        ImageClassificationDecoder(image_size=32),
    ))
    loader = _loader(service)
    original_hello = loader._hello

    def v1_hello(start_step, probe=False):
        msg = original_hello(start_step, probe)
        msg["version"] = 1  # an old client on the wire
        return msg

    loader._hello = v1_hello
    got = list(loader)
    assert len(got) == len(local)
    for a, b in zip(got, local):
        np.testing.assert_array_equal(a["image"], b["image"])
        np.testing.assert_array_equal(a["label"], b["label"])
    # No lineage was sent, none observed — and that is not an error.
    assert len(loader.recent_lineage) == 0
    assert loader.last_lineage is None


def test_v2_client_downgrades_to_v1_server():
    """New-client -> old-server interop: a v1 server's handshake predates
    range negotiation and rejects any HELLO version but its own. The client
    must re-offer MIN_PROTOCOL_VERSION and succeed — and keep speaking the
    negotiated version on later reconnects instead of re-tripping the
    mismatch on every drop."""
    import threading

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    port = srv.getsockname()[1]
    offered = []

    def strict_v1_server():  # the committed v1 equality check, verbatim
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return  # listener closed: test over
            try:
                _, req = P.recv_msg(conn)
                offered.append(req["version"])
                if req["version"] != 1:
                    P.send_msg(conn, P.MSG_ERROR, {"message": (
                        "protocol version mismatch: server 1, "
                        f"client {req['version']}")})
                else:
                    P.send_msg(conn, P.MSG_HELLO_OK,
                               {"version": 1, "num_steps": 7,
                                "start_step": 0})
            finally:
                conn.close()

    threading.Thread(target=strict_v1_server, daemon=True).start()
    try:
        # connect_retries=1: the downgrade redial is negotiation, not a
        # failed attempt, so even a single-attempt client must get through.
        loader = RemoteLoader(f"127.0.0.1:{port}", 16, 0, 1,
                              connect_retries=1, backoff_s=0.01,
                              timeout_s=5.0)
        assert len(loader) == 7  # probe handshake, post-downgrade
        assert offered == [P.PROTOCOL_VERSION, P.MIN_PROTOCOL_VERSION]
        loader._num_steps = None  # force a fresh probe handshake
        assert len(loader) == 7
        assert offered[-1] == P.MIN_PROTOCOL_VERSION  # sticky downgrade
    finally:
        srv.close()


@pytest.mark.parametrize("version", [1, 2, 3])
def test_cross_version_client_matrix(image_dataset, service, version):
    """The full interop matrix against the current server: a client forced
    to each protocol version must receive the bit-identical batch stream —
    versions change envelope features (lineage, striping), never content —
    with lineage present exactly when the negotiated version carries it."""
    local = list(make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        ImageClassificationDecoder(image_size=32),
    ))
    loader = _loader(service)
    loader._hello_version = version
    got = list(loader)
    assert len(got) == len(local)
    for a, b in zip(got, local):
        np.testing.assert_array_equal(a["image"], b["image"])
        np.testing.assert_array_equal(a["label"], b["label"])
    if version >= P.LINEAGE_MIN_VERSION:
        assert len(loader.recent_lineage) == len(local)
    else:
        assert len(loader.recent_lineage) == 0


def test_hello_ok_start_step_echo_validated():
    """The client must reject a HELLO_OK whose start_step echo disagrees
    with its request — the stream would silently begin at the wrong step
    and every later resume cursor would be off by the difference."""
    import threading

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    port = srv.getsockname()[1]

    def desynced_server():
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            try:
                _, req = P.recv_msg(conn)
                P.send_msg(conn, P.MSG_HELLO_OK, {
                    "version": req["version"], "num_steps": 7,
                    "start_step": int(req["start_step"]) + 1,  # off by one
                })
            finally:
                conn.close()

    threading.Thread(target=desynced_server, daemon=True).start()
    try:
        loader = RemoteLoader(f"127.0.0.1:{port}", 16, 0, 1,
                              connect_retries=1, backoff_s=0.01,
                              timeout_s=5.0)
        with pytest.raises(P.ProtocolError, match="start_step"):
            len(loader)
    finally:
        srv.close()


def test_hello_ok_garbage_start_step_echo_is_protocol_error():
    """A non-integer echo must be the diagnosable ProtocolError, never a
    raw ValueError escaping the connect path (the handler-killing-repr
    class hello_malformed fixes server-side)."""
    import threading

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)
    port = srv.getsockname()[1]

    def garbage_server():
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            try:
                _, req = P.recv_msg(conn)
                P.send_msg(conn, P.MSG_HELLO_OK, {
                    "version": req["version"], "num_steps": 7,
                    "start_step": "zero",
                })
            finally:
                conn.close()

    threading.Thread(target=garbage_server, daemon=True).start()
    try:
        loader = RemoteLoader(f"127.0.0.1:{port}", 16, 0, 1,
                              connect_retries=1, backoff_s=0.01,
                              timeout_s=5.0)
        with pytest.raises(P.ProtocolError, match="start_step"):
            len(loader)
    finally:
        srv.close()


@pytest.mark.parametrize("field,bad", [
    ("batch_size", "16"),
    ("process_index", "0"),
    ("process_count", True),  # JSON true is not an integer count
    ("seed", "7"),
    ("epoch", [1]),
    ("start_step", "zero"),
    ("stripe_index", 1.5),
    ("stripe_count", "4"),
    ("image_size", "abc"),
    ("sampler_type", 3),
    ("client_id", 9),
    ("task_type", 7),
    ("dataset_fingerprint", 123),
    ("shuffle", "yes"),
    ("probe", 1),
    ("device_decode", "true"),
    ("columns", "image"),
])
def test_malformed_hello_field_answers_skew_style_error(
    image_dataset, service, field, bad
):
    """Satellite: a HELLO field of the wrong TYPE must be rejected with a
    diagnosable MSG_ERROR at connect time — before this, a non-numeric
    image_size reached ``int(size)`` inside decode_config_skew and killed
    the handler thread with a ValueError repr."""
    sock = socket.create_connection(("127.0.0.1", service.port), timeout=5)
    try:
        req = P.hello(batch_size=16, process_index=0, process_count=1)
        req[field] = bad
        P.send_msg(sock, P.MSG_HELLO, req)
        msg_type, msg = P.recv_msg(sock)
        assert msg_type == P.MSG_ERROR
        assert "malformed HELLO field" in msg["message"]
        assert repr(field) in msg["message"]
    finally:
        sock.close()
    # The handler thread answered and moved on — the server still serves
    # (a probe handshake is the cheap liveness check).
    assert len(_loader(service)) == 240 // 16
    assert service.counters.snapshot().get(
        "svc_proto_malformed_hello", 0
    ) >= 1


def test_well_typed_hello_passes_malformed_check():
    """The validator accepts every shape our own constructors emit —
    including all-None optional fields and the v1 bare dict."""
    assert P.hello_malformed(P.hello(
        batch_size=16, process_index=0, process_count=1,
    )) is None
    assert P.hello_malformed(P.hello(
        batch_size=16, process_index=0, process_count=1,
        stripe_index=1, stripe_count=4, task_type="classification",
        image_size=224, device_decode=True, dataset_fingerprint="ab" * 16,
        columns=["image", "label"],
    )) is None
    assert P.hello_malformed({"version": 1, "batch_size": 8}) is None


def test_v1_server_hello_ok_accepted():
    """Range check on the server's echoed version: v1 is in-range, an
    out-of-range or garbage version is a hard skew."""
    assert P.version_supported(1) and P.version_supported(P.PROTOCOL_VERSION)
    assert not P.version_supported(0)
    assert not P.version_supported(P.PROTOCOL_VERSION + 1)
    assert not P.version_supported("2")
    assert not P.version_supported(None)
    assert not P.version_supported(True)  # JSON true: bool is an int subtype


def test_encode_batch_lineage_roundtrip_and_v1_compat():
    batch = {"x": np.arange(6, dtype=np.float32).reshape(2, 3)}
    lin = {"batch_seq": 5, "created_ns": 123, "decode_ms": 1.5}
    payload = P.encode_batch(5, batch, lineage=lin)
    # v2 decoder sees the lineage...
    step, out, got = P.decode_batch(payload, with_lineage=True)
    assert step == 5 and got == lin
    np.testing.assert_array_equal(out["x"], batch["x"])
    # ...a v1-style decode (no with_lineage) ignores the extra meta key...
    step, out = P.decode_batch(payload)
    assert step == 5
    np.testing.assert_array_equal(out["x"], batch["x"])
    # ...and a lineage-less frame reads as None, not an error.
    assert P.decode_batch(P.encode_batch(5, batch), with_lineage=True)[2] is None


def test_service_metrics_endpoint_serves_lineage_histograms(image_dataset):
    """Acceptance: loopback service + 2-shard client pass, then /metrics
    serves Prometheus text with _bucket/_sum/_count series for wire_ms and
    batch_age_ms, and /healthz reports liveness."""
    import json as _json
    import urllib.request

    svc = DataService(ServeConfig(
        dataset_path=image_dataset.uri, host="127.0.0.1", port=0,
        image_size=32, metrics_port=0,
    )).start()
    try:
        for p in range(2):
            list(RemoteLoader(
                f"127.0.0.1:{svc.port}", 16, p, 2,
                connect_retries=2, backoff_s=0.01,
            ))
        base = f"http://127.0.0.1:{svc.metrics_port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        for series in (
            "lineage_wire_ms_bucket", "lineage_wire_ms_sum",
            "lineage_wire_ms_count", "lineage_batch_age_ms_bucket",
            "lineage_batch_age_ms_sum", "lineage_batch_age_ms_count",
            "svc_decode_ms_bucket", "svc_queue_wait_ms_bucket",
            "svc_batches_sent",
        ):
            assert series in text, f"missing {series}"
        health = _json.loads(
            urllib.request.urlopen(f"{base}/healthz").read()
        )
        assert health["status"] == "ok"
        assert "active_clients" in health and "sessions" in health
    finally:
        svc.stop()


# -- handshake failure modes ------------------------------------------------


def test_version_mismatch_rejected(image_dataset, service):
    sock = socket.create_connection(("127.0.0.1", service.port), timeout=5)
    try:
        bad = P.hello(batch_size=16, process_index=0, process_count=1)
        bad["version"] = 999
        P.send_msg(sock, P.MSG_HELLO, bad)
        msg_type, msg = P.recv_msg(sock)
        assert msg_type == P.MSG_ERROR
        assert "version" in msg["message"]
    finally:
        sock.close()


def test_hello_ok_echoes_negotiated_version(image_dataset, service):
    """The echo must be min(server, client), not the server's ceiling: a
    future vN+1 server answering a vN client with N+1 would trip the
    client's range check on a connection the server just accepted."""
    sock = socket.create_connection(("127.0.0.1", service.port), timeout=5)
    try:
        req = P.hello(batch_size=16, process_index=0, process_count=1,
                      probe=True)
        req["version"] = 1  # an old client on the wire
        P.send_msg(sock, P.MSG_HELLO, req)
        msg_type, msg = P.recv_msg(sock)
        assert msg_type == P.MSG_HELLO_OK
        assert msg["version"] == 1
    finally:
        sock.close()


def test_decode_config_skew_rejected(image_dataset, service):
    """A trainer expecting a different image_size than the server decodes
    must be refused at connect time, never trained at the wrong resolution."""
    loader = _loader(service, image_size=64, task_type="classification")
    with pytest.raises(P.ProtocolError, match="skew"):
        len(loader)
    # Matching declaration connects fine.
    ok = _loader(service, image_size=32, task_type="classification")
    assert len(ok) == 240 // 16


def test_full_sampler_multiprocess_refused_remotely(image_dataset, service):
    """Parity with make_train_pipeline's refusal: 'full' is not DP-aware."""
    loader = RemoteLoader(
        f"127.0.0.1:{service.port}", 16, 0, 2, sampler_type="full",
        connect_retries=1, backoff_s=0.01,
    )
    with pytest.raises((P.ProtocolError, RuntimeError)):
        list(loader)


def test_client_drop_with_empty_queue_frees_session(image_dataset, service):
    """A client that handshakes and immediately vanishes (empty per-client
    queue) must not strand the server's sender thread or leak the session."""
    import time as _time

    sock, _ = _loader(service)._connect(0)
    sock.close()  # drop before consuming anything
    deadline = _time.monotonic() + 10
    while _time.monotonic() < deadline:
        with service._sessions_lock:
            if not service._sessions:
                break
        _time.sleep(0.05)
    with service._sessions_lock:
        assert not service._sessions  # session reaped, gauge accurate
    # Server still healthy for the next client.
    assert len(list(_loader(service))) == 240 // 16


def test_recv_deadline_bounds_whole_frame_not_each_byte():
    """A byte-dripping peer must not extend the handshake window: the
    deadline bounds the entire frame read, while each individual recv
    would otherwise reset a plain settimeout."""
    import time as _time

    a, b = socket.socketpair()
    try:
        # A valid header promising 8 payload bytes, then... one byte only.
        a.sendall(P._HEADER.pack(8, P.MSG_HELLO))
        a.sendall(b"x")
        t0 = _time.monotonic()
        with pytest.raises((socket.timeout, TimeoutError)):
            P.recv_msg(b, deadline=_time.monotonic() + 0.3)
        assert _time.monotonic() - t0 < 5.0  # bounded, not pinned
    finally:
        a.close()
        b.close()


def test_silent_peer_dropped_after_handshake_timeout(image_dataset):
    """A peer that connects and never sends HELLO (scanner, wedged client)
    must be dropped at handshake_timeout_s instead of pinning its handler
    thread forever (the ldt check LDT203 invariant, exercised live)."""
    import time as _time

    svc = DataService(ServeConfig(
        dataset_path=image_dataset.uri, host="127.0.0.1", port=0,
        image_size=32, handshake_timeout_s=0.3,
    )).start()
    try:
        silent = socket.create_connection(("127.0.0.1", svc.port))
        try:
            # The session must first register (accept happened)...
            deadline = _time.monotonic() + 10
            while _time.monotonic() < deadline:
                with svc._sessions_lock:
                    if svc._sessions:
                        break
                _time.sleep(0.01)
            # ...then be reaped when the HELLO deadline expires.
            while _time.monotonic() < deadline:
                with svc._sessions_lock:
                    if not svc._sessions:
                        break
                _time.sleep(0.05)
            with svc._sessions_lock:
                assert not svc._sessions  # reaped by the deadline
            # The server stayed healthy for a real client afterwards.
            assert len(list(_loader(svc))) == 240 // 16
        finally:
            silent.close()
    finally:
        svc.stop()


def test_bad_shard_rejected(image_dataset, service):
    loader = RemoteLoader(
        f"127.0.0.1:{service.port}", 16, 3, 2,  # process 3 of 2
        connect_retries=1, backoff_s=0.01,
    )
    with pytest.raises((P.ProtocolError, RuntimeError)):
        list(loader)


def test_unreachable_service_raises_after_backoff():
    # Reserve a port and close it so nothing listens there.
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    loader = RemoteLoader(
        f"127.0.0.1:{port}", 16, 0, 1, connect_retries=2, backoff_s=0.01,
    )
    with pytest.raises(ConnectionError, match="unreachable"):
        len(loader)


def test_bad_address_rejected_eagerly():
    with pytest.raises(ValueError, match="host:port"):
        RemoteLoader("nonsense", 16, 0, 1)


def test_ipv6_address_parsed_not_mangled():
    """Bracketed IPv6 must parse as the literal host — the old bare
    rpartition(":") yielded host '[::1' and dialed garbage."""
    loader = RemoteLoader("[::1]:8476", 16, 0, 1)
    assert (loader.host, loader.port) == ("::1", 8476)
    # Unbracketed multi-colon literals are ambiguous, not silently split.
    with pytest.raises(ValueError, match="bracket"):
        RemoteLoader("::1:8476", 16, 0, 1)


# -- trainer config validation ---------------------------------------------


def test_train_config_service_combos():
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    base = dict(dataset_path="/nonexistent", data_service_addr="h:1",
                no_wandb=True)
    with pytest.raises(ValueError, match="iterable columnar"):
        train(TrainConfig(**base, loader_style="map"))
    with pytest.raises(ValueError, match="iterable columnar"):
        train(TrainConfig(**base, data_format="folder"))
    with pytest.raises(ValueError, match="filter"):
        train(TrainConfig(**base, filter="label < 5"))


def test_train_requires_local_dataset_for_eval():
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    with pytest.raises(ValueError, match="eval"):
        train(TrainConfig(
            dataset_path="/nonexistent/ds", data_service_addr="h:1",
            no_wandb=True, eval_at_end=True,
        ))


@pytest.mark.slow
def test_train_through_service(image_dataset):
    """Full trainer integration: train() with data_service_addr streams every
    batch through the loopback service (resnet18 compile — slow tier)."""
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    svc = DataService(ServeConfig(
        dataset_path=image_dataset.uri, host="127.0.0.1", port=0,
        image_size=32,
    )).start()
    try:
        results = train(TrainConfig(
            dataset_path=image_dataset.uri,
            data_service_addr=f"127.0.0.1:{svc.port}",
            num_classes=10, model_name="resnet18", image_size=32,
            batch_size=16, epochs=1, no_wandb=True, eval_at_end=False,
            metrics_port=0,  # ephemeral trainer-side /metrics exporter
        ))
        assert np.isfinite(results["loss"])
        assert results["steps"] == 240 // 16
        assert svc.counters.snapshot()["svc_batches_sent"] >= results["steps"]
    finally:
        svc.stop()


def test_serve_cli_parser_roundtrip():
    from lance_distributed_training_tpu.cli import build_serve_parser

    args = build_serve_parser().parse_args([
        "--dataset_path", "/d", "--port", "0", "--num_workers", "3",
        "--queue_depth", "8", "--image_size", "64",
    ])
    assert args.port == 0 and args.num_workers == 3
    assert args.queue_depth == 8 and args.image_size == 64
    assert args.metrics_port is None  # exporter off by default
    args = build_serve_parser().parse_args(
        ["--dataset_path", "/d", "--metrics_port", "9464"]
    )
    assert args.metrics_port == 9464


def test_train_cli_data_service_flag(monkeypatch):
    import lance_distributed_training_tpu.cli as cli

    captured = {}
    monkeypatch.setattr(
        cli, "train", lambda config: captured.update(config=config) or {}
    )
    cli.main(["train", "--dataset_path", "/d", "--no_wandb",
              "--data_service", "cpu-host:8476",
              "--metrics_port", "9465"])
    assert captured["config"].data_service_addr == "cpu-host:8476"
    assert captured["config"].metrics_port == 9465
