"""Zero-copy batch plane (data/buffers.py + the layers threaded through it).

Five invariant families from the r6 acceptance criteria:

* BufferPool lease/return/recycle semantics — incl. the refcount guard
  that makes eager release safe next to jax's CPU zero-copy aliasing;
* concurrent lease safety (no two live leases alias one page);
* shm ring slot lifecycle — write/read parity, resize, token cycling,
  worker-crash cleanup, no leaked ``/dev/shm`` segments after shutdown or
  abrupt abandonment;
* recv_into framing parity — ``FrameReader`` and the vectored
  ``send_batch_frame`` move byte-identical frames vs the legacy
  reader/encoder;
* decode-into-pool equality — the service's bit-identical-batches
  guarantee extends to the buffer plane (pooled vs fresh decode, shm vs
  pickle worker transport).
"""

import multiprocessing as mp
import os
import socket
import sys
import threading

import numpy as np
import pytest

from lance_distributed_training_tpu.data.buffers import (
    BufferPool,
    ShmRing,
    ShmSlotWriter,
    shm_available,
)

pytestmark = pytest.mark.fast


def _shm_segments():
    try:
        return [f for f in os.listdir("/dev/shm") if f.startswith("ldtshm")]
    except FileNotFoundError:  # non-tmpfs platform: covered by shm_available
        return []


_FOREIGN: set = set()


@pytest.fixture(autouse=True)
def _note_foreign_segments():
    """Segments that exist when a test starts belong to another process's
    ring (another xdist worker's test that trains with shm workers): not
    this test's to reap, and not its leftovers. Seen in a whole run under
    six workers: one foreign ring outlived three tests here (PR 30)."""
    _FOREIGN.clear()
    _FOREIGN.update(_shm_segments())
    yield


def _shm_leftovers():
    return [f for f in _shm_segments() if f not in _FOREIGN]


# -- BufferPool -------------------------------------------------------------


def test_lease_release_recycle():
    pool = BufferPool()
    a = pool.lease((4, 8), np.uint8)
    first_id = id(a)
    a[:] = 7
    assert pool.stats()["outstanding"] == 1
    pool.release(a)
    del a
    b = pool.lease((4, 8), np.uint8)
    assert id(b) == first_id  # recycled, not refaulted
    assert pool.stats() == {"outstanding": 1, "pending": 0, "free": 0}


def test_release_deferred_while_externally_referenced():
    """The refcount guard: a released page someone still holds (a live
    batch dict, a jax CPU zero-copy alias) must NOT be handed out again."""
    pool = BufferPool()
    a = pool.lease((16,), np.float32)
    holder = {"x": a}  # external reference outliving the release
    pool.release(a)
    del a
    b = pool.lease((16,), np.float32)
    assert id(b) != id(holder["x"])  # deferred: no alias handed out
    assert pool.stats()["pending"] == 1
    del holder
    pool.release(b)
    del b
    c = pool.lease((16,), np.float32)
    d = pool.lease((16,), np.float32)
    # Both earlier pages eventually recycled once truly free.
    assert pool.stats()["outstanding"] == 2
    assert (
        pool.stats()["pending"] + pool.stats()["free"] == 0
    )
    del c, d


def test_dropped_lease_is_garbage_not_a_leak():
    """A leased page dropped WITHOUT release (early generator close, a
    crashed consumer, a skipped teardown drain) must degrade to ordinary
    GC — the pool holds only a weak reference, so outstanding drains to
    zero and memory is returned, just without the recycle."""
    import gc

    pool = BufferPool()
    for _ in range(5):
        pool.lease((1024,), np.uint8)  # dropped immediately, never released
    gc.collect()
    assert pool.stats()["outstanding"] == 0
    # And the pool still works normally afterwards.
    a = pool.lease((1024,), np.uint8)
    assert pool.release(a) is True


def test_release_foreign_and_double_release_are_noops():
    pool = BufferPool()
    foreign = np.zeros(8)
    assert pool.release(foreign) is False
    a = pool.lease((8,), np.float64)
    assert pool.release(a) is True
    assert pool.release(a) is False  # double release: ignored
    assert pool.release_batch({"x": np.ones(3), "y": None}) == 0


def test_free_list_cap_evicts():
    pool = BufferPool(max_free_per_key=1)
    a, b = pool.lease((8,), np.uint8), pool.lease((8,), np.uint8)
    pool.release(a), pool.release(b)
    del a, b
    pool.lease((4,), np.uint8)  # trigger a sweep
    assert pool.stats()["free"] == 1  # second page evicted at the cap


def test_keying_by_shape_and_dtype():
    pool = BufferPool()
    a = pool.lease((8,), np.uint8)
    pool.release(a)
    a_id = id(a)
    del a
    b = pool.lease((8,), np.int32)  # same shape, different dtype: miss
    assert id(b) != a_id
    c = pool.lease((8,), np.uint8)  # exact key: hit
    assert id(c) == a_id


def test_concurrent_lease_safety():
    """No two concurrently-live leases may alias one page, under threads."""
    pool = BufferPool()
    errors = []
    live_lock = threading.Lock()
    live = set()

    def worker(seed):
        rng = np.random.default_rng(seed)
        for i in range(50):
            arr = pool.lease((64,), np.int64)
            with live_lock:
                if id(arr) in live:
                    errors.append("aliased live lease")
                    return
                live.add(id(arr))
            fill = int(rng.integers(0, 2**31))
            arr[:] = fill
            if not (arr == fill).all():
                errors.append("torn write")
            with live_lock:
                live.discard(id(arr))
            pool.release(arr)
            del arr

    threads = [
        threading.Thread(target=worker, args=(s,), daemon=True)
        for s in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors
    assert pool.stats()["outstanding"] == 0


# -- shm ring ---------------------------------------------------------------


needs_shm = pytest.mark.skipif(
    not shm_available(), reason="POSIX shared memory unavailable"
)


def _ring():
    return ShmRing(2, mp.get_context("spawn"), acquire_timeout_s=2.0)


@needs_shm
def test_shm_write_read_roundtrip_and_token_cycle():
    ring = _ring()
    writer = ShmSlotWriter(*ring.writer_args())
    try:
        rng = np.random.default_rng(0)
        for step in range(6):  # 3 full cycles over 2 slots
            batch = {
                "image": rng.integers(0, 255, (4, 8, 8, 3)).astype(np.uint8),
                "label": rng.integers(0, 10, 4).astype(np.int32),
            }
            desc = writer.write_batch(batch)
            assert desc is not None
            out = ring.read_batch(desc)
            assert set(out) == {"image", "label"}
            assert np.array_equal(out["image"], batch["image"])
            assert np.array_equal(out["label"], batch["label"])
    finally:
        writer.close()
        ring.cleanup()
    assert not _shm_leftovers()


@needs_shm
def test_shm_slot_resize_grows_and_preserves_content():
    ring = _ring()
    writer = ShmSlotWriter(*ring.writer_args())
    try:
        small = {"x": np.arange(16, dtype=np.int64)}
        big = {"x": np.arange(65536, dtype=np.int64)}
        d1 = writer.write_batch(small)
        assert np.array_equal(ring.read_batch(d1)["x"], small["x"])
        d2 = writer.write_batch(big)  # forces a resize of some slot
        assert d2["size"] >= big["x"].nbytes
        assert np.array_equal(ring.read_batch(d2)["x"], big["x"])
        d3 = writer.write_batch(small)  # resized slot still serves small
        assert np.array_equal(ring.read_batch(d3)["x"], small["x"])
    finally:
        writer.close()
        ring.cleanup()
    assert not _shm_leftovers()


@needs_shm
def test_shm_acquire_timeout_falls_back():
    """All tokens held + timeout ⇒ write_batch returns None (the pickle
    fallback), never a deadlock."""
    ring = ShmRing(1, mp.get_context("spawn"), acquire_timeout_s=0.3)
    writer = ShmSlotWriter(*ring.writer_args())
    try:
        d = writer.write_batch({"x": np.zeros(4)})
        assert d is not None  # token 0 now held (no read_batch ack)
        assert writer.write_batch({"x": np.zeros(4)}) is None
        ring.release_token(d)  # ack returns the token
        assert writer.write_batch({"x": np.zeros(4)}) is not None
    finally:
        writer.close()
        ring.cleanup()
    assert not _shm_leftovers()


@needs_shm
def test_shm_alloc_failure_falls_back_and_slot_recovers(monkeypatch):
    """An OSError inside the slot write (e.g. ENOSPC on an undersized
    /dev/shm) must degrade to the pickle fallback (None) — never kill the
    epoch — and must requeue a RESET token so the slot stays usable."""
    ring = ShmRing(1, mp.get_context("spawn"), acquire_timeout_s=2.0)
    writer = ShmSlotWriter(*ring.writer_args())
    try:
        batch = {"x": np.arange(64, dtype=np.int64)}
        real_ensure = ShmSlotWriter._ensure
        monkeypatch.setattr(
            ShmSlotWriter, "_ensure",
            lambda self, *a: (_ for _ in ()).throw(OSError(28, "ENOSPC")),
        )
        assert writer.write_batch(batch) is None  # fallback, not a raise
        monkeypatch.setattr(ShmSlotWriter, "_ensure", real_ensure)
        desc = writer.write_batch(batch)  # reset token: slot still works
        assert desc is not None
        assert np.array_equal(ring.read_batch(desc)["x"], batch["x"])
    finally:
        writer.close()
        ring.cleanup()
    assert not _shm_leftovers()


@needs_shm
def test_shm_non_array_batch_refuses():
    ring = _ring()
    writer = ShmSlotWriter(*ring.writer_args())
    try:
        assert writer.write_batch({"x": np.zeros(4), "bad": "str"}) is None
    finally:
        writer.close()
        ring.cleanup()


@needs_shm
def test_shm_cleanup_reaps_crashed_writer_segments():
    """Segments created by a (now dead) worker are unlinked by the parent's
    cleanup — deterministic names make the reap crash-proof."""
    ring = _ring()
    writer = ShmSlotWriter(*ring.writer_args())
    desc = writer.write_batch({"x": np.zeros(1024)})
    assert desc is not None
    writer.close()  # "crash": the writer vanishes without returning tokens
    assert _shm_leftovers()  # segment exists while the ring is live
    ring.cleanup()
    assert not _shm_leftovers()
    ring.cleanup()  # idempotent
    with pytest.raises(RuntimeError):
        ring.read_batch(desc)


@needs_shm
def test_shm_pool_copyout_uses_leases():
    ring = _ring()
    writer = ShmSlotWriter(*ring.writer_args())
    pool = BufferPool()
    try:
        batch = {"x": np.arange(32, dtype=np.float32)}
        out1 = ring.read_batch(writer.write_batch(batch), pool)
        assert np.array_equal(out1["x"], batch["x"])
        first = id(out1["x"])
        pool.release_batch(out1)
        del out1
        out2 = ring.read_batch(writer.write_batch(batch), pool)
        assert id(out2["x"]) == first  # recycled pool page
    finally:
        writer.close()
        ring.cleanup()


# -- WorkerPool end-to-end: shm vs pickle bit-parity + leak-free shutdown ---


@pytest.fixture(scope="module")
def wp_dataset(tmp_path_factory):
    import pyarrow as pa

    from lance_distributed_training_tpu.data import write_dataset
    from tests.conftest import make_jpeg

    rng = np.random.default_rng(3)
    table = pa.table({
        "image": pa.array([make_jpeg(rng) for _ in range(64)], pa.binary()),
        "label": pa.array(rng.integers(0, 10, 64), pa.int64()),
    })
    uri = tmp_path_factory.mktemp("zc") / "ds"
    return write_dataset(table, uri, mode="create", max_rows_per_file=32)


@needs_shm
@pytest.mark.slow
def test_worker_pool_shm_matches_pickle_and_leaks_nothing(wp_dataset):
    from lance_distributed_training_tpu.data.decode import (
        ImageClassificationDecoder,
    )
    from lance_distributed_training_tpu.data.workers import (
        WorkerPool,
        columnar_spec,
    )

    decode = ImageClassificationDecoder(image_size=32)
    plan = [np.arange(i * 16, (i + 1) * 16) for i in range(4)]
    with WorkerPool(columnar_spec(wp_dataset.uri), decode, 2,
                    transport="pickle") as wp:
        assert wp.transport == "pickle"
        pickled = list(wp.imap(plan))
    pool = BufferPool()
    wp = WorkerPool(columnar_spec(wp_dataset.uri), decode, 2,
                    transport="shm", buffer_pool=pool)
    assert wp.transport == "shm"
    shm_batches = list(wp.imap(plan))
    for a, b in zip(pickled, shm_batches):
        assert np.array_equal(a["image"], b["image"])
        assert np.array_equal(a["label"], b["label"])
    # Abrupt abandonment mid-epoch: drop the iterator after one batch —
    # slots must be reclaimed (or cleanup must reap them) either way.
    it = wp.imap(plan)
    next(it)
    it.close()
    wp.shutdown()
    assert not _shm_leftovers()


# -- wire framing parity ----------------------------------------------------


def _pipe():
    return socket.socketpair()


def test_frame_reader_parity_with_recv_msg():
    """FrameReader and recv_msg decode the SAME byte stream identically —
    control frames, batch frames, interleaved."""
    from lance_distributed_training_tpu.service import protocol as P

    rng = np.random.default_rng(0)
    batch = {
        "image": rng.integers(0, 255, (4, 8, 8, 3)).astype(np.uint8),
        "label": rng.integers(0, 10, 4).astype(np.int32),
    }
    frames = []
    frames.append((P.MSG_HELLO_OK, {"version": 2, "num_steps": 3}))
    frames.append((P.MSG_BATCH, P.encode_batch(0, batch)))
    frames.append((P.MSG_BATCH, P.encode_batch(1, batch, {"batch_seq": 1})))
    frames.append((P.MSG_END, {}))

    def send_all(sock):
        for msg_type, payload in frames:
            if msg_type == P.MSG_BATCH:
                P.send_frame(sock, msg_type, payload)
            else:
                P.send_msg(sock, msg_type, payload)

    results = []
    for use_reader in (False, True):
        a, b = _pipe()
        t = threading.Thread(target=send_all, args=(a,), daemon=True)
        t.start()
        reader = P.FrameReader(b)
        got = []
        for _ in frames:
            if use_reader:
                msg_type, payload = reader.recv_msg()
            else:
                msg_type, payload = P.recv_msg(b)
            if msg_type == P.MSG_BATCH:
                got.append((msg_type, bytes(payload["raw"])))
            else:
                got.append((msg_type, payload))
        t.join(timeout=10)
        a.close(), b.close()
        results.append(got)
    legacy, pooled = results
    assert len(legacy) == len(pooled) == len(frames)
    for (t1, p1), (t2, p2) in zip(legacy, pooled):
        assert t1 == t2
        assert p1 == p2  # byte-for-byte identical frames


def test_vectored_send_wire_parity():
    """send_batch_frame over tensor_views puts the EXACT bytes of the
    legacy encode_batch+send_frame on the wire."""
    from lance_distributed_training_tpu.service import protocol as P

    rng = np.random.default_rng(1)
    batch = {
        "a": rng.integers(0, 255, (3, 5, 7)).astype(np.uint8),
        "b": rng.random((2, 9)).astype(np.float32),
        "empty": np.zeros((0, 4), np.int64),  # zero-size tensor edge
    }
    legacy = P.encode_batch(7, batch, {"batch_seq": 7})
    metas, views = P.tensor_views(batch)
    meta = P.encode_batch_meta(7, metas, {"batch_seq": 7})

    a, b = _pipe()
    t = threading.Thread(
        target=lambda: (P.send_frame(a, P.MSG_BATCH, legacy),
                        P.send_batch_frame(a, meta, views)),
        daemon=True,
    )
    t.start()
    _, p1 = P.recv_frame(b)
    _, p2 = P.recv_frame(b)
    t.join(timeout=10)
    a.close(), b.close()
    assert bytes(p1) == bytes(p2)
    s1, o1 = P.decode_batch(p1)
    pool = BufferPool()
    s2, o2 = P.decode_batch(p2, pool=pool)
    assert s1 == s2 == 7
    for k in o1:
        assert np.array_equal(o1[k], o2[k])


def test_frame_reader_grows_and_rejects_oversize():
    from lance_distributed_training_tpu.service import protocol as P

    a, b = _pipe()
    reader = P.FrameReader(b, initial_capacity=16)
    big = {"blob": "x" * 4096}
    t = threading.Thread(target=P.send_msg, args=(a, P.MSG_ACK, big),
                         daemon=True)
    t.start()
    msg_type, payload = reader.recv_msg()
    t.join(timeout=10)
    assert msg_type == P.MSG_ACK and payload == big
    # Oversize header: rejected before any allocation.
    a.sendall(b"\xff\xff\xff\xff" + bytes([P.MSG_ACK]))
    with pytest.raises(P.ProtocolError):
        reader.recv_msg()
    a.close(), b.close()


# -- decode-into-pool equality ----------------------------------------------


def test_decode_into_pool_bit_identical(wp_dataset):
    """Pooled vs fresh-alloc decode produce equal tensors — the service's
    bit-identical-batches guarantee extends to the buffer plane."""
    from lance_distributed_training_tpu.data.decode import (
        ImageClassificationDecoder,
    )

    table = wp_dataset.read_range(0, 0, 24)
    pool = BufferPool()
    plain = ImageClassificationDecoder(image_size=32)(table)
    pooled_dec = ImageClassificationDecoder(image_size=32, buffer_pool=pool)
    pooled = pooled_dec(table)
    assert np.array_equal(plain["image"], pooled["image"])
    assert np.array_equal(plain["label"], pooled["label"])
    # Release + redecode: recycled page, still identical.
    pool.release_batch(pooled)
    del pooled
    again = pooled_dec(table)
    assert np.array_equal(plain["image"], again["image"])


def test_decoder_pickles_without_pool(wp_dataset):
    """Crossing the process boundary must drop the (lock-holding) pool —
    workers re-bind their own."""
    import pickle

    from lance_distributed_training_tpu.data.decode import (
        ImageClassificationDecoder,
    )

    dec = ImageClassificationDecoder(image_size=32, buffer_pool=BufferPool())
    clone = pickle.loads(pickle.dumps(dec))
    assert clone.buffer_pool is None
    table = wp_dataset.read_range(0, 0, 8)
    a, b = dec(table), clone(table)
    assert np.array_equal(a["image"], b["image"])


def test_pipeline_releases_host_batches(wp_dataset):
    """DataPipeline + pool: pages recycle across host-batch yields (the
    loader-only bench shape) — hit counter climbs, outstanding drains."""
    from lance_distributed_training_tpu.data.decode import (
        ImageClassificationDecoder,
    )
    from lance_distributed_training_tpu.data.pipeline import (
        make_train_pipeline,
    )
    from lance_distributed_training_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    pool = BufferPool(registry=reg)
    decode = ImageClassificationDecoder(image_size=32, buffer_pool=pool)
    pipe = make_train_pipeline(
        wp_dataset, "batch", 16, 0, 1, decode, buffer_pool=pool
    )
    for batch in pipe:
        assert batch["image"].shape == (16, 32, 32, 3)
        del batch
    # Second pass rides recycled pages.
    for batch in pipe:
        del batch
    assert reg.counter("bufpool_hit_total").value > 0
    assert pool.stats()["outstanding"] == 0
