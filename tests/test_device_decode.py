"""Device-side decode (the entropy split): kernel correctness, host-vs-
device parity across all five loaders, bit-identical repeats, resume
cursors, degraded paths, and the split's autotune surface.

Parity contract: the device arm (coefficient pages + jitted kernel) must
match the host arm (``--no_device_decode``: native libjpeg decode) within
the pinned :data:`~lance_distributed_training_tpu.ops.jpeg_device.
HOST_PARITY_MAX_ABS_DIFF` envelope on the canonical corpus (sources below
the DCT draft threshold). The device arm itself must be bit-deterministic:
same coefficient pages in, same bytes out, every run.
"""

import io

import numpy as np
import pyarrow as pa
import pytest

import jax

from lance_distributed_training_tpu.data.decode import (
    ImageClassificationDecoder,
    decoder_for_task,
)
from lance_distributed_training_tpu.data.device_decode import (
    CoeffImageDecoder,
)
from lance_distributed_training_tpu.data.pipeline import (
    MapStylePipeline,
    make_train_pipeline,
)
from lance_distributed_training_tpu.native import jpeg as native_jpeg
from lance_distributed_training_tpu.ops.jpeg_device import (
    COEFF_KEYS,
    HOST_PARITY_MAX_ABS_DIFF,
    decode_coeff_batch,
    is_coeff_batch,
    make_batch_transform,
)

pytestmark = pytest.mark.skipif(
    not native_jpeg.native_available(),
    reason="native coefficient extractor not built in this environment",
)

SIZE = 32  # decode target; conftest's image_dataset holds 32px sources


def _device_images(coeff_batch, out_size=SIZE) -> np.ndarray:
    return np.asarray(decode_coeff_batch(
        coeff_batch["jpeg_coef_y"], coeff_batch["jpeg_coef_cb"],
        coeff_batch["jpeg_coef_cr"], coeff_batch["jpeg_quant"],
        coeff_batch["jpeg_geom"], out_size=out_size,
    ))


def _assert_parity(dev: np.ndarray, host: np.ndarray, tol=None):
    tol = HOST_PARITY_MAX_ABS_DIFF if tol is None else tol
    diff = np.abs(dev.astype(np.int32) - host.astype(np.int32))
    assert diff.max() <= tol, (
        f"host-vs-device parity broke the pinned envelope: max abs diff "
        f"{diff.max()} > {tol}"
    )


def _smooth_jpeg(w, h, *, gray=False, quality=85, subsampling=2) -> bytes:
    from PIL import Image

    yy, xx = np.mgrid[0:h, 0:w]
    arr = np.stack([
        xx * 255 / max(w - 1, 1),
        yy * 255 / max(h - 1, 1),
        (np.sin(xx / 7.0) + np.cos(yy / 5.0) + 2) / 4 * 255,
    ], axis=-1).astype(np.uint8)
    img = Image.fromarray(arr)
    if gray:
        img = img.convert("L")
    buf = io.BytesIO()
    img.save(buf, format="JPEG", quality=quality, subsampling=subsampling)
    return buf.getvalue()


# -- kernel unit ------------------------------------------------------------


def test_kernel_matches_float_reference_idct():
    """The fixed-point IDCT against a float64 reference: a handful of
    random coefficient blocks must decode within ±1 level."""
    rng = np.random.default_rng(0)
    coef = np.zeros((1, 1, 1, 64), np.int16)
    coef[0, 0, 0, :16] = rng.integers(-64, 64, 16)
    quant = np.ones((1, 3, 64), np.int32) * 4
    geom = np.array([[8, 8, 1, 1, 1, 1]], np.int32)
    out = np.asarray(decode_coeff_batch(
        coef, np.zeros((1, 1, 1, 64), np.int16),
        np.zeros((1, 1, 1, 64), np.int16), quant, geom, out_size=8,
    ))
    x = np.arange(8)
    B = np.cos((2 * x[:, None] + 1) * x[None, :] * np.pi / 16) * np.where(
        x[None, :] == 0, np.sqrt(1 / 8), np.sqrt(2 / 8)
    )
    ref = B @ (coef[0, 0, 0].reshape(8, 8) * quant[0, 0].reshape(8, 8)) @ B.T
    ref = np.clip(np.round(ref + 128), 0, 255)
    # Neutral chroma: every channel equals the luma plane.
    assert np.abs(out[0, :, :, 0].astype(int) - ref).max() <= 1


def test_kernel_gray_and_color_and_odd_dims():
    payloads = [
        _smooth_jpeg(64, 48),
        _smooth_jpeg(31, 57),          # odd dims: partial edge blocks
        _smooth_jpeg(40, 40, gray=True),
        _smooth_jpeg(SIZE, SIZE),      # exact-size: no resize
    ]
    dec = CoeffImageDecoder(image_size=SIZE)
    batch = dec.decode_payloads(payloads)
    dev = _device_images(batch)
    host, failed = native_jpeg.batch_decode_jpeg(payloads, SIZE)
    assert not failed.any()
    _assert_parity(dev, host)
    # Grayscale must land as gray RGB (R == G == B).
    g = dev[2]
    np.testing.assert_array_equal(g[..., 0], g[..., 1])
    np.testing.assert_array_equal(g[..., 0], g[..., 2])


def test_device_arm_bit_identical_repeats():
    """The whole device arm twice — extraction AND kernel — must produce
    byte-identical results (the stream-determinism contract)."""
    payloads = [_smooth_jpeg(48, 48), _smooth_jpeg(64, 40)]
    a = CoeffImageDecoder(image_size=SIZE).decode_payloads(payloads)
    b = CoeffImageDecoder(image_size=SIZE).decode_payloads(payloads)
    for k in COEFF_KEYS:
        np.testing.assert_array_equal(a[k], b[k])
    np.testing.assert_array_equal(_device_images(a), _device_images(b))


def test_transform_passthrough_and_replacement(image_table):
    dec = CoeffImageDecoder(image_size=SIZE)
    coeff = dec(image_table.slice(0, 8))
    assert is_coeff_batch(coeff)
    tx = make_batch_transform(SIZE)
    out = tx(coeff)
    assert set(out) == {"image", "label"}
    assert out["image"].shape == (8, SIZE, SIZE, 3)
    pixel = {"image": np.zeros((8, SIZE, SIZE, 3), np.uint8),
             "label": np.zeros(8, np.int32)}
    assert tx(pixel) is pixel  # pixel batches pass through whole


def test_weight_column_passes_through(image_table):
    dec = CoeffImageDecoder(image_size=SIZE)
    coeff = dec(image_table.slice(0, 4))
    coeff["_weight"] = np.array([1, 1, 0, 1], np.float32)
    out = make_batch_transform(SIZE)(coeff)
    np.testing.assert_array_equal(
        np.asarray(out["_weight"]), coeff["_weight"]
    )


# -- degraded paths ---------------------------------------------------------


def test_device_decode_raises_when_native_switched_off(monkeypatch):
    """A run that asked for device decode never proceeds on the host
    pixel path: with the extractor opted out the decoder refuses."""
    monkeypatch.setattr(
        "lance_distributed_training_tpu.native.jpeg.native_available",
        lambda: False,
    )
    with pytest.raises(RuntimeError, match="LDT_DISABLE_NATIVE"):
        decoder_for_task("classification", SIZE, device_decode=True)


def test_corrupt_row_degrades_to_gray(image_table):
    payloads = [_smooth_jpeg(40, 40), b"not a jpeg at all"]
    dec = CoeffImageDecoder(image_size=SIZE)
    batch = dec.decode_payloads(payloads)
    dev = _device_images(batch)
    host, _ = native_jpeg.batch_decode_jpeg([payloads[0]], SIZE)
    _assert_parity(dev[:1], host)
    # The undecodable row: zeroed page → neutral gray, never garbage.
    assert (dev[1] == 128).all()


def test_non_420_row_reencodes():
    """A 4:4:4 JPEG can't ship on the canonical chroma grid — the driver
    re-encodes it to 4:2:0 and extracts from that (counted); the decoded
    row stays close to the host decode of the original."""
    payloads = [_smooth_jpeg(48, 48), _smooth_jpeg(48, 48, subsampling=0)]
    dec = CoeffImageDecoder(image_size=SIZE)
    batch = dec.decode_payloads(payloads)
    dev = _device_images(batch)
    host, failed = native_jpeg.batch_decode_jpeg(payloads, SIZE)
    assert not failed.any()
    _assert_parity(dev[:1], host[:1])
    # Re-encoded row: requantisation + chroma subsample add error on top
    # of the parity envelope, but the smooth corpus stays close.
    diff = np.abs(dev[1].astype(int) - host[1].astype(int))
    assert diff.mean() < 4.0


def test_non_420_row_reencodes_on_arrow_path():
    """Same tolerant path through decode_column: the re-encoded row's
    pointer/length slots are patched IN PLACE in the Arrow-built pointer
    table — the untouched rows keep their zero-copy pointers."""
    payloads = [_smooth_jpeg(48, 48), _smooth_jpeg(48, 48, subsampling=0),
                _smooth_jpeg(40, 56)]
    col = pa.array(payloads, pa.binary())
    dec = CoeffImageDecoder(image_size=SIZE)
    batch = dec.decode_column(col)
    dev = _device_images(batch)
    host, failed = native_jpeg.batch_decode_jpeg(payloads, SIZE)
    assert not failed.any()
    _assert_parity(dev[[0, 2]], host[[0, 2]])
    assert np.abs(dev[1].astype(int) - host[1].astype(int)).mean() < 4.0


def test_lease_failure_mid_batch_strands_nothing(image_table):
    """A pool whose Nth lease raises must not strand the earlier pages
    (the dict-literal leak the review caught)."""
    from lance_distributed_training_tpu.data.buffers import BufferPool

    class FlakyPool(BufferPool):
        def __init__(self):
            super().__init__()
            self.calls = 0

        def lease(self, shape, dtype):
            self.calls += 1
            if self.calls == 3:  # fail the third page lease
                raise MemoryError("synthetic allocation failure")
            return super().lease(shape, dtype)

    pool = FlakyPool()
    dec = CoeffImageDecoder(image_size=SIZE, buffer_pool=pool)
    with pytest.raises(MemoryError):
        dec(image_table.slice(0, 4))
    pool.sweep()
    assert pool.stats()["outstanding"] == 0  # pages 1-2 were released


def test_decoder_for_task_dispatch():
    dec = decoder_for_task("classification", SIZE, device_decode=True)
    assert isinstance(dec, CoeffImageDecoder)
    with pytest.raises(ValueError, match="classification"):
        decoder_for_task("masked_lm", SIZE, device_decode=True)


# -- canonical grid / autotune surface --------------------------------------


def test_grid_chunk_rounding_and_growth():
    dec = CoeffImageDecoder(image_size=SIZE, chunk_blocks=4)
    dec.decode_payloads([_smooth_jpeg(40, 40)])  # 5x5 blocks → rounds to 8x8
    assert dec._grid == (8, 8)
    dec.decode_payloads([_smooth_jpeg(80, 40)])  # 10 wide → grows to 12
    assert dec._grid == (8, 12)
    dec.decode_payloads([_smooth_jpeg(16, 16)])  # smaller: never shrinks
    assert dec._grid == (8, 12)


def test_coeff_chunk_tunable_declares_bounds():
    dec = CoeffImageDecoder(image_size=SIZE)
    (t,) = dec.tunables()
    assert t.name == "coeff_chunk" and t.lo == 1 and t.hi == 16
    assert t.set(64) == 16  # clamped to hi
    assert dec.chunk_blocks == 16


def test_pipeline_forwards_decoder_tunables(image_dataset):
    pipe = make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        CoeffImageDecoder(image_size=SIZE),
    )
    names = [t.name for t in pipe.tunables()]
    assert "prefetch" in names and "coeff_chunk" in names


# -- host-vs-device parity across all five loaders --------------------------


def _pixel_batches(image_dataset):
    return list(make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        ImageClassificationDecoder(image_size=SIZE),
    ))


def _check_stream_parity(coeff_batches, pixel_batches):
    assert len(coeff_batches) == len(pixel_batches) > 0
    for cb, pb in zip(coeff_batches, pixel_batches):
        assert is_coeff_batch(cb)
        _assert_parity(_device_images(cb), pb["image"])
        np.testing.assert_array_equal(
            np.asarray(cb["label"], np.int64),
            np.asarray(pb["label"], np.int64),
        )


def test_parity_iterable_pipeline(image_dataset):
    coeff = list(make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        CoeffImageDecoder(image_size=SIZE),
    ))
    _check_stream_parity(coeff, _pixel_batches(image_dataset))


def test_parity_map_style_pipeline(image_dataset):
    kw = dict(shuffle=True, seed=3)
    coeff = list(MapStylePipeline(
        image_dataset, 16, 0, 1, CoeffImageDecoder(image_size=SIZE), **kw
    ))
    pixel = list(MapStylePipeline(
        image_dataset, 16, 0, 1, ImageClassificationDecoder(image_size=SIZE),
        **kw
    ))
    _check_stream_parity(coeff, pixel)


def test_parity_folder_pipeline(tmp_path):
    from lance_distributed_training_tpu.data.authoring import (
        create_synthetic_image_folder,
    )
    from lance_distributed_training_tpu.data.folder import FolderDataPipeline

    root = create_synthetic_image_folder(
        str(tmp_path / "tree"), rows=48, num_classes=4, image_size=SIZE,
        unique_images=12,
    )
    kw = dict(loader_style="map", shuffle=True, seed=1)
    coeff = list(FolderDataPipeline(
        root, 16, 0, 1, CoeffImageDecoder(image_size=SIZE), **kw
    ))
    pixel = list(FolderDataPipeline(
        root, 16, 0, 1, ImageClassificationDecoder(image_size=SIZE), **kw
    ))
    _check_stream_parity(coeff, pixel)


def test_parity_remote_loader(image_dataset):
    from lance_distributed_training_tpu.service import (
        DataService,
        RemoteLoader,
        ServeConfig,
    )

    svc = DataService(ServeConfig(
        dataset_path=image_dataset.uri, host="127.0.0.1", port=0,
        image_size=SIZE, queue_depth=2, device_decode=True,
    )).start()
    try:
        coeff = list(RemoteLoader(
            f"127.0.0.1:{svc.port}", 16, 0, 1,
            connect_retries=2, backoff_s=0.01, device_decode=True,
        ))
        _check_stream_parity(coeff, _pixel_batches(image_dataset))
        # Declared-skew rejection: a pixel client must not silently
        # consume coefficient pages.
        with pytest.raises(Exception, match="skew"):
            list(RemoteLoader(
                f"127.0.0.1:{svc.port}", 16, 0, 1,
                connect_retries=1, backoff_s=0.01, device_decode=False,
            ))
    finally:
        svc.stop()


def test_parity_fleet_loader(image_dataset):
    from lance_distributed_training_tpu.fleet import (
        Coordinator,
        CoordinatorConfig,
        FleetLoader,
    )
    from lance_distributed_training_tpu.service import DataService, ServeConfig

    coord = Coordinator(CoordinatorConfig(
        host="127.0.0.1", port=0,
        heartbeat_interval_s=0.1, lease_ttl_s=2.0,
    )).start()
    servers = []
    try:
        for _ in range(2):
            svc = DataService(ServeConfig(
                dataset_path=image_dataset.uri, host="127.0.0.1", port=0,
                image_size=SIZE, queue_depth=2, device_decode=True,
                coordinator_addr=f"127.0.0.1:{coord.port}",
            )).start()
            assert svc.fleet_agent.registered.wait(5)
            servers.append(svc)
        coeff = list(FleetLoader(
            f"127.0.0.1:{coord.port}", 16, 0, 1,
            connect_retries=2, resolve_retries=3, backoff_s=0.05,
            device_decode=True,
        ))
        _check_stream_parity(coeff, _pixel_batches(image_dataset))
    finally:
        for s in servers:
            s.stop()
        coord.stop()


# -- resume cursor with device decode on ------------------------------------


def test_resume_cursor_round_trip(image_dataset):
    """state_dict() round-trip mid-epoch with the coefficient decoder: the
    resumed tail must be BIT-identical (pages, not just pixels)."""
    def build():
        return make_train_pipeline(
            image_dataset, "batch", 16, 0, 1,
            CoeffImageDecoder(image_size=SIZE),
        )

    full = list(build())
    pipe = build()
    it = iter(pipe)
    consumed = [next(it) for _ in range(5)]
    cursor = pipe.state_dict()
    assert cursor["step"] == 5
    it.close()
    resumed_pipe = build()
    resumed_pipe.load_state_dict(cursor)
    tail = list(resumed_pipe)
    assert len(consumed) + len(tail) == len(full)
    for got, want in zip(tail, full[5:]):
        for k in COEFF_KEYS:
            np.testing.assert_array_equal(got[k], want[k])
        np.testing.assert_array_equal(got["label"], want["label"])


# -- pooled pages -----------------------------------------------------------


def test_pages_lease_and_release_through_pool(image_table):
    from lance_distributed_training_tpu.data.buffers import BufferPool
    from lance_distributed_training_tpu.obs.registry import default_registry

    pool = BufferPool()
    dec = CoeffImageDecoder(image_size=SIZE, buffer_pool=pool)
    batch = dec(image_table.slice(0, 16))
    assert pool.stats()["outstanding"] >= 5  # the five page leaves leased
    released = pool.release_batch(batch)
    assert released >= 5
    del batch  # drop the last external reference so the sweep can recycle
    pool.sweep()
    assert pool.stats()["outstanding"] == 0
    # Second batch on the same grid: warm pages recycle (pool hits).
    before = default_registry().snapshot().get("bufpool_hit_total", 0.0)
    batch2 = dec(image_table.slice(16, 16))
    after = default_registry().snapshot().get("bufpool_hit_total", 0.0)
    assert after > before
    pool.release_batch(batch2)


def test_worker_pickle_round_trip():
    import pickle

    dec = CoeffImageDecoder(image_size=SIZE, chunk_blocks=8)
    clone = pickle.loads(pickle.dumps(dec))
    assert clone.chunk_blocks == 8
    out = clone.decode_payloads([_smooth_jpeg(40, 40)])
    assert is_coeff_batch(out)


# -- wire / protocol --------------------------------------------------------


def test_hello_carries_device_decode():
    from lance_distributed_training_tpu.service import protocol as P

    h = P.hello(batch_size=4, process_index=0, process_count=1,
                device_decode=True)
    assert h["device_decode"] is True
    assert P.hello(batch_size=4, process_index=0,
                   process_count=1)["device_decode"] is None


def test_coeff_batch_survives_wire_encoding():
    from lance_distributed_training_tpu.service import protocol as P

    dec = CoeffImageDecoder(image_size=SIZE)
    batch = dec.decode_payloads([_smooth_jpeg(40, 40), _smooth_jpeg(48, 32)])
    step, out = P.decode_batch(P.encode_batch(3, batch))
    assert step == 3
    for k in COEFF_KEYS:
        np.testing.assert_array_equal(out[k], batch[k])


# -- decode pool lifecycle (satellite) --------------------------------------


def test_decode_pool_shutdown_is_idempotent_and_reaps():
    import lance_distributed_training_tpu.data.decode as decode_mod

    pool = decode_mod._pool()
    assert decode_mod._POOL is pool
    decode_mod.shutdown_decode_pool()
    assert decode_mod._POOL is None
    assert pool._shutdown  # the executor really was shut down
    decode_mod.shutdown_decode_pool()  # idempotent
    # Lazily respawns for later callers.
    assert decode_mod._pool() is not pool


def test_resources_vocabulary_guards_decode_pool():
    """The [tool.ldt-check.resources] table must carry the decode-pool
    kind (satellite: LDT1201 guards the shared executor's lifecycle)."""
    import os

    from lance_distributed_training_tpu.analysis.config import load_config

    cfg = load_config(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    assert "decode-pool" in cfg.resources
    kind = cfg.resources["decode-pool"]
    assert "ThreadPoolExecutor" in kind["acquire"]
    assert "shutdown" in kind["release"]


# -- obs (satellite) --------------------------------------------------------


def test_decode_byte_counters_and_entropy_histogram(image_table):
    from lance_distributed_training_tpu.obs.registry import default_registry

    reg = default_registry()
    before = reg.snapshot()
    CoeffImageDecoder(image_size=SIZE)(image_table.slice(0, 8))
    ImageClassificationDecoder(image_size=SIZE)(image_table.slice(0, 8))
    after = reg.snapshot()

    def delta(key):
        return after.get(key, 0.0) - before.get(key, 0.0)

    assert delta("decode_coeff_bytes_total") > 0
    assert delta("decode_pixel_bytes_total") == 8 * SIZE * SIZE * 3
    assert delta("decode_entropy_ms_count") == 1


# -- trainer integration (slow) ---------------------------------------------


@pytest.mark.slow
def test_train_with_device_decode_matches_host_arm(image_dataset):
    """A short train run on each arm: the device arm must train (finite
    loss, eval runs) and stay close to the host arm — the decoded tensors
    differ by at most the parity envelope, so the first-steps loss paths
    track each other."""
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    common = dict(
        dataset_path=image_dataset.uri, num_classes=10, image_size=SIZE,
        batch_size=16, epochs=1, max_steps=3, no_wandb=True,
        eval_at_end=True, log_every=0, model_name="resnet18",
        autotune=False, lr=0.01,
    )
    host = train(TrainConfig(device_decode=False, **common))
    dev = train(TrainConfig(device_decode=True, **common))
    assert np.isfinite(dev["loss"])
    assert "train_acc" in dev  # eval consumed coefficient batches too
    assert dev["loss"] == pytest.approx(host["loss"], abs=0.05)
