"""Native JPEG decoder tests (skip cleanly where g++/libjpeg are absent)."""

import io

import numpy as np
import pytest

from lance_distributed_training_tpu.native import batch_decode_jpeg, native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native decoder unavailable"
)


def _jpeg(arr):
    from PIL import Image

    b = io.BytesIO()
    Image.fromarray(arr).save(b, format="JPEG", quality=90)
    return b.getvalue()


@pytest.fixture()
def fresh_native(tmp_path, monkeypatch):
    """The loader pointed at a private copy of the source in ``tmp_path``,
    with no library loaded yet."""
    import shutil

    from lance_distributed_training_tpu.native import jpeg as jmod

    src = tmp_path / "ldt_decode.cpp"
    shutil.copy(jmod._SRC, src)
    monkeypatch.setattr(jmod, "_HERE", str(tmp_path))
    monkeypatch.setattr(jmod, "_SRC", str(src))
    monkeypatch.setattr(jmod, "_lib", None)
    monkeypatch.delenv("LDT_DISABLE_NATIVE", raising=False)
    return jmod, src


def test_library_identity_follows_source_content(fresh_native):
    """Same source, command and CPU → same name; one changed byte of the
    source → another name, so an older build can never be the one loaded."""
    jmod, src = fresh_native
    first = jmod.library_path()
    assert first == jmod.library_path()
    assert first.startswith(str(src.parent))  # inside the checkout
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    assert jmod.library_path() != first


def test_library_identity_follows_command_and_cpu(fresh_native, monkeypatch):
    jmod, _ = fresh_native
    base = jmod.library_path()
    monkeypatch.setattr(jmod, "_COMPILE", jmod._COMPILE + ("-DX",))
    assert jmod.library_path() != base
    monkeypatch.setattr(jmod, "_cpu_identity", lambda: "another machine")
    assert jmod.library_path() != base


def test_foreign_library_at_old_path_is_not_loaded(fresh_native):
    """A stale or foreign ``_ldt_decode.so`` (the pre-keyed name, trusted by
    mtime) riding along in a copied tree is ignored: the loader builds its
    own library under the keyed name and binds that."""
    jmod, src = fresh_native
    stale = src.parent / "_ldt_decode.so"
    stale.write_bytes(b"\x7fELF not a library this CPU can run")
    lib = jmod._load()
    assert lib is not None
    assert lib._name == jmod.library_path()
    assert jmod.library_path() != str(stale)
    assert stale.read_bytes().startswith(b"\x7fELF not")  # untouched
    rng = np.random.default_rng(0)
    payload = _jpeg((rng.random((48, 48, 3)) * 255).astype(np.uint8))
    out, failed = jmod.batch_decode_jpeg([payload], 32)
    assert out.shape == (1, 32, 32, 3) and not failed.any()


def test_failed_build_raises_with_compiler_stderr(fresh_native):
    """No silent PIL: a decoder that cannot be built raises, and the error
    carries g++'s own message."""
    from lance_distributed_training_tpu.data.decode import (
        ImageClassificationDecoder,
    )

    jmod, src = fresh_native
    src.write_text("this is not C++ @@@\n")
    with pytest.raises(jmod.NativeBuildError) as err:
        jmod.native_available()
    assert "error" in str(err.value) and "ldt_decode.cpp" in str(err.value)
    with pytest.raises(jmod.NativeBuildError):
        ImageClassificationDecoder(image_size=32)
    # PIL is reached only by asking for it.
    assert ImageClassificationDecoder(
        image_size=32, use_native=False)._native is None


def test_missing_compiler_raises_and_names_the_opt_out(fresh_native,
                                                       monkeypatch):
    jmod, _ = fresh_native
    monkeypatch.setattr(jmod, "_COMPILE", ("g++-not-installed",))
    with pytest.raises(jmod.NativeBuildError, match="LDT_DISABLE_NATIVE"):
        jmod.native_available()


def test_disable_env_selects_pil(fresh_native, monkeypatch):
    jmod, _ = fresh_native
    monkeypatch.setenv("LDT_DISABLE_NATIVE", "1")
    assert jmod.native_available() is False


def test_decode_shapes_and_determinism():
    rng = np.random.default_rng(0)
    payloads = [_jpeg((rng.random((64, 64, 3)) * 255).astype(np.uint8))
                for _ in range(10)]
    a, failed_a = batch_decode_jpeg(payloads, 32)
    b, failed_b = batch_decode_jpeg(payloads, 32)
    assert a.shape == (10, 32, 32, 3) and a.dtype == np.uint8
    assert not failed_a.any() and not failed_b.any()
    np.testing.assert_array_equal(a, b)


def test_decode_matches_pil_closely():
    from PIL import Image

    rng = np.random.default_rng(1)
    # Smooth gradient image: decode differences should be tiny.
    base = np.linspace(0, 255, 128, dtype=np.uint8)
    arr = np.stack(np.broadcast_arrays(base[:, None], base[None, :],
                                       base[::-1, None]), axis=-1)
    payload = _jpeg(np.ascontiguousarray(arr))
    out, failed = batch_decode_jpeg([payload], 128)
    ref = np.asarray(Image.open(io.BytesIO(payload)).convert("RGB"))
    assert not failed.any()
    assert np.abs(out[0].astype(int) - ref.astype(int)).mean() < 3.0


def test_dct_scaled_downscale_decode():
    rng = np.random.default_rng(2)
    arr = (rng.random((512, 512, 3)) * 255).astype(np.uint8)
    out, failed = batch_decode_jpeg([_jpeg(arr)], 224)
    assert out.shape == (1, 224, 224, 3) and not failed.any()


def test_grayscale_jpeg_expands_to_rgb():
    from PIL import Image

    gray = (np.linspace(0, 255, 64 * 64).reshape(64, 64)).astype(np.uint8)
    b = io.BytesIO()
    Image.fromarray(gray, mode="L").save(b, format="JPEG")
    out, failed = batch_decode_jpeg([b.getvalue()], 32)
    assert not failed.any()
    # All three channels equal.
    np.testing.assert_array_equal(out[0][..., 0], out[0][..., 1])


def test_corrupt_payload_flagged_not_fatal():
    rng = np.random.default_rng(3)
    good = _jpeg((rng.random((64, 64, 3)) * 255).astype(np.uint8))
    out, failed = batch_decode_jpeg([good, b"not a jpeg", good], 32)
    assert failed.tolist() == [0, 1, 0]
    assert out[1].sum() == 0  # zero-filled slot
    assert out[0].sum() > 0


def test_decoder_class_uses_native_with_pil_fallback(image_table):
    from lance_distributed_training_tpu.data.decode import ImageClassificationDecoder

    dec = ImageClassificationDecoder(image_size=32, use_native=True)
    assert dec._native is not None
    out = dec(image_table.slice(0, 12))
    assert out["image"].shape == (12, 32, 32, 3)
    # Native and PIL paths agree closely on the same rows.
    ref = ImageClassificationDecoder(image_size=32, use_native=False)(
        image_table.slice(0, 12)
    )
    diff = np.abs(out["image"].astype(int) - ref["image"].astype(int)).mean()
    # Random-noise JPEGs are worst-case for decoder variance (IFAST DCT +
    # non-fancy chroma upsampling vs PIL's ISLOW/fancy); smooth images agree
    # within ~3 (test_decode_matches_pil_closely).
    assert diff < 20.0


@pytest.fixture(scope="module")
def jpeg_payloads():
    rng = np.random.default_rng(11)
    return [_jpeg((rng.random((48, 48, 3)) * 255).astype(np.uint8))
            for _ in range(8)]


def test_arrow_path_matches_pylist_path(jpeg_payloads):
    """Zero-copy Arrow-buffer decode must be bit-identical to the c_char_p
    path, including on sliced (non-zero offset) arrays."""
    import pyarrow as pa

    from lance_distributed_training_tpu.native import (
        batch_decode_jpeg,
        batch_decode_jpeg_arrow,
        native_available,
    )

    if not native_available():
        pytest.skip("native decoder not built")
    arr = pa.array(jpeg_payloads, pa.binary())
    via_list, f1 = batch_decode_jpeg(jpeg_payloads, 32)
    via_arrow, f2 = batch_decode_jpeg_arrow(arr, 32)
    assert not f1.any() and not f2.any()
    np.testing.assert_array_equal(via_list, via_arrow)
    # Sliced array: offsets no longer start at 0.
    sliced = arr.slice(1, len(jpeg_payloads) - 2)
    via_sliced, f3 = batch_decode_jpeg_arrow(sliced, 32)
    assert not f3.any()
    np.testing.assert_array_equal(via_sliced, via_list[1:-1])
    # large_binary offsets (int64) work too.
    large = arr.cast(pa.large_binary())
    via_large, f4 = batch_decode_jpeg_arrow(large, 32)
    np.testing.assert_array_equal(via_large, via_list)


def test_arrow_path_flags_corrupt_rows(jpeg_payloads):
    import pyarrow as pa

    from lance_distributed_training_tpu.native import (
        batch_decode_jpeg_arrow,
        native_available,
    )

    if not native_available():
        pytest.skip("native decoder not built")
    payloads = list(jpeg_payloads[:3]) + [b"not a jpeg"] + list(jpeg_payloads[3:])
    arr = pa.array(payloads, pa.binary())
    images, failed = batch_decode_jpeg_arrow(arr, 32)
    assert failed.tolist() == [0, 0, 0, 1] + [0] * (len(payloads) - 4)
    assert not images[3].any()  # zero-filled failed slot


def test_decoder_uses_arrow_path(jpeg_payloads):
    """ImageClassificationDecoder over a Table equals the raw native output."""
    import pyarrow as pa

    from lance_distributed_training_tpu.data.decode import (
        ImageClassificationDecoder,
    )

    table = pa.table(
        {"image": pa.array(jpeg_payloads, pa.binary()),
         "label": pa.array(range(len(jpeg_payloads)), pa.int64())}
    )
    dec = ImageClassificationDecoder(image_size=32)
    out = dec(table)
    ref = dec.decode_payloads(list(jpeg_payloads))
    np.testing.assert_array_equal(out["image"], ref)
    assert out["label"].tolist() == list(range(len(jpeg_payloads)))
