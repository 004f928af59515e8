"""Decode hooks — the pluggable RecordBatch→tensor hot loop.

These are the public customisation points the reference exposes as
``to_tensor_fn`` (iterable path, ``/root/reference/lance_iterable.py:38-50``)
and ``collate_fn`` (map-style path, ``lance_map_style.py:21-44``). Signature
here: ``decode_fn(record_batch: pa.RecordBatch | pa.Table) -> dict[str,
np.ndarray]``.

Re-design of the reference's weakest link (SURVEY.md §3 hot-loop summary):

* the reference does ``batch.to_pylist()`` then a per-row Python loop with
  PIL decode + Resize(224) + ToTensor, single-threaded in the training
  process (``lance_iterable.py:75-77``), and the map-style twin rebuilds the
  transform ``Compose`` on every call (``lance_map_style.py:29-32``);
* here, JPEG decode fans out over a shared thread pool (PIL releases the GIL
  in its decode/resize C paths), the output is a **uint8 NHWC** batch — 3×
  less host→device traffic than f32 CHW — and scale/normalize run on device,
  fused into the first conv (:mod:`..ops.image`). No per-call allocation of
  transform objects; the pool and buffers persist.
"""

from __future__ import annotations

import io
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Union

import numpy as np
import pyarrow as pa

__all__ = ["ImageClassificationDecoder", "decode_tensor_image",
           "numeric_decoder", "decoder_for_task", "shutdown_decode_pool"]

_POOL: Optional[ThreadPoolExecutor] = None
_POOL_ATEXIT_REGISTERED = False


def _pool() -> ThreadPoolExecutor:
    global _POOL, _POOL_ATEXIT_REGISTERED
    if _POOL is None:
        import os

        # Reap at interpreter exit, mirroring WorkerPool's finalize
        # discipline (LDT1201 guards the pool via the decode-pool resource
        # kind): without this the executor's own non-daemon threads hold
        # the interpreter on the concurrent.futures atexit join, and a
        # wedged PIL decode would hang shutdown forever. Registered ONCE,
        # BEFORE the executor exists (shutdown of a None pool no-ops), so
        # no raise can strand an unregistered pool and shutdown/respawn
        # cycles never stack duplicate atexit entries.
        if not _POOL_ATEXIT_REGISTERED:
            import atexit

            atexit.register(shutdown_decode_pool)
            _POOL_ATEXIT_REGISTERED = True
        _POOL = ThreadPoolExecutor(
            max_workers=max(4, (os.cpu_count() or 8) // 2),
            thread_name_prefix="ldt-decode",
        )
    return _POOL


def shutdown_decode_pool() -> None:
    """Shut the shared decode ThreadPoolExecutor down (idempotent; also
    registered atexit on first use). The next ``_pool()`` call lazily
    spawns a fresh one, so tests and long-lived embedders can reap it
    between phases."""
    global _POOL
    pool, _POOL = _POOL, None
    if pool is not None:
        pool.shutdown(wait=False, cancel_futures=True)


def _pixel_bytes_counter():
    """``decode_pixel_bytes_total`` — finished-pixel bytes the HOST path
    produces per batch; against ``decode_coeff_bytes_total`` (the
    device-decode half, :mod:`.device_decode`) the wire-traffic trade of
    the entropy split is scrapeable on /metrics. Looked up lazily so the
    decoder stays picklable across worker processes."""
    from ..obs.registry import default_registry

    return default_registry().counter("decode_pixel_bytes_total")


class ImageClassificationDecoder:
    """JPEG-bytes + int label columns → ``{'image': u8 [B,H,W,3], 'label': i32 [B]}``.

    Drop-in equivalent of the reference's ``decode_tensor_image``
    (``/root/reference/lance_iterable.py:38-50``) over the schema written by
    ``create_datasets/classification.py:50-53`` (``{image: binary, label:
    int64}``), minus its inefficiencies: thread-pool decode, one persistent
    transform, uint8 output.
    """

    def __init__(
        self,
        image_size: int = 224,
        image_column: str = "image",
        label_column: Optional[str] = "label",
        use_native: bool = True,
        buffer_pool=None,
    ):
        self.image_size = image_size
        self.image_column = image_column
        self.label_column = label_column
        self.use_native = use_native
        # Optional data.buffers.BufferPool: decode writes into warm,
        # recycled pages (out=) instead of faulting a fresh np.empty per
        # batch. The pipeline that consumes the batch owns the release
        # (after device_put dispatch / after yield).
        self.buffer_pool = buffer_pool
        self._bind_native()

    @property
    def required_columns(self) -> list[str]:
        """Columns this decoder reads — the pipelines project reads to these
        (Lance scanner column selection; unused columns never leave disk)."""
        cols = [self.image_column]
        if self.label_column is not None:
            cols.append(self.label_column)
        return cols

    def cache_fingerprint(self) -> str:
        """Batch-cache identity (``data/cache.py``): everything that can
        change the BYTES this decoder emits. Native availability is
        included — libjpeg and the PIL fallback decode to slightly
        different pixels, so a cache written by one must never hit in a
        process running the other."""
        return (
            f"ImageClassificationDecoder/{self.image_size}/"
            f"{self.image_column}/{self.label_column}/"
            f"native={self._native is not None}"
        )

    def _bind_native(self) -> None:
        self._native = None
        self._native_arrow = None
        if self.use_native:
            from ..native import (
                batch_decode_jpeg,
                batch_decode_jpeg_arrow,
                native_available,
            )

            # A decoder that cannot be built raises here (with g++'s
            # message): training at PIL's rate is chosen, never fallen into.
            if native_available():
                self._native = batch_decode_jpeg
                self._native_arrow = batch_decode_jpeg_arrow

    # Picklable for process-pool workers (the ctypes binding can't cross the
    # process boundary; each worker re-binds its own).
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_native"] = None
        state["_native_arrow"] = None
        # A BufferPool holds locks and process-local pages — meaningless
        # across the process boundary. Workers re-bind their own
        # (data/workers._init_worker).
        state["buffer_pool"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._bind_native()

    def _decode_one(self, payload: bytes) -> np.ndarray:
        from PIL import Image

        img = Image.open(io.BytesIO(payload))
        # DCT-scaled decode: libjpeg decodes at 1/2, 1/4 or 1/8 scale when the
        # target is smaller, typically 2-4x faster than decode-then-resize
        # (the reference decodes at full size then resizes,
        # lance_iterable.py:29,44-46).
        img.draft("RGB", (self.image_size, self.image_size))
        if img.mode != "RGB":
            img = img.convert("RGB")
        if img.size != (self.image_size, self.image_size):
            img = img.resize((self.image_size, self.image_size), Image.BILINEAR)
        return np.asarray(img, dtype=np.uint8)

    def _lease_out(self, n: int) -> Optional[np.ndarray]:
        """A pooled ``[n, S, S, 3] u8`` output page, or ``None`` when no
        pool is bound (fresh-alloc path) or the batch is empty."""
        if self.buffer_pool is None or n == 0:
            return None
        return self.buffer_pool.lease(
            (n, self.image_size, self.image_size, 3), np.uint8
        )

    def decode_payloads(self, payloads: list[bytes]) -> np.ndarray:
        """JPEG byte strings → ``[N, S, S, 3] uint8`` (native path if built).

        Each path leases its output page immediately before handing it to
        the call that fills it (the ``out=`` transfer) — leasing up front
        would strand the page if a PIL decode raised first (LDT1201's
        exception-edge leak class).
        """
        if self._native is not None:
            images, failed = self._native(
                payloads, self.image_size, out=self._lease_out(len(payloads))
            )
            if failed.any():
                # Corrupt-for-libjpeg rows: retry via the tolerant PIL path.
                for i in np.nonzero(failed)[0]:
                    images[i] = self._decode_one(payloads[i])
            return images
        if len(payloads) >= 8:
            images = list(_pool().map(self._decode_one, payloads))
        else:
            images = [self._decode_one(p) for p in payloads]
        out = self._lease_out(len(payloads))
        if out is not None:
            return np.stack(images, out=out)
        return np.stack(images)

    def decode_column(self, col) -> np.ndarray:
        """Decode an Arrow (chunked) binary column of JPEGs.

        Fast path: hand the column's Arrow buffers straight to the native
        decoder (zero Python objects on the hot loop — the reference
        materialises a pylist per batch, ``lance_iterable.py:44``). Falls
        back to per-row bytes + PIL when the native library isn't built.
        """
        if isinstance(col, pa.ChunkedArray):
            col = col.combine_chunks()
        if self._native_arrow is not None and (
            pa.types.is_binary(col.type) or pa.types.is_large_binary(col.type)
        ):
            images, failed = self._native_arrow(
                col, self.image_size, out=self._lease_out(len(col))
            )
            if failed.any():
                # Corrupt-for-libjpeg rows: tolerant PIL retry, row by row.
                for i in np.nonzero(failed)[0]:
                    images[i] = self._decode_one(col[int(i)].as_py())
            return images
        return self.decode_payloads(col.to_pylist())  # ldt: ignore[LDT701] -- deliberate PIL fallback arm: tolerant row-by-row decode needs Python bytes; the zero-copy path above handles the native decoder

    def __call__(
        self, batch: Union[pa.RecordBatch, pa.Table]
    ) -> dict[str, np.ndarray]:
        images = self.decode_column(batch.column(self.image_column))
        _pixel_bytes_counter().inc(images.nbytes)
        out = {"image": images}
        if self.label_column is not None:
            out["label"] = np.asarray(
                batch.column(self.label_column).to_numpy(zero_copy_only=False),
                dtype=np.int32,
            )
        return out


def decode_tensor_image(
    batch: Union[pa.RecordBatch, pa.Table], image_size: int = 224
) -> dict[str, np.ndarray]:
    """Functional form, name-compatible with the reference hook."""
    return ImageClassificationDecoder(image_size=image_size)(batch)


class ImageTextDecoder:
    """Mixed-modal collate: JPEG bytes + packed token columns → one batch dict
    (the BASELINE "LAION-subset image+caption → CLIP" config). Images via the
    native/PIL path, token columns zero-copy via :func:`numeric_decoder` —
    or, with ``token_pack``/``seq_len``, the ragged plane's
    :class:`~.token_pack.TokenDecoder` in **bucket** mode: one sequence per
    slot (caption i stays paired with image i), slot length bucketed to the
    batch max instead of padded to the dataset max."""

    def __init__(self, image_size: int = 224, image_column: str = "image",
                 buffer_pool=None, token_pack=None,
                 seq_len: Optional[int] = None):
        self._image = ImageClassificationDecoder(
            image_size=image_size, image_column=image_column,
            label_column=None, buffer_pool=buffer_pool,
        )
        self.image_column = image_column
        self._text = None
        if token_pack is not None or seq_len is not None:
            from .token_pack import TokenDecoder, TokenPackPlanner

            if token_pack is not None:
                self._text = TokenDecoder(
                    mode="bucket",
                    seq_len=seq_len or token_pack.pack_len,
                    planner=TokenPackPlanner(token_pack),
                    buffer_pool=buffer_pool,
                    pad_id=token_pack.pad_id,
                )
            else:
                self._text = TokenDecoder(mode="pad", seq_len=seq_len,
                                          buffer_pool=buffer_pool)

    @property
    def buffer_pool(self):
        return self._image.buffer_pool

    @buffer_pool.setter
    def buffer_pool(self, pool) -> None:
        self._image.buffer_pool = pool
        if self._text is not None:
            self._text.buffer_pool = pool

    def cache_fingerprint(self) -> str:
        text = (
            self._text.cache_fingerprint() if self._text is not None
            else "numeric"
        )
        return f"ImageTextDecoder/{self._image.cache_fingerprint()}/{text}"

    def tunables(self):
        if self._text is None:
            return []
        return self._text.tunables()

    def __call__(
        self, batch: Union[pa.RecordBatch, pa.Table]
    ) -> dict[str, np.ndarray]:
        table = (
            pa.Table.from_batches([batch])
            if isinstance(batch, pa.RecordBatch)
            else batch
        )
        text_fn = self._text if self._text is not None else numeric_decoder
        out = text_fn(table.drop_columns([self.image_column]))
        out["image"] = self._image.decode_column(
            table.column(self.image_column)
        )
        _pixel_bytes_counter().inc(out["image"].nbytes)
        return out


def decoder_for_task(task_type: str, image_size: int = 224,
                     buffer_pool=None, device_decode: bool = False,
                     token_pack=None, seq_len: Optional[int] = None):
    """THE task-type → decode-hook dispatch, shared by the trainer and the
    data-service server. Keeping it in one place is what upholds the
    service's bit-identical-batches guarantee: a decoder change that only
    landed on one side would silently train on different tensors.
    ``buffer_pool`` (data/buffers.BufferPool) makes the image decoders
    write into recycled pages; output values are bit-identical either way
    (the guarantee extends to the buffer plane — tests pin it).

    ``device_decode`` selects the entropy-split decoder
    (:mod:`.device_decode`): the host emits half-decoded coefficient pages
    and the dense back half runs as the jitted device kernel
    (:mod:`..ops.jpeg_device`) — classification only; raises when the
    native extractor is switched off or cannot be built.

    The text tasks' ragged plane (r15, :mod:`.token_pack`): ``token_pack``
    (a :class:`~.token_pack.TokenPackConfig`) selects the ragged emit —
    variable-length columns ship as values+offsets pages plus a
    deterministic FFD pack plan, finished by the device kernel
    (:mod:`..ops.token_device`). With ``seq_len`` alone the padded
    :class:`~.token_pack.TokenDecoder` control arm runs (variable columns
    pad to ``seq_len`` — the exact pre-ragged stream); with neither, the
    plain :func:`numeric_decoder` keeps its historical fixed-size-only
    contract."""
    if task_type == "classification":
        if device_decode:
            from .device_decode import CoeffImageDecoder

            return CoeffImageDecoder(
                image_size=image_size, buffer_pool=buffer_pool
            )
        return ImageClassificationDecoder(
            image_size=image_size, buffer_pool=buffer_pool
        )
    if device_decode:
        raise ValueError(
            "device_decode currently supports task_type='classification' "
            f"only (the JPEG entropy split), got {task_type!r}"
        )
    if task_type in ("masked_lm", "causal_lm"):
        if token_pack is not None or seq_len is not None:
            from .token_pack import TokenDecoder, TokenPackPlanner

            if token_pack is not None:
                return TokenDecoder(
                    mode="pack",
                    seq_len=seq_len or token_pack.pack_len,
                    planner=TokenPackPlanner(token_pack),
                    buffer_pool=buffer_pool,
                    pad_id=token_pack.pad_id,
                )
            return TokenDecoder(mode="pad", seq_len=seq_len,
                                buffer_pool=buffer_pool)
        return numeric_decoder  # zero-copy Arrow→numpy: nothing to pool
    if task_type == "contrastive":
        return ImageTextDecoder(image_size=image_size,
                                buffer_pool=buffer_pool,
                                token_pack=token_pack, seq_len=seq_len)
    raise ValueError(f"Invalid task type: {task_type}")


def numeric_decoder(batch: Union[pa.RecordBatch, pa.Table]) -> dict[str, np.ndarray]:
    """Decode all-numeric columnar batches (text-token / tabular datasets):
    each column straight to numpy, fixed-size list columns to 2-D arrays.

    Zero-copy (the r15 silent-copy fix): a null-free primitive buffer is
    viewed with one ``np.frombuffer`` window instead of the
    ``to_numpy(zero_copy_only=False)`` path, which memcpys even when the
    buffer is directly addressable; fallbacks are counted on the LDT701
    copy-hygiene rows (``decode_token_bytes_total`` /
    ``decode_token_copies_total``). Variable-length list columns pad to
    the *batch* max (shape varies batch to batch) — static-shape training
    goes through :class:`~.token_pack.TokenDecoder` instead."""
    from .token_pack import (
        _token_copy_metrics,
        fill_padded,
        list_column_parts,
        primitive_view,
    )

    out: dict[str, np.ndarray] = {}
    table = pa.Table.from_batches([batch]) if isinstance(batch, pa.RecordBatch) else batch
    tok_bytes, tok_copies = _token_copy_metrics()
    for name in table.column_names:
        col = table.column(name).combine_chunks()
        if pa.types.is_fixed_size_list(col.type):
            flat = col.chunk(0) if isinstance(col, pa.ChunkedArray) else col
            values, copied = primitive_view(flat.values)
            tok_bytes.inc(values.nbytes)
            if copied:
                tok_copies.inc(values.nbytes)
            out[name] = values.reshape(len(flat), col.type.list_size)
        elif pa.types.is_list(col.type) or pa.types.is_large_list(col.type):
            values, offsets, copied = list_column_parts(col)
            tok_bytes.inc(values.nbytes)
            if copied:
                tok_copies.inc(values.nbytes)
            lengths = offsets[1:] - offsets[:-1]
            width = int(lengths.max()) if len(lengths) else 0
            page = np.zeros((len(lengths), width), values.dtype)
            fill_padded(page, values, offsets, lengths)
            out[name] = page
        else:
            values, copied = primitive_view(
                col.chunk(0) if isinstance(col, pa.ChunkedArray) else col
            )
            tok_bytes.inc(values.nbytes)
            if copied:
                tok_copies.inc(values.nbytes)
            out[name] = values
    return out
