"""Host half of device-side decode: entropy-only JPEG → coefficient pages.

:class:`CoeffImageDecoder` is the ``--device_decode`` counterpart of
:class:`~.decode.ImageClassificationDecoder`: same decode-hook signature
(RecordBatch/Table → batch dict), but instead of finished pixels it emits
**half-decoded coefficient pages** — quantized DCT blocks, dequant tables
and per-image geometry (layout documented in :mod:`..ops.jpeg_device`) —
leaving everything dense to the jitted device kernel. The host does only
the inherently sequential Huffman/entropy work (``jpeg_read_coefficients``
via ``native/ldt_decode.cpp`` ABI v3); the dense back half (dequantise,
IDCT, colour, resize) is the part of decode that does not have to stay on
the host's cores.

Canonical page geometry: pages are padded to a per-decoder block grid that
grows monotonically to the largest image seen, rounded UP to
``chunk_blocks`` granularity. The rounding is the stability lever — every
distinct grid is a separate jit compile of the device kernel and a
separate :class:`~.buffers.BufferPool` page key, so coarser chunks mean
fewer recompiles and better page reuse at the price of more padding bytes
on the wire. ``chunk_blocks`` is exposed as the ``coeff_chunk`` autotune
Tunable (mandatory lo/hi, LDT1101).

Degraded path:

* a row the extractor cannot take (non-4:2:0 sampling, CMYK, corrupt-for-
  libjpeg bytes) is PIL-decoded and re-encoded to baseline 4:2:0 JPEG,
  then extracted again (``decode_coeff_reencode_total``); a row that still
  fails keeps its zeroed page — which decodes to neutral gray, mirroring
  the pixel path's zero-fill contract for undecodable rows.

Telemetry: ``decode_entropy_ms`` (per-batch host entropy time — the half
that remains on the CPU), ``decode_coeff_bytes_total`` (coefficient bytes
produced; against ``decode_pixel_bytes_total`` from the pixel decoders it
makes the wire-traffic trade scrapeable on /metrics).
"""

from __future__ import annotations

import io
import time
from typing import Optional, Union

import numpy as np
import pyarrow as pa

from ..obs.costs import note_cost
from ..obs.registry import default_registry

__all__ = ["CoeffImageDecoder"]


def _round_up(blocks: int, chunk: int) -> int:
    chunk = max(1, int(chunk))
    return ((max(1, blocks) + chunk - 1) // chunk) * chunk


class CoeffImageDecoder:
    """JPEG-bytes + label columns → coefficient-page batch dict.

    Output keys: ``jpeg_coef_y/cb/cr``, ``jpeg_quant``, ``jpeg_geom``
    (:data:`~..ops.jpeg_device.COEFF_KEYS`) plus ``label``. Raises at
    construction when the native extractor is switched off
    (``LDT_DISABLE_NATIVE``) or cannot be built: a run that asked for
    device decode never proceeds on the host pixel path instead.
    """

    def __init__(
        self,
        image_size: int = 224,
        image_column: str = "image",
        label_column: Optional[str] = "label",
        buffer_pool=None,
        chunk_blocks: int = 4,
        n_threads: int = 0,
    ):
        self.image_size = image_size
        self.image_column = image_column
        self.label_column = label_column
        self.buffer_pool = buffer_pool
        self.chunk_blocks = max(1, int(chunk_blocks))
        self.n_threads = n_threads
        # Canonical luma grid (blocks), monotonically grown; chroma is
        # always its ceil-half (the 4:2:0 canonical layout).
        self._grid: tuple[int, int] = (0, 0)
        self._bind()

    # -- plumbing ----------------------------------------------------------

    def _bind(self) -> None:
        from ..native import jpeg as native_jpeg

        if not native_jpeg.native_available():
            raise RuntimeError(
                "device_decode needs the native coefficient extractor and "
                "LDT_DISABLE_NATIVE switches it off"
            )
        self._native = native_jpeg
        reg = default_registry()
        self._entropy_ms = reg.histogram("decode_entropy_ms")
        self._coeff_bytes = reg.counter("decode_coeff_bytes_total")
        self._reencodes = reg.counter("decode_coeff_reencode_total")
        self._undecodable = reg.counter("decode_coeff_undecodable_total")

    # Picklable for process-pool workers: the ctypes binding and the
    # BufferPool are process-local; each worker re-binds its own
    # (data/workers._init_worker re-attaches the pool).
    def __getstate__(self):
        state = dict(self.__dict__)
        for key in ("_native", "_entropy_ms", "_coeff_bytes", "_reencodes",
                    "_undecodable"):
            state.pop(key, None)
        state["buffer_pool"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._bind()

    @property
    def required_columns(self) -> list[str]:
        cols = [self.image_column]
        if self.label_column is not None:
            cols.append(self.label_column)
        return cols

    def cache_fingerprint(self) -> str:
        """Batch-cache identity (``data/cache.py``). ``chunk_blocks`` is
        included because the grid rounding shapes the PAGE bytes (not the
        decoded image): an autotuner ``coeff_chunk`` actuation therefore
        changes the key space and old entries simply stop hitting —
        capacity moved, content never aliased."""
        return (
            f"CoeffImageDecoder/{self.image_size}/{self.image_column}/"
            f"{self.label_column}/chunk={self.chunk_blocks}"
        )

    # -- autotune surface --------------------------------------------------

    def set_chunk(self, blocks: int) -> int:
        """Autotune actuator: the canonical-grid rounding granularity, in
        8×8 blocks. Takes effect on the next grid growth; the current grid
        never shrinks (shrinking would recompile the kernel and churn the
        page keys for zero content change)."""
        blocks = max(1, int(blocks))
        self.chunk_blocks = blocks  # ldt: ignore[LDT1002] -- atomic int swap; readers take any recent value
        return blocks

    def tunables(self):
        from ..tune.tunable import Tunable

        return [Tunable(
            "coeff_chunk",
            lambda: self.chunk_blocks,
            self.set_chunk,
            lo=1, hi=16,
            doc="coefficient-page grid rounding, in 8x8 blocks (coarser = "
                "fewer kernel recompiles / warmer pages, more padding "
                "bytes on the wire). In-process decode only: WorkerPool "
                "workers hold pickled decoder copies made at spawn, so an "
                "actuation there lands on the next respawn, not live",
        )]

    # -- page management ---------------------------------------------------

    def _ensure_grid(self, yb_h: int, yb_w: int) -> tuple[int, int, int, int]:
        gh, gw = self._grid
        if yb_h > gh or yb_w > gw:
            gh = max(gh, _round_up(yb_h, self.chunk_blocks))
            gw = max(gw, _round_up(yb_w, self.chunk_blocks))
            self._grid = (gh, gw)  # ldt: ignore[LDT1002] -- monotonic grow; producer threads tolerate either grid
        return gh, gw, (gh + 1) // 2, (gw + 1) // 2

    def _lease(self, shape, dtype) -> np.ndarray:
        if self.buffer_pool is not None:
            arr = self.buffer_pool.lease(shape, dtype)
            try:
                # The extractor's contract: pages arrive ZEROED (padding
                # blocks are never written), and recycled pool pages carry
                # old batches.
                arr.fill(0)
            except BaseException:
                self.buffer_pool.release(arr)
                raise
            return arr
        arr = np.empty(shape, dtype)
        arr.fill(0)
        return arr

    # -- decode ------------------------------------------------------------

    def _reencode(self, payload: bytes) -> Optional[bytes]:
        """Tolerant path for rows the extractor refuses: PIL decode,
        re-encode as baseline 4:2:0 JPEG (quality 95 bounds the
        requantisation error), extract from that."""
        from PIL import Image

        try:
            img = Image.open(io.BytesIO(payload))
            if img.mode != "RGB":
                img = img.convert("RGB")
            buf = io.BytesIO()
            img.save(buf, format="JPEG", quality=95, subsampling=2)
            return buf.getvalue()
        except Exception:
            return None

    def _payload(self, source, i: int) -> Optional[bytes]:
        if isinstance(source, list):
            return source[i]
        return source[int(i)].as_py()

    def _extract(self, pointers, source) -> dict[str, np.ndarray]:
        """``pointers`` from payload_pointers/arrow_pointers; ``source``
        (the payload list or arrow array) is only touched on the per-row
        re-encode fallback. This is the content-assembly core — a pure
        function of the payload bytes (LDT1301 content path); timing and
        byte counters live in the callers."""
        native = self._native
        n = pointers[2]
        if n == 0:
            gh, gw, ch, cw = self._ensure_grid(1, 1)
            return {
                "jpeg_coef_y": np.zeros((0, gh, gw, 64), np.int16),
                "jpeg_coef_cb": np.zeros((0, ch, cw, 64), np.int16),
                "jpeg_coef_cr": np.zeros((0, ch, cw, 64), np.int16),
                "jpeg_quant": np.zeros((0, 3, 64), np.int32),
                "jpeg_geom": np.zeros((0, 6), np.int32),
            }
        geom, probe_failed = native.batch_probe_jpeg(pointers)
        replaced: dict[int, bytes] = {}
        for i in np.nonzero(probe_failed | (geom[:, 3] == 0))[0]:
            alt = self._reencode(self._payload(source, int(i)))
            if alt is not None:
                self._reencodes.inc()
                replaced[int(i)] = alt
                ag, af = native.batch_probe_jpeg(
                    native.payload_pointers([alt])
                )
                if not af[0]:
                    geom[int(i)] = ag[0]
        yb_h = int(max(1, ((geom[:, 1].max() + 7) // 8)))
        yb_w = int(max(1, ((geom[:, 0].max() + 7) // 8)))
        gh, gw, ch, cw = self._ensure_grid(yb_h, yb_w)
        # Lease the five pages one by one into the dict, with the whole
        # sequence under the release guard: a later lease that raises
        # (pool allocation failure) must not strand the earlier pages —
        # the same LDT1201 exception-edge class the extractor call below
        # is guarded against.
        batch: dict[str, np.ndarray] = {}
        try:
            batch["jpeg_coef_y"] = self._lease((n, gh, gw, 64), np.int16)
            batch["jpeg_coef_cb"] = self._lease((n, ch, cw, 64), np.int16)
            batch["jpeg_coef_cr"] = self._lease((n, ch, cw, 64), np.int16)
            batch["jpeg_quant"] = self._lease((n, 3, 64), np.int32)
            batch["jpeg_geom"] = self._lease((n, 6), np.int32)
            if replaced:
                # Patch ONLY the re-encoded rows' pointer/length slots in
                # place — the untouched rows keep their zero-copy Arrow
                # pointers (ctypes retains the assigned bytes in the
                # array's object table; `replaced` also stays live for the
                # duration of the call).
                srcs, lens, _, keepalive = pointers
                for i, alt in replaced.items():
                    srcs[i] = alt
                    lens[i] = len(alt)
                pointers = (srcs, lens, n, (keepalive, replaced))
            failed = native.batch_extract_coeffs(
                pointers, gh, gw, ch, cw,
                batch["jpeg_coef_y"], batch["jpeg_coef_cb"],
                batch["jpeg_coef_cr"], batch["jpeg_quant"],
                batch["jpeg_geom"], n_threads=self.n_threads,
            )
            if failed.any():
                # Rows that still fail keep a zeroed page → neutral gray
                # (the pixel path's zero-fill contract for undecodable
                # rows). Re-zero: the failed extractor may have written a
                # partial block row.
                for i in np.nonzero(failed)[0]:
                    i = int(i)
                    self._undecodable.inc()
                    batch["jpeg_coef_y"][i].fill(0)
                    batch["jpeg_coef_cb"][i].fill(0)
                    batch["jpeg_coef_cr"][i].fill(0)
                    batch["jpeg_quant"][i].fill(1)
                    # Zero geometry: the kernel clamps extents to >= 1 and
                    # samples pixel (0, 0) of the zeroed (gray) page.
                    batch["jpeg_geom"][i].fill(0)
        except BaseException:
            # Exception edge (LDT1201): the leased pages must not strand.
            if self.buffer_pool is not None:
                self.buffer_pool.release_batch(batch)
            raise
        return batch

    def _observed(self, pointers, source) -> dict[str, np.ndarray]:
        """Run the extraction core with its telemetry: per-batch host
        entropy time (decode_entropy_ms — the only decode work left on the
        CPU) and the coefficient-byte counter the wire-traffic trade is
        judged by."""
        t0 = time.monotonic_ns()
        reenc_before = self._reencodes.value
        batch = self._extract(pointers, source)
        entropy_ms = (time.monotonic_ns() - t0) / 1e6
        self._entropy_ms.observe(entropy_ms)
        self._coeff_bytes.inc(sum(v.nbytes for v in batch.values()))
        # Cost-ledger hand-off: lands on the enclosing cost_context (the
        # server's per-item decode scope) when one is open on this thread;
        # a free-standing decode (tests, worker subprocess) drops it.
        note_cost(
            entropy_ms=round(entropy_ms, 3),
            reencode=self._reencodes.value > reenc_before,
        )
        return batch

    def decode_payloads(self, payloads: list[bytes]) -> dict[str, np.ndarray]:
        """JPEG byte strings → coefficient-page dict (the folder-tree and
        tolerant-retry entry point)."""
        return self._observed(self._native.payload_pointers(payloads),
                              payloads)

    def decode_column(self, col) -> dict[str, np.ndarray]:
        """Arrow (chunked) binary column → coefficient-page dict, pointer
        arrays built straight over the Arrow buffers (no per-row Python
        bytes on the happy path)."""
        if isinstance(col, pa.ChunkedArray):
            col = col.combine_chunks()
        if not (pa.types.is_binary(col.type)
                or pa.types.is_large_binary(col.type)):
            raise TypeError(
                f"image column must be binary, got {col.type}"
            )
        return self._observed(self._native.arrow_pointers(col), col)

    def __call__(
        self, batch: Union[pa.RecordBatch, pa.Table]
    ) -> dict[str, np.ndarray]:
        out = self.decode_column(batch.column(self.image_column))
        if self.label_column is not None:
            out["label"] = np.asarray(
                batch.column(self.label_column).to_numpy(
                    zero_copy_only=False
                ),
                dtype=np.int32,
            )
        return out
