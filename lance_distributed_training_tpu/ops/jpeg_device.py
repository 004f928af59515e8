"""Jitted columnar JPEG back-half — the device side of the entropy split.

The host half (``native/ldt_decode.cpp`` ABI v3 via
``data/device_decode.py``) stops at the entropy boundary: Huffman decode,
DC prediction and de-zigzag — the only inherently sequential work in a
JPEG — and ships **half-decoded coefficient pages** (quantized DCT blocks
+ quant tables + per-image geometry, padded to a canonical grid). This
module is everything after that boundary as ONE pure jitted kernel:

    dequant → 8×8 IDCT → chroma upsample → YCbCr→RGB → resize(S) → stack

Design constraints (pinned by LDT101/LDT1301 — the module is listed under
``[tool.ldt-check]`` hot-paths AND content-paths):

* **pure jit** — no host callbacks, no host syncs, no I/O; the identical
  code path runs on CPU today and a real TPU unmodified;
* **integer-exact** — every stage is int32 fixed-point arithmetic
  (libjpeg's own constants where one exists), so the device arm is
  bit-deterministic across runs and backends: the same coefficient page
  always yields the same bytes;
* **batched** — the IDCT is one einsum over ``[N, BH, BW, 8, 8]`` blocks,
  which is what makes the dense half worth moving: XLA vectorises it
  across the whole batch where libjpeg walks blocks scalar-by-scalar.

Numerical parity with the host (``--no_device_decode``) arm: the chroma
upsample mirrors libjpeg's non-fancy h2v2 replicate, the color convert
uses jdcolor's exact 16.16 constants, and the resize mirrors
``native/ldt_decode.cpp::resize_bilinear``'s 16.16 fixed-point sampling
(with one weight-product truncated to keep intermediates in int32 —
worst-case ±2 levels vs the native C). The remaining deltas come from the
IDCT method (libjpeg decodes with JDCT_IFAST; this kernel uses an
11-bit-scaled exact-basis IDCT) and accumulate through the bilinear mix —
:data:`HOST_PARITY_MAX_ABS_DIFF` pins the observed envelope and the tests/
bench record the measured value next to it.

Coefficient-batch layout (produced by ``data/device_decode.py``)::

    jpeg_coef_y  : int16 [N, YBH, YBW, 64]   natural-order quantized blocks
    jpeg_coef_cb : int16 [N, CBH, CBW, 64]   canonical 4:2:0 chroma grid
    jpeg_coef_cr : int16 [N, CBH, CBW, 64]   (all-zero for grayscale rows)
    jpeg_quant   : int32 [N, 3, 64]          per-component dequant tables
    jpeg_geom    : int32 [N, 6]              w, h, yb_w, yb_h, cb_w, cb_h

Padding blocks are zero; a zero block dequantises to a flat 128 after the
level shift, so padded regions decode to neutral gray and the per-image
resize never samples them (it clamps to ``w-1``/``h-1``).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "COEFF_KEYS",
    "HOST_PARITY_MAX_ABS_DIFF",
    "decode_coeff_batch",
    "make_coeff_decode_fn",
    "make_batch_transform",
    "is_coeff_batch",
]

# The keys a coefficient batch carries instead of "image". Everything else
# in the batch dict (label, _weight, token columns) passes through the
# transform untouched.
COEFF_KEYS = (
    "jpeg_coef_y",
    "jpeg_coef_cb",
    "jpeg_coef_cr",
    "jpeg_quant",
    "jpeg_geom",
)

# Pinned host-vs-device parity envelope (max abs u8 difference) on the
# canonical corpora (tests/test_device_decode.py, scripts/
# device_decode_smoke.py): sources below the DCT
# draft threshold (< 2× target on both dims), so the host arm decodes at
# full scale and the two arms differ only in IDCT method, one truncated
# resize weight product, and the PIL-retry rows' requantisation.
HOST_PARITY_MAX_ABS_DIFF = 16

# 8-point DCT-III basis, 11-bit fixed point: B[x, u] = c(u)/2 ·
# cos((2x+1)uπ/16), the exact orthonormal basis libjpeg's jpeg_idct_islow
# approximates. Computed once in float64 at import — a pure constant, so
# the kernel stays bit-deterministic.
_x = np.arange(8)
_B = np.cos((2 * _x[:, None] + 1) * _x[None, :] * np.pi / 16) * np.where(
    _x[None, :] == 0, np.sqrt(1 / 8), np.sqrt(2 / 8)
)
IDCT_BASIS_FIX = np.round(_B * 2048.0).astype(np.int32)  # [x, u]
del _x, _B

# jdcolor's 16.16 fixed-point YCbCr→RGB constants (FIX(x) = round(x·65536)).
_FIX_1_40200 = 91881
_FIX_1_77200 = 116130
_FIX_0_34414 = 22554
_FIX_0_71414 = 46802
_ONE_HALF = 32768


def _idct_plane(coef: jax.Array, quant: jax.Array) -> jax.Array:
    """Quantized natural-order blocks ``[N, BH, BW, 64] i16`` + per-image
    dequant table ``[N, 64] i32`` → clipped pixel plane ``[N, BH·8, BW·8]``
    int32 in [0, 255].

    Fixed-point two-pass IDCT: each pass multiplies by the 11-bit basis and
    descales with round-half-up. Intermediates stay well inside int32 for
    any coefficients a valid JPEG can carry (|dequantised| ≤ ~2^15 · basis
    2^11 · 8 terms < 2^29)."""
    n, bh, bw = coef.shape[0], coef.shape[1], coef.shape[2]
    c = coef.astype(jnp.int32) * quant[:, None, None, :]
    c = c.reshape(n, bh, bw, 8, 8)
    b = jnp.asarray(IDCT_BASIS_FIX)
    # s1[u, y] = Σ_v C[u, v] · B[y, v]   (columns pass)
    s1 = jnp.einsum("nhwuv,yv->nhwuy", c, b)
    s1 = (s1 + 1024) >> 11
    # p[x, y] = Σ_u B[x, u] · s1[u, y]   (rows pass)
    p = jnp.einsum("xu,nhwuy->nhwxy", b, s1)
    p = ((p + 1024) >> 11) + 128
    p = jnp.clip(p, 0, 255)
    # [N, BH, BW, 8, 8] → [N, BH·8, BW·8]
    return p.transpose(0, 1, 3, 2, 4).reshape(n, bh * 8, bw * 8)


def _upsample_h2v2(plane: jax.Array, yh: int, yw: int) -> jax.Array:
    """libjpeg non-fancy h2v2 upsample: replicate each chroma sample 2×2,
    cropped to the luma plane's padded size."""
    up = jnp.repeat(jnp.repeat(plane, 2, axis=1), 2, axis=2)
    return up[:, :yh, :yw]


def _ycc_to_rgb(y: jax.Array, cb: jax.Array, cr: jax.Array) -> jax.Array:
    """jdcolor's exact integer conversion; inputs int32 [N, H, W] in
    [0, 255], output int32 [N, H, W, 3] clipped to [0, 255]."""
    cb = cb - 128
    cr = cr - 128
    r = y + ((_FIX_1_40200 * cr + _ONE_HALF) >> 16)
    b = y + ((_FIX_1_77200 * cb + _ONE_HALF) >> 16)
    g = y - ((_FIX_0_34414 * cb + _FIX_0_71414 * cr + _ONE_HALF) >> 16)
    return jnp.clip(jnp.stack([r, g, b], axis=-1), 0, 255)


def _axis_samples(size: jax.Array, out_size: int):
    """Native ``resize_bilinear``'s 16.16 source sampling for one axis:
    per-image ``(idx0 [N, S], idx1 [N, S], weight [N, S])``. ``size`` is the
    per-image real extent (int32 [N]), clamped ≥ 1 so zeroed geometry
    (a failed row's page) degrades to sampling pixel 0."""
    size = jnp.maximum(size, 1)
    ratio = ((size - 1) << 16) // (out_size - 1 if out_size > 1 else 1)
    fix = jnp.arange(out_size, dtype=jnp.int32)[None, :] * ratio[:, None]
    idx0 = fix >> 16
    weight = fix & 0xFFFF
    idx1 = jnp.minimum(idx0 + 1, size[:, None] - 1)
    return idx0, idx1, weight


def _resize_one(img, sy0, sy1, wy, sx0, sx1, wx):
    """One image ``[H, W, 3] i32`` → ``[S, S, 3] i32`` by 16.16
    fixed-point bilinear (vmapped over the batch), vertical pass first:
    ``v = r0 + ((r1 - r0)·wy) >> 16`` stays exactly inside int32
    (|r1 - r0| ≤ 2^9, wy < 2^16), then the horizontal mix on the reduced
    ``[S, W]`` plane the same way — every intermediate is an exact
    integer, so the resize is bit-deterministic by construction. The
    native C (``resize_bilinear``) mixes horizontally first in one 48-bit
    expression; the different rounding order costs at most ±1 level
    against it, inside the pinned parity envelope."""
    r0 = img[sy0]  # [S, W, 3]
    r1 = img[sy1]
    v = r0 + (((r1 - r0) * wy[:, None, None]) >> 16)  # vertical mix
    v0, v1 = v[:, sx0], v[:, sx1]  # [S, S, 3]
    return v0 + (((v1 - v0) * wx[None, :, None]) >> 16)


@partial(jax.jit, static_argnames=("out_size",))
@jax.named_scope("device_decode")  # metadata only: names it in a device trace
def decode_coeff_batch(
    coef_y: jax.Array,
    coef_cb: jax.Array,
    coef_cr: jax.Array,
    quant: jax.Array,
    geom: jax.Array,
    *,
    out_size: int = 224,
) -> jax.Array:
    """Coefficient pages → ``uint8 [N, S, S, 3]`` RGB batch, fully on
    device. Pure function of its inputs — no host callbacks — and integer
    throughout, so repeated runs are bit-identical."""
    yh, yw = coef_y.shape[1] * 8, coef_y.shape[2] * 8
    y = _idct_plane(coef_y, quant[:, 0])
    cb = _idct_plane(coef_cb, quant[:, 1])
    cr = _idct_plane(coef_cr, quant[:, 2])
    rgb = _ycc_to_rgb(y, _upsample_h2v2(cb, yh, yw), _upsample_h2v2(cr, yh, yw))
    w = geom[:, 0]
    h = geom[:, 1]
    sx0, sx1, wx = _axis_samples(w, out_size)
    sy0, sy1, wy = _axis_samples(h, out_size)
    out = jax.vmap(_resize_one)(rgb, sy0, sy1, wy, sx0, sx1, wx)
    return out.astype(jnp.uint8)


def make_coeff_decode_fn(out_size: int = 224):
    """The kernel bound to one output size: ``fn(coeff_batch_dict) → u8
    [N, S, S, 3]``. Jit-cached per (out_size, page geometry)."""

    def decode(batch) -> jax.Array:
        return decode_coeff_batch(
            batch["jpeg_coef_y"],
            batch["jpeg_coef_cb"],
            batch["jpeg_coef_cr"],
            batch["jpeg_quant"],
            batch["jpeg_geom"],
            out_size=out_size,
        )

    return decode


def is_coeff_batch(batch) -> bool:
    """Does this batch carry coefficient pages instead of pixels?"""
    return "jpeg_coef_y" in batch


def make_batch_transform(out_size: int = 224):
    """The trainer's device-side transform stage: a jittable function that
    replaces a coefficient batch's ``jpeg_*`` leaves with the decoded
    ``image`` and passes every other leaf (label, ``_weight``, token
    columns) through untouched. Pixel batches (the ``--no_device_decode``
    arm) pass through whole, so one transform handle serves both arms. The downstream normalize/augment
    (:mod:`.image`, inside the task's jitted step) consumes the result
    exactly as it consumes a host-decoded batch."""
    decode = make_coeff_decode_fn(out_size)

    def transform(batch):
        if not is_coeff_batch(batch):
            return batch
        out = {k: v for k, v in batch.items() if k not in COEFF_KEYS}
        out["image"] = decode(batch)
        return out

    return transform


# Compile-witness funnel: when the sanitizer env flag is set at import time
# the decode kernel records every invocation's abstract signature under its
# def site (recovered via __wrapped__), so `ldt check --compile-witness` can
# corroborate or prune LDT1703 hazards on the decode path.
from ..utils import compiletrack  # noqa: E402 — deliberate bottom import

if compiletrack.enabled():
    decode_coeff_batch = compiletrack.wrap_jit(decode_coeff_batch)
