"""An RMSNorm a head under a SiLU gate: what a Gated DeltaNet
(arXiv:2412.06464) does to its rule's output ahead of the output projection.

    y = o / sqrt(mean(o^2) + eps) * scale * silu(z)          a head's ``d``

with ``o`` ``[B, S, H, d]`` in the layer's type, ``scale`` ``[d]`` f32 (one
for every head) and ``z`` the last ``H * d`` columns of ``[B, S, W]`` (a
fused projection, or the gate alone); the statistics, the scale and the SiLU
in float32 whatever the operands' type, one cast to ``dtype`` at the end,
``[B, S, H * d]``. Two forms:

* :func:`gated_rms_norm_plain`, the ``jax.numpy`` lines the layer had: XLA
  makes float32 copies of ``o`` and ``z`` and some three passes each way;
  what runs off the TPU and what the kernels are held to;
* :func:`norm_kernel`, a Pallas kernel pair with its own differentiation
  rule, one pass over HBM each way, on :mod:`.conv`'s grid (row, channel
  block, sequence block) with a loop over ``STEP_ROWS`` tokens and of them
  one head after the other, so a step's arithmetic stays in registers.
  Forward: cast, square, a lane sum, ``rsqrt``, the scale, the SiLU, one
  cast, one store. Backward: it keeps ``o``, ``z`` and ``scale`` and nothing
  else, makes the statistics again, writes ``do`` and ``dz`` once each in
  the operands' types and sums ``d scale`` in f32 over a row's sequence
  blocks in VMEM (summed over rows, sublanes and heads outside). ``z`` is
  read where it lies: the block specification walks the last columns of the
  wider array, and no slice is written out in front of the kernel.

:func:`gated_rms_norm` chooses between them from the platform and the shapes
(:func:`norm_fused_applies`), as :mod:`.conv`, :mod:`.scan` and :mod:`.delta`
do: no flag.

A Mamba-2 layer's gated norm (Granite 4.0-H's) is another function of the
same three arrays, :func:`gate_then_rms_norm`: the gate is inside the
statistic and the group is the whole row,

    y = scale * g / sqrt(mean(g^2) + eps),   g = o * silu(z)     all ``H * d``

so neither kernel above computes it (theirs norm a head's ``d`` and gate
afterwards). It has the plain form alone, on the chip too: a row of 4,096
columns is 32 lane groups whose sum of squares a kernel would have to finish
before it scales any of them, two passes over the tile where the head-wise
norm makes one.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .conv import _block
from .scan import _pallas  # the same grid: (row, channel block, sequence)

__all__ = ["gated_rms_norm", "gated_rms_norm_plain", "gate_then_rms_norm",
           "norm_kernel", "norm_fused_applies", "BLOCK_S", "BLOCK_D",
           "STEP_ROWS"]

# the tile and the step inside it, timed on the v5e (PERF.md section 6, PR 48)
BLOCK_S = 1024  # tokens a grid step holds
BLOCK_D = 1024  # channels a grid step holds: whole heads
STEP_ROWS = 64  # tokens a loop step inside a tile takes, a head at a time
_LANES = 128
_ROWS = 8  # sublanes of a float32 tile
_F32 = jnp.float32


def gated_rms_norm_plain(o, z, scale, *, eps: float = 1e-6, dtype=None):
    """The plain form: ``o`` [B, S, H, d], ``z`` [B, S, W] whose last ``H *
    d`` columns are the gate, ``scale`` [d]; [B, S, H * d] in ``dtype``
    (``o``'s if None)."""
    rows, seq, heads, d = o.shape
    dtype = dtype or o.dtype
    o = o.astype(_F32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * scale
    z = z[..., z.shape[2] - heads * d:].reshape(rows, seq, heads, d)
    return (o * jax.nn.silu(z.astype(_F32))).astype(dtype).reshape(
        rows, seq, heads * d)


def gate_then_rms_norm(o, z, scale, *, eps: float = 1e-5, dtype=None):
    """``scale * rmsnorm(o * silu(z))`` over all of a token's columns: ``o``
    [B, S, W] (or [B, S, H, d], flattened), ``z`` [B, S, W'] whose last ``W``
    columns are the gate, ``scale`` [W]; the gate, the statistic and the
    scale in float32, [B, S, W] in ``dtype`` (``o``'s if None). Plain
    ``jax.numpy``, everywhere."""
    dtype = dtype or o.dtype
    o = o.reshape(*o.shape[:2], -1).astype(_F32)
    z = z[..., z.shape[2] - o.shape[2]:].astype(_F32)
    gated = o * jax.nn.silu(z)
    return (gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True)
                                  + eps) * scale).astype(dtype)


def _heads(width, d):
    """A tile's heads, each a slice of its lanes."""
    return [slice(h * d, (h + 1) * d) for h in range(width // d)]


def _normed(o, eps):
    """``(o / sqrt(mean(o^2) + eps), 1 / sqrt(.))`` for a head's rows ``[n,
    d]`` f32."""
    r = jax.lax.rsqrt(jnp.mean(o * o, 1, keepdims=True) + eps)
    return o * r, r


def _fwd_kernel(o_ref, z_ref, scale_ref, y_ref, *, d, eps, rows):
    from jax.experimental import pallas as pl

    scale = scale_ref[...]  # [1, d]

    @pl.loop(0, o_ref.shape[1] // rows)
    def _(i):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for lanes in _heads(o_ref.shape[2], d):
            normed, _ = _normed(o_ref[0, at, lanes].astype(_F32), eps)
            z = z_ref[0, at, lanes].astype(_F32)
            y_ref[0, at, lanes] = (normed * scale * (z * jax.nn.sigmoid(z))
                                   ).astype(y_ref.dtype)


def _bwd_kernel(o_ref, z_ref, scale_ref, dy_ref, do_ref, dz_ref, sums_ref, *,
                d, eps, rows):
    from jax.experimental import pallas as pl

    scale = scale_ref[...]

    @pl.when(pl.program_id(2) == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    @pl.loop(0, o_ref.shape[1] // rows)
    def _(i):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for lanes in _heads(o_ref.shape[2], d):
            normed, r = _normed(o_ref[0, at, lanes].astype(_F32), eps)
            z = z_ref[0, at, lanes].astype(_F32)
            dy = dy_ref[0, at, lanes].astype(_F32)
            sig = jax.nn.sigmoid(z)
            dz_ref[0, at, lanes] = (
                dy * (normed * scale) * (sig * (1.0 + z * (1.0 - sig)))
            ).astype(dz_ref.dtype)
            d_scaled = dy * (z * sig)  # the cotangent of normed * scale
            d_normed = d_scaled * scale
            do_ref[0, at, lanes] = (r * (d_normed - normed * jnp.mean(
                d_normed * normed, 1, keepdims=True))).astype(do_ref.dtype)
            # whole tiles added, no shuffle: [n, d] -> [8, d]
            sums_ref[0, :, lanes] += (d_scaled * normed).reshape(
                rows // _ROWS, _ROWS, d).sum(0)


def _tiles(values, wide, block_s, block_d):
    """The block specifications of ``o``'s tile and of ``z``'s, the same
    tile ``wide - values`` columns on."""
    from jax.experimental import pallas as pl

    ahead = (wide - values) // block_d
    return (pl.BlockSpec((1, block_s, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, block_s, block_d),
                         lambda i, j, k: (i, k, ahead + j)))


def _scale_spec(d):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, d), lambda i, j, k: (0, 0))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _norm_forward(o, z, scale, eps, dtype, tile):
    from jax.experimental import pallas as pl

    rows, seq, values = o.shape
    d = scale.shape[0]
    block_s, block_d, step_rows = tile
    tile, gate = _tiles(values, z.shape[2], block_s, block_d)
    return _pallas(
        functools.partial(_fwd_kernel, d=d, eps=eps, rows=step_rows),
        (rows, values // block_d, seq // block_s),
        [tile, gate, _scale_spec(d)], tile,
        jax.ShapeDtypeStruct((rows, seq, values), dtype), [],
        "gated_rms_norm_fwd",
        pl.CostEstimate(
            flops=12 * rows * seq * values,
            transcendentals=rows * seq * values,
            bytes_accessed=rows * seq * values * (
                o.dtype.itemsize + z.dtype.itemsize
                + jnp.dtype(dtype).itemsize)),
    )(o, z, scale.astype(_F32).reshape(1, d))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _norm_backward(o, z, scale, dy, eps, tile):
    from jax.experimental import pallas as pl

    rows, seq, values = o.shape
    d = scale.shape[0]
    block_s, block_d, step_rows = tile
    tile, gate = _tiles(values, z.shape[2], block_s, block_d)
    return _pallas(
        functools.partial(_bwd_kernel, d=d, eps=eps, rows=step_rows),
        (rows, values // block_d, seq // block_s),
        [tile, gate, _scale_spec(d), tile],
        [tile, tile, pl.BlockSpec((1, _ROWS, block_d),
                                  lambda i, j, k: (i, 0, j))],
        [jax.ShapeDtypeStruct((rows, seq, values), o.dtype),
         jax.ShapeDtypeStruct((rows, seq, values), z.dtype),
         jax.ShapeDtypeStruct((rows, _ROWS, values), _F32)], [],
        "gated_rms_norm_bwd",
        pl.CostEstimate(
            flops=30 * rows * seq * values,
            transcendentals=rows * seq * values,
            bytes_accessed=rows * seq * values * (
                2 * o.dtype.itemsize + 2 * z.dtype.itemsize
                + dy.dtype.itemsize)),
    )(o, z, scale.astype(_F32).reshape(1, d), dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gated_norm(o, z, scale, eps, dtype, tile):
    return _norm_forward(o, z, scale, eps, dtype, tile)


def _gated_norm_fwd(o, z, scale, eps, dtype, tile):
    return _norm_forward(o, z, scale, eps, dtype, tile), (o, z, scale)


def _gated_norm_bwd(eps, dtype, tile, residuals, dy):
    o, z, scale = residuals
    do, dz, sums = _norm_backward(o, z, scale, dy, eps, tile)
    # the columns of a wider z that the norm never read
    dz = jnp.pad(dz, ((0, 0), (0, 0), (z.shape[2] - o.shape[2], 0)))
    d_scale = sums.sum((0, 1)).reshape(-1, scale.shape[0]).sum(0)
    return do, dz, d_scale.astype(scale.dtype)


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def norm_kernel(o, z, scale, *, eps: float = 1e-6, dtype=None,
                block_s: int = BLOCK_S, block_d: int = BLOCK_D,
                step_rows: int = STEP_ROWS):
    """The gated norm as the Pallas kernel pair (``S`` in whole tiles of
    eight rows, ``d`` in whole 128-lane groups, ``z``'s gate starting at a
    whole channel block of its array); differentiable, by its own rule."""
    rows, seq, heads, d = o.shape
    values, wide = heads * d, z.shape[2]
    if seq % _ROWS or d % _LANES or wide < values or wide % d:
        raise ValueError(
            f"the gated norm's kernel takes rows in whole tiles of {_ROWS} "
            f"tokens and heads in whole groups of {_LANES} lanes, the gate "
            f"behind whole heads' columns; got {seq} tokens, heads of {d} "
            f"and a gate in {wide} columns")
    # whole heads that tile the values and the columns ahead of the gate
    block_d = _block(math.gcd(values, wide - values), block_d, d)
    block_s = _block(seq, block_s, _LANES, _ROWS)
    return _gated_norm(
        o.reshape(rows, seq, values), z, scale, float(eps),
        jnp.dtype(dtype or o.dtype),
        (block_s, block_d, math.gcd(step_rows, block_s)))


def norm_fused_applies(seq: int, heads: int, d: int, mesh=None,
                       platform: Optional[str] = None) -> bool:
    """The rule by which a layer's gated norm runs the kernel pair: on a
    TPU, a row in whole sequence tiles (of 128 tokens at least), heads of
    whole lane groups, over one device or a mesh of one (XLA cannot
    partition a Mosaic call). Everything else is the plain form."""
    del heads  # any number: a block takes a divisor of them
    if (platform or jax.default_backend()) != "tpu":
        return False
    if seq % _LANES or d % _LANES:
        return False
    if mesh is not None and mesh.size > 1:
        return False
    return jax.device_count() == 1


def gated_rms_norm(o, z, scale, *, eps: float = 1e-6, dtype=None):
    """``rmsnorm(o) * scale * silu(z)`` a head, cast to ``dtype`` (``o``'s
    if None), ``[B, S, H * d]``: ``o`` ``[B, S, H, d]``, and ``z`` ``[B, S,
    W]`` may be a fused projection whose last ``H * d`` columns are the
    gate, read where they lie where the kernel runs. By the kernel pair
    where :func:`norm_fused_applies` says so for these shapes; elsewhere
    :func:`gated_rms_norm_plain` to the letter."""
    rows, seq, heads, d = o.shape
    if norm_fused_applies(seq, heads, d):
        if z.shape[2] % d:  # a ragged projection: the slice written out
            z = z[..., z.shape[2] - heads * d:]
        return norm_kernel(o, z, scale, eps=eps, dtype=dtype)
    return gated_rms_norm_plain(o, z, scale, eps=eps, dtype=dtype)
