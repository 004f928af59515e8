"""A depthwise causal convolution along the sequence and the SiLU behind it:
what a Mamba layer (arXiv:2312.00752) and a Gated DeltaNet (arXiv:2412.06464)
do to their projections before the recurrence.

    y_t = silu(sum_j x_{t - (K - 1) + j} * taps_j [+ bias])      x_t = 0, t < 0

per row and channel, with ``x`` ``[B, S, D]`` in the layer's type, ``taps``
``[K, D]`` f32 (``taps[K - 1]`` this token's, ``taps[0]`` the token ``K - 1``
before: a ``Conv1d`` with ``groups = D`` and ``K - 1`` zeros before the row),
``bias`` ``[D]`` f32 or None; the products, the sums, the bias and the SiLU in
float32 whatever ``x``'s type, one cast to ``dtype`` at the end. Nothing here
knows where a document ends inside a row. Two forms:

* :func:`causal_depthwise_conv`, plain ``jax.numpy`` (the sums alone, f32):
  a padded copy of the row and ``K`` shifted slices of it, which XLA makes
  some ten passes over HBM of, its derivative included; what runs off the
  TPU, what the tests and the benchmark's reference read, and what the
  kernels are held to;
* :func:`conv_kernel`, a Pallas kernel pair with its own differentiation
  rule, one pass over HBM each way. Grid (row, channel block, sequence
  block), the sequence innermost and in order; inside a ``[block_s,
  block_d]`` tile a loop takes ``STEP_ROWS`` tokens at a time and of them one
  group of ``STEP_LANES`` channels after the other, so a step's arithmetic
  stays in registers. Forward: the step's rows stacked under the eight rows
  before them (carried in VMEM from the step and the sequence block before;
  zeros at a row's start) are rolled ``K - 1`` times down the sublanes;
  products, sums, bias, SiLU, one cast, one store; nothing f32 reaches HBM.
  Backward, the sequence blocks and the steps in reverse: it keeps ``x`` and
  nothing else, makes the pre-activation again (the rows before a tile read
  once more from ``x`` itself, a block of ``HALO`` rows), multiplies the
  cotangent by SiLU's derivative, rolls that *up* over the rows carried from
  the step after, writes ``dx`` once in ``x``'s type, and sums ``d taps`` and
  ``d bias`` in f32 over the sequence blocks in VMEM (written at a row's last
  block, summed over rows and sublanes outside). ``x`` may be the first
  ``D`` columns of a wider array (a fused projection): the block
  specification walks those columns where they lie, and no slice is written
  out in front of the kernel.

:func:`causal_conv_silu` chooses between them from the platform and the
shapes (:func:`conv_fused_applies`), as :mod:`.scan` and :mod:`.delta` do: no
flag.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .scan import _pallas  # the same grid: (row, channel block, sequence)

__all__ = ["causal_conv_silu", "causal_depthwise_conv", "conv_kernel",
           "conv_fused_applies", "BLOCK_S", "BLOCK_D", "STEP_ROWS",
           "STEP_LANES", "HALO"]

# the tile and the step inside it, timed on the v5e (PERF.md section 6, PR 43)
BLOCK_S = 1024  # tokens a grid step holds
BLOCK_D = 1024  # channels a grid step holds: eight 128-lane groups
STEP_ROWS = 64  # tokens a loop step inside a tile takes, and of them
STEP_LANES = 128  # the channels whose arithmetic is in registers at once
HALO = 16  # rows read of the block before: one bf16 tile's sublanes
_LANES = 128  # and the fewest tokens of a sequence tile the rule takes
_ROWS = 8  # sublanes of a float32 tile; taps and the bias's sum share one
_F32 = jnp.float32


def causal_depthwise_conv(x, taps, bias=None):
    """A depthwise causal convolution along the sequence, the plain form:
    ``x`` [B, S, D], ``taps`` [K, D] f32 with ``taps[K - 1]`` this token's
    and ``taps[0]`` the token ``K - 1`` before (a ``Conv1d`` with ``groups =
    D`` and ``K - 1`` zeros before the row), ``bias`` [D] or None; the sums
    in f32, [B, S, D] f32. Nothing here knows where a document ends inside a
    row. The layers call :func:`causal_conv_silu`, which is this and a SiLU
    everywhere but on one TPU device at whole tiles, where it is the kernel
    pair of this module."""
    k = taps.shape[0]
    back = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(back[:, j:j + x.shape[1]].astype(jnp.float32) * taps[j]
            for j in range(k))
    return y if bias is None else y + bias


def _pre_activation(before, x, taps, bias):
    """``(conv(x) [+ bias], shifted)`` for rows ``x`` [n, 128] f32 whose
    eight rows before are ``before``; ``shifted[j][t] = x[t - j]``, by a roll
    down the sublanes of the two stacked. The sums in the plain form's
    order, the oldest tap first."""
    from jax.experimental.pallas import tpu as pltpu

    k = len(taps)
    full = jnp.concatenate([before, x], 0)
    shifted = [x] + [pltpu.roll(full, j, 0)[_ROWS:] for j in range(1, k)]
    pre = shifted[k - 1] * taps[0]
    for j in range(1, k):
        pre = pre + shifted[k - 1 - j] * taps[j]
    return (pre if bias is None else pre + bias), shifted


def _lane_groups(taps_ref, bias_ref):
    """A tile's lane groups: ``(lanes, taps, bias)`` with each tap ``[1,
    STEP_LANES]``. The kernels walk a tile ``STEP_ROWS`` rows at a time and,
    inside such a step, one lane group after the other: what a group makes
    lives in registers, and the groups are independent chains the scheduler
    lays side by side. The same arithmetic written over the whole tile at
    once is bound by its loads and stores (PERF.md section 6, PR 43)."""
    width = math.gcd(STEP_LANES, taps_ref.shape[1])
    for g in range(taps_ref.shape[1] // width):
        lanes = slice(g * width, (g + 1) * width)
        yield (lanes, [taps_ref[j:j + 1, lanes]
                       for j in range(taps_ref.shape[0])],
               None if bias_ref is None else bias_ref[:, lanes])


def _fwd_kernel(*refs, has_bias, rows):
    from jax.experimental import pallas as pl

    x_ref, taps_ref = refs[:2]
    bias_ref = refs[2] if has_bias else None
    y_ref, tail_ref = refs[-2:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        tail_ref[...] = jnp.zeros_like(tail_ref)

    @pl.loop(0, x_ref.shape[1] // rows)
    def _(i):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for lanes, taps, bias in _lane_groups(taps_ref, bias_ref):
            x = x_ref[0, at, lanes].astype(_F32)
            pre, _ = _pre_activation(tail_ref[:, lanes], x, taps, bias)
            y_ref[0, at, lanes] = (pre * jax.nn.sigmoid(pre)).astype(
                y_ref.dtype)
            tail_ref[:, lanes] = x[rows - _ROWS:]


def _bwd_kernel(*refs, has_bias, rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x_ref, before_ref, dy_ref, taps_ref = refs[:4]
    bias_ref = refs[4] if has_bias else None
    dx_ref, sums_ref, head_ref = refs[-3:]
    k = taps_ref.shape[0]
    steps = x_ref.shape[1] // rows
    block, blocks = pl.program_id(2), pl.num_programs(2)

    @pl.when(block == 0)  # the row's last sequence block comes first
    def _():
        head_ref[...] = jnp.zeros_like(head_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def sublane_sums(t):  # [n, d] -> [8, d]: whole tiles added, no shuffle
        return t.reshape(rows // _ROWS, _ROWS, t.shape[1]).sum(0)

    def step(start, before_of):
        at = pl.ds(start, rows)
        for lanes, taps, bias in _lane_groups(taps_ref, bias_ref):
            x = x_ref[0, at, lanes].astype(_F32)
            pre, shifted = _pre_activation(
                before_of(lanes)[HALO - _ROWS:], x, taps, bias)
            sig = jax.nn.sigmoid(pre)
            dpre = dy_ref[0, at, lanes].astype(_F32) * (
                sig * (1.0 + pre * (1.0 - sig)))
            # dx_s = sum_j dpre_{s + (K - 1) - j} * taps_j: dpre moved *up*,
            # the rows after these being the head of the step after
            full = jnp.concatenate([dpre, head_ref[:, lanes]], 0)
            dx = dpre * taps[k - 1]
            for j in range(k - 1):
                ahead = pltpu.roll(full, rows + _ROWS - (k - 1 - j), 0)
                dx = dx + ahead[:rows] * taps[j]
            dx_ref[0, at, lanes] = dx.astype(dx_ref.dtype)
            head_ref[:, lanes] = dpre[:_ROWS]
            for j in range(k):
                sums_ref[0, j, :, lanes] += sublane_sums(
                    dpre * shifted[k - 1 - j])
            if has_bias:
                sums_ref[0, k, :, lanes] += sublane_sums(dpre)

    @pl.loop(0, steps - 1)  # the steps whose rows before are in the tile
    def _(i):
        start = pl.multiple_of((steps - 1 - i) * rows, rows)
        step(start, lambda lanes: x_ref[
            0, pl.ds(start - HALO, HALO), lanes].astype(_F32))

    step(0, lambda lanes: jnp.where(
        block == blocks - 1, 0.0, before_ref[0, :, lanes].astype(_F32)))


def _coefficients(taps, bias, block_d):
    """The taps (and the bias as one row) with their block specifications."""
    from jax.experimental import pallas as pl

    arrays = [taps.astype(_F32)]
    specs = [pl.BlockSpec((taps.shape[0], block_d), lambda i, j, k: (0, j))]
    if bias is not None:
        arrays.append(bias.astype(_F32).reshape(1, -1))
        specs.append(pl.BlockSpec((1, block_d), lambda i, j, k: (0, j)))
    return arrays, specs


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _conv_forward(x, taps, bias, dtype, block_s, block_d):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, seq, _ = x.shape
    taps_n, width = taps.shape
    tile = pl.BlockSpec((1, block_s, block_d), lambda i, j, k: (i, k, j))
    coefficients, specs = _coefficients(taps, bias, block_d)
    return _pallas(
        functools.partial(_fwd_kernel, has_bias=bias is not None,
                          rows=math.gcd(STEP_ROWS, block_s)),
        (rows, width // block_d, seq // block_s),
        [tile, *specs], tile,
        jax.ShapeDtypeStruct((rows, seq, width), dtype),
        [pltpu.VMEM((_ROWS, block_d), _F32)],
        "causal_conv_silu_fwd",
        pl.CostEstimate(
            flops=(2 * taps_n + 6) * rows * seq * width,
            transcendentals=2 * rows * seq * width,
            bytes_accessed=rows * seq * width * (
                x.dtype.itemsize + jnp.dtype(dtype).itemsize)),
    )(x, *coefficients)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _conv_backward(x, taps, bias, dy, block_s, block_d):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, seq, _ = x.shape
    taps_n, width = taps.shape
    blocks, per = seq // block_s, block_s // HALO
    tile = pl.BlockSpec((1, block_s, block_d),
                        lambda i, j, k: (i, blocks - 1 - k, j))
    # the HALO rows before the tile (the row's first tile reads its own
    # first rows and takes zeros)
    before = pl.BlockSpec(
        (1, HALO, block_d),
        lambda i, j, k: (i, jnp.maximum((blocks - 1 - k) * per - 1, 0), j))
    coefficients, specs = _coefficients(taps, bias, block_d)
    return _pallas(
        functools.partial(_bwd_kernel, has_bias=bias is not None,
                          rows=math.gcd(STEP_ROWS, block_s)),
        (rows, width // block_d, blocks),
        [tile, before, tile, *specs],
        [tile, pl.BlockSpec((1, taps_n + 1, _ROWS, block_d),
                            lambda i, j, k: (i, 0, 0, j))],
        [jax.ShapeDtypeStruct((rows, seq, width), x.dtype),
         jax.ShapeDtypeStruct((rows, taps_n + 1, _ROWS, width), _F32)],
        [pltpu.VMEM((_ROWS, block_d), _F32)],
        "causal_conv_silu_bwd",
        pl.CostEstimate(
            flops=(6 * taps_n + 14) * rows * seq * width,
            transcendentals=2 * rows * seq * width,
            bytes_accessed=rows * seq * width * (
                2 * x.dtype.itemsize + dy.dtype.itemsize)),
    )(x, x, dy, *coefficients)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_silu(x, taps, bias, dtype, block_s, block_d):
    return _conv_forward(x, taps, bias, dtype, block_s, block_d)


def _conv_silu_fwd(x, taps, bias, dtype, block_s, block_d):
    return (_conv_forward(x, taps, bias, dtype, block_s, block_d),
            (x, taps, bias))


def _conv_silu_bwd(dtype, block_s, block_d, residuals, dy):
    x, taps, bias = residuals
    taps_n, width = taps.shape
    dx, sums = _conv_backward(x, taps, bias, dy, block_s, block_d)
    # the columns of a wider x that the convolution never read
    dx = jnp.pad(dx, ((0, 0), (0, 0), (0, x.shape[2] - width)))
    d_taps = sums[:, :taps_n].sum((0, 2)).astype(taps.dtype)
    d_bias = None if bias is None else sums[:, taps_n].sum((0, 1)).astype(
        bias.dtype)
    return dx, d_taps, d_bias


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def _block(size: int, most: int, *units: int) -> int:
    """The largest divisor of ``size`` up to ``most`` in whole ``units[0]``s,
    or failing one in whole ``units[1]``s, and so on."""
    for unit in units:
        found = [b for b in range(unit, min(most, size) + 1, unit)
                 if size % b == 0]
        if found:
            return max(found)
    raise ValueError(f"no block of {units} up to {most} divides {size}")


def conv_kernel(x, taps, bias=None, *, dtype=None, block_s: int = BLOCK_S,
                block_d: int = BLOCK_D):
    """``silu(conv(x[..., :D]) [+ bias])`` in ``dtype`` (``x``'s if None)
    as the Pallas kernel pair (``S`` in whole tiles of ``HALO`` rows, ``D``
    and ``x``'s own width in whole 128-lane groups, at most ``7`` taps);
    differentiable, by its own rule."""
    taps_n, width = taps.shape
    rows, seq, wide = x.shape
    if (seq % HALO or width % _LANES or wide % _LANES or wide < width
            or not 1 <= taps_n < _ROWS):
        raise ValueError(
            f"the convolution's kernel takes rows in whole tiles of {HALO} "
            f"tokens, channels in whole groups of {_LANES} lanes and 1 to "
            f"{_ROWS - 1} taps; got {seq} tokens, {width} of {wide} "
            f"channels and {taps_n} taps")
    return _conv_silu(x, taps, bias, jnp.dtype(dtype or x.dtype),
                      _block(seq, block_s, _LANES, HALO),
                      _block(width, block_d, _LANES))


def conv_fused_applies(seq: int, channels: int, taps: int = 4, mesh=None,
                       platform: Optional[str] = None) -> bool:
    """The rule by which a layer's convolution and SiLU run the kernel pair:
    on a TPU, a row in whole sequence tiles (of 128 tokens at least),
    channels in whole lane groups, the taps and the bias's sum inside one
    tile of eight sublanes, over one device or a mesh of one (XLA cannot
    partition a Mosaic call). Everything else is the plain form."""
    if (platform or jax.default_backend()) != "tpu":
        return False
    if seq % _LANES or channels % _LANES:
        return False
    if not 1 <= taps < _ROWS:
        return False
    if mesh is not None and mesh.size > 1:
        return False
    return jax.device_count() == 1


def causal_conv_silu(x, taps, bias=None, *, dtype=None):
    """``silu(conv(x[..., :D]) [+ bias])`` cast to ``dtype`` (``x``'s if
    None), ``D`` the taps' channels: ``x`` ``[B, S, W]`` may be a fused
    projection whose first ``D`` columns are the convolved ones, and where
    the kernel runs they are read where they lie. By the kernel pair where
    :func:`conv_fused_applies` says so for these shapes; elsewhere
    ``nn.silu(causal_depthwise_conv(...)).astype(dtype)`` to the letter."""
    taps_n, width = taps.shape
    if conv_fused_applies(x.shape[1], width, taps_n):
        if x.shape[2] % _LANES:  # a ragged projection: the slice written out
            x = x[..., :width]
        return conv_kernel(x, taps, bias, dtype=dtype)
    if x.shape[2] != width:
        x = x[..., :width]
    return jax.nn.silu(causal_depthwise_conv(x, taps, bias)).astype(
        dtype or x.dtype)
