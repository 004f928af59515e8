"""Selective scan (Mamba-1, arXiv:2312.00752): the recurrence of a
state-space layer, the first mixer here that is not attention.

    h_t = exp(dt_t * a) * h_{t-1} + (dt_t * x_t) outer b_t      h: [D, N]
    y_t = h_t . c_t                                             h_0 = 0

per row, with ``x``, ``dt`` ``[B, L, D]``, ``a`` ``[D, N]`` (negative), ``b``,
``c`` ``[B, L, N]``: every channel ``d`` carries ``N`` states whose decay
depends on the token. ``dt``, the exponent, ``h`` and the sum over the states
are float32 whatever ``x``'s type. Written as one ``associative_scan`` over a
row it keeps ``[L, D, N]`` float32 intermediates (2.7 GB a layer at 8,192
tokens of 5,120 channels and 16 states), so both forms here go chunk by chunk
and keep one state a chunk boundary:

* :func:`scan_chunked`, plain ``jax.numpy``: a ``lax.scan`` over chunks whose
  body (an ``associative_scan`` over the chunk's tokens) is recomputed in the
  backward pass; what runs off the TPU, and what the kernel is held to;
* :func:`scan_kernel`, a Pallas kernel with its own differentiation rule: the
  state ``[N, block_d]`` stays in VMEM (states down the sublanes, channels
  along the lanes) while a grid step walks its chunk token by token; the
  forward keeps the state each chunk starts from (``[L / chunk, N, D]``), and
  the backward kernel walks the chunks in reverse, recomputes a chunk's states
  from that and runs the adjoint recurrence over them. It is bound by the
  vector unit and by latency, not by the matrix unit, which only spreads
  ``b_t`` and ``c_t`` over the lanes (a product with a one-row matrix) and
  sums their gradients over them.

:func:`selective_scan` chooses between them from the platform and the shapes
(:func:`scan_fused_applies`), as ``ops/flash.py`` chooses attention's kernel:
no flag. Both return ``(y, h_last)``: ``h_last`` ``[B, D, N]`` is the state at
each row's end, for a gauge; it takes no gradient.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["selective_scan", "scan_chunked", "scan_kernel",
           "scan_fused_applies", "CHUNK", "BLOCK_D"]

CHUNK = 128  # tokens a grid step walks; timed on the v5e (PERF.md, PR 33)
BLOCK_D = 1024  # channels a grid step holds: eight 128-lane groups of state
_LANES = 128
_ROWS = 8  # sublanes of a float32 tile
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _check(x, dt, a, b, c, chunk):
    rows, seq, width = x.shape
    if dt.shape != x.shape or a.shape[0] != width or b.shape != c.shape \
            or b.shape != (rows, seq, a.shape[1]):
        raise ValueError(
            f"selective scan takes x, dt [B, L, D], a [D, N], b, c [B, L, N];"
            f" got {x.shape}, {dt.shape}, {a.shape}, {b.shape}, {c.shape}")
    if seq % min(chunk, seq):
        raise ValueError(f"a row of {seq} tokens is not whole chunks of "
                         f"{chunk}")


# -- the plain chunked form ----------------------------------------------------


def scan_chunked(x, dt, a, b, c, *, chunk: int = CHUNK):
    """The recurrence in plain ``jax.numpy``, ``chunk`` tokens at a time."""
    _check(x, dt, a, b, c, chunk)
    rows, seq, width = x.shape
    step = min(chunk, seq)
    a = a.astype(_F32)

    def chunks(t):  # [B, L, ·] -> [L / step, B, step, ·]
        return t.astype(_F32).reshape(rows, seq // step, step, -1).swapaxes(
            0, 1)

    def combine(left, right):
        (a_l, b_l), (a_r, b_r) = left, right
        return a_l * a_r, a_r * b_l + b_r

    @jax.checkpoint
    def body(h, parts):
        x_c, dt_c, b_c, c_c = parts
        decay = jnp.exp(dt_c[..., None] * a)  # [B, step, D, N]
        fed = (dt_c * x_c)[..., None] * b_c[:, :, None, :]
        through, local = jax.lax.associative_scan(combine, (decay, fed),
                                                  axis=1)
        states = local + through * h[:, None]
        return states[:, -1], jnp.einsum("btdn,btn->btd", states, c_c)

    h_last, y = jax.lax.scan(
        body, jnp.zeros((rows, width, a.shape[1]), _F32),
        (chunks(x), chunks(dt), chunks(b), chunks(c)))
    return (y.swapaxes(0, 1).reshape(x.shape).astype(x.dtype),
            jax.lax.stop_gradient(h_last))


# -- the kernel ----------------------------------------------------------------
#
# A grid step is (row, block of channels, chunk); the chunks of a row's block
# follow each other, and the state rides from one to the next in scratch. A
# token's work is on [N, 128] tiles, one a lane group: the decay
# ``exp(dt_t * a)`` (``dt_t`` a row spread down the sublanes), the update, and
# the sum over the sublanes that gives ``y_t``. ``b_t`` and ``c_t`` are
# needed as columns spread along the lanes; the chunk's are made at once from
# the flat ``[1, chunk * N]`` row by a product with a one-row matrix.


def _columns(row_ref, out_ref):
    """``out[t * N + n, :] = row[0, t * N + n]`` for a chunk's flat b or c."""
    first = (jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 0) == 0
             ).astype(_F32)
    out_ref[...] = jax.lax.dot_general(
        row_ref[0], first, _TN, precision=_HIGHEST,
        preferred_element_type=_F32)


def _lane_sums(acc_ref):
    """``[8, chunk * N]``: every row the sums over the lanes of ``acc``
    ``[chunk * N, 128]``, flat again."""
    return jax.lax.dot_general(
        jnp.ones((_ROWS, _LANES), _F32), acc_ref[...], _NT,
        precision=_HIGHEST, preferred_element_type=_F32)


def _groups(block_d):
    return [slice(g, g + _LANES) for g in range(0, block_d, _LANES)]


def _fwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, start_ref,
                last_ref, h_ref, u_ref, dt32_ref, y32_ref, bcol_ref,
                ccol_ref, *, chunk, block_d, states):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    start_ref[0, 0] = h_ref[...]  # what the backward pass recomputes from
    dt32_ref[...] = dt_ref[0].astype(_F32)
    u_ref[...] = dt32_ref[...] * x_ref[0].astype(_F32)
    _columns(b_ref, bcol_ref)
    _columns(c_ref, ccol_ref)
    groups = _groups(block_d)
    a = [a_ref[:, g] for g in groups]

    def eight(i, hs):
        # a dynamic load or store starts at a whole tile: eight tokens a turn
        rows = pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS)
        dts = [dt32_ref[rows, g] for g in groups]
        us = [u_ref[rows, g] for g in groups]
        ys = [[] for _ in groups]
        for r in range(_ROWS):
            at = pl.ds(pl.multiple_of((i * _ROWS + r) * states, states),
                       states)
            b_t, c_t = bcol_ref[at, :], ccol_ref[at, :]
            hs = tuple(jnp.exp(dt[r:r + 1] * a_g) * h + u[r:r + 1] * b_t
                       for dt, u, a_g, h in zip(dts, us, a, hs))
            for y, h in zip(ys, hs):
                y.append(jnp.sum(h * c_t, axis=0, keepdims=True))
        for g, y in zip(groups, ys):
            y32_ref[rows, g] = jnp.concatenate(y, axis=0)
        return hs

    hs = jax.lax.fori_loop(0, chunk // _ROWS, eight,
                           tuple(h_ref[:, g] for g in groups))
    for g, h in zip(groups, hs):
        h_ref[:, g] = h
    y_ref[0] = y32_ref[...].astype(y_ref.dtype)
    last_ref[0] = h_ref[...]


def _bwd_kernel(x_ref, dt_ref, dy_ref, b_ref, c_ref, a_ref, start_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, carry_ref, hs_ref,
                x32_ref, dt32_ref, dy32_ref, dx32_ref, bcol_ref, ccol_ref,
                bacc_ref, cacc_ref, *, chunk, block_d, states):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)  # the row's last chunk comes first
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        da_ref[...] = jnp.zeros_like(da_ref)

    x32_ref[...] = x_ref[0].astype(_F32)
    dt32_ref[...] = dt_ref[0].astype(_F32)
    dy32_ref[...] = dy_ref[0].astype(_F32)
    _columns(b_ref, bcol_ref)
    _columns(c_ref, ccol_ref)
    groups = _groups(block_d)
    a = [a_ref[:, g] for g in groups]

    def at(t):
        return pl.ds(pl.multiple_of(t * states, states), states)

    # the chunk's states again, from the one it started with: hs[t] is the
    # state before token t. A dynamic load or store starts at a whole tile,
    # so both loops take eight tokens a turn.
    hs_ref[at(0), :] = start_ref[0, 0]

    def again(i, hs):
        rows = pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS)
        dts = [dt32_ref[rows, g] for g in groups]
        xs = [x32_ref[rows, g] for g in groups]
        for r in range(_ROWS):
            t = i * _ROWS + r
            b_t = bcol_ref[at(t), :]
            hs = tuple(
                jnp.exp(dt[r:r + 1] * a_g) * h + dt[r:r + 1] * x[r:r + 1] * b_t
                for dt, x, a_g, h in zip(dts, xs, a, hs))
            for g, h in zip(groups, hs):
                hs_ref[at(t + 1), g] = h
        return hs

    jax.lax.fori_loop(0, chunk // _ROWS, again,
                      tuple(start_ref[0, 0, :, g] for g in groups))

    def back(i, state):
        first = (chunk // _ROWS - 1 - i) * _ROWS
        rows = pl.ds(pl.multiple_of(first, _ROWS), _ROWS)
        carries, das = state
        dts = [dt32_ref[rows, g] for g in groups]
        xs = [x32_ref[rows, g] for g in groups]
        dys = [dy32_ref[rows, g] for g in groups]
        ddts, dxs = [[] for _ in groups], [[] for _ in groups]
        for r in reversed(range(_ROWS)):
            t = first + r
            b_t, c_t = bcol_ref[at(t), :], ccol_ref[at(t), :]
            b_sum = jnp.zeros((states, _LANES), _F32)
            c_sum = jnp.zeros((states, _LANES), _F32)
            new_carries, new_das = [], []
            for k, (g, a_g, carry, da) in enumerate(
                    zip(groups, a, carries, das)):
                dt, x = dts[k][r:r + 1], xs[k][r:r + 1]
                dy = dys[k][r:r + 1]
                decay = jnp.exp(dt * a_g)
                grad_h = c_t * dy + carry  # dL/dh_t, later tokens' included
                c_sum += dy * hs_ref[at(t + 1), g]
                b_sum += grad_h * (dt * x)
                fed = jnp.sum(grad_h * b_t, axis=0, keepdims=True)
                through = grad_h * hs_ref[at(t), g] * decay  # dL/d(dt a)
                new_das.append(da + through * dt)
                ddts[k].append(jnp.sum(through * a_g, axis=0, keepdims=True)
                               + fed * x)
                dxs[k].append(fed * dt)
                new_carries.append(decay * grad_h)
            carries, das = tuple(new_carries), tuple(new_das)
            bacc_ref[at(t), :] = b_sum
            cacc_ref[at(t), :] = c_sum
        for k, g in enumerate(groups):
            ddt_ref[0, rows, g] = jnp.concatenate(ddts[k][::-1], axis=0)
            dx32_ref[rows, g] = jnp.concatenate(dxs[k][::-1], axis=0)
        return carries, das

    zeros = tuple(jnp.zeros((states, _LANES), _F32) for _ in groups)
    carries, das = jax.lax.fori_loop(
        0, chunk // _ROWS, back,
        (tuple(carry_ref[:, g] for g in groups), zeros))
    for g, carry, da in zip(groups, carries, das):
        carry_ref[:, g] = carry
        da_ref[0, :, g] += da
    dx_ref[0] = dx32_ref[...].astype(dx_ref.dtype)
    db_ref[0, 0] = _lane_sums(bacc_ref)
    dc_ref[0, 0] = _lane_sums(cacc_ref)


def _flat_rows(t):
    """b or c ``[B, L, N]`` as the kernels take it: ``[B, 8, L * N]`` float32,
    the flat row in the first of a tile's eight sublanes."""
    rows, seq, states = t.shape
    flat = t.astype(_F32).reshape(rows, 1, seq * states)
    return jnp.pad(flat, ((0, 0), (0, _ROWS - 1), (0, 0)))


def _pallas(kernel, grid, in_specs, out_specs, out_shape, scratch, name,
            cost):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        cost_estimate=cost, name=name)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _scan_forward(x, dt, a, b, c, chunk, block_d):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, seq, width = x.shape
    states = a.shape[1]
    n_chunks = seq // chunk
    tile = pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j))
    flat = pl.BlockSpec((1, _ROWS, chunk * states), lambda i, j, k: (i, 0, k))
    a_tile = pl.BlockSpec((states, block_d), lambda i, j, k: (0, j))
    vmem = functools.partial(pltpu.VMEM, dtype=_F32)
    y, starts, last = _pallas(
        functools.partial(_fwd_kernel, chunk=chunk, block_d=block_d,
                          states=states),
        (rows, width // block_d, n_chunks),
        [tile, tile, flat, flat, a_tile],
        [tile,
         pl.BlockSpec((1, 1, states, block_d), lambda i, j, k: (i, k, 0, j)),
         pl.BlockSpec((1, states, block_d), lambda i, j, k: (i, 0, j))],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct((rows, n_chunks, states, width), _F32),
         jax.ShapeDtypeStruct((rows, states, width), _F32)],
        [vmem((states, block_d)), vmem((chunk, block_d)),
         vmem((chunk, block_d)), vmem((chunk, block_d)),
         vmem((chunk * states, _LANES)), vmem((chunk * states, _LANES))],
        "ssm_scan_fwd",
        pl.CostEstimate(flops=7 * rows * seq * width * states,
                        transcendentals=rows * seq * width * states,
                        bytes_accessed=rows * seq * width * (
                            2 * x.dtype.itemsize + 4)),
    )(x, dt, _flat_rows(b), _flat_rows(c), a.astype(_F32).T)
    return y, starts, last


@functools.partial(jax.jit, static_argnums=(7, 8))
def _scan_backward(x, dt, a, b, c, starts, dy, chunk, block_d):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, seq, width = x.shape
    states = a.shape[1]
    n_chunks, n_blocks = seq // chunk, width // block_d
    tile = pl.BlockSpec((1, chunk, block_d),
                        lambda i, j, k: (i, n_chunks - 1 - k, j))
    flat = pl.BlockSpec((1, _ROWS, chunk * states),
                        lambda i, j, k: (i, 0, n_chunks - 1 - k))
    flat_out = pl.BlockSpec((1, 1, _ROWS, chunk * states),
                            lambda i, j, k: (i, j, 0, n_chunks - 1 - k))
    per_block = jax.ShapeDtypeStruct(
        (rows, n_blocks, _ROWS, seq * states), _F32)
    vmem = functools.partial(pltpu.VMEM, dtype=_F32)
    dx, ddt, da, db, dc = _pallas(
        functools.partial(_bwd_kernel, chunk=chunk, block_d=block_d,
                          states=states),
        (rows, n_blocks, n_chunks),
        [tile, tile, tile, flat, flat,
         pl.BlockSpec((states, block_d), lambda i, j, k: (0, j)),
         pl.BlockSpec((1, 1, states, block_d),
                      lambda i, j, k: (i, n_chunks - 1 - k, 0, j))],
        [tile, tile,
         pl.BlockSpec((1, states, block_d), lambda i, j, k: (i, 0, j)),
         flat_out, flat_out],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(x.shape, _F32),
         jax.ShapeDtypeStruct((rows, states, width), _F32),
         per_block, per_block],
        [vmem((states, block_d)),
         vmem(((chunk + 1) * states, block_d)),
         vmem((chunk, block_d)), vmem((chunk, block_d)),
         vmem((chunk, block_d)), vmem((chunk, block_d)),
         vmem((chunk * states, _LANES)), vmem((chunk * states, _LANES)),
         vmem((chunk * states, _LANES)), vmem((chunk * states, _LANES))],
        "ssm_scan_bwd",
        pl.CostEstimate(flops=22 * rows * seq * width * states,
                        transcendentals=2 * rows * seq * width * states,
                        bytes_accessed=rows * seq * width * (
                            3 * x.dtype.itemsize + 8)),
    )(x, dt, dy, _flat_rows(b), _flat_rows(c), a.astype(_F32).T, starts)

    def per_token(t):  # the blocks' shares of db or dc, summed
        return t[:, :, 0].sum(1).reshape(rows, seq, states)

    return dx, ddt, da.sum(0).T, per_token(db), per_token(dc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_kernel(x, dt, a, b, c, chunk, block_d):
    y, _, last = _scan_forward(x, dt, a, b, c, chunk, block_d)
    return y, last.swapaxes(1, 2)


def _scan_kernel_fwd(x, dt, a, b, c, chunk, block_d):
    y, starts, last = _scan_forward(x, dt, a, b, c, chunk, block_d)
    return (y, last.swapaxes(1, 2)), (x, dt, a, b, c, starts)


def _scan_kernel_bwd(chunk, block_d, residuals, cotangents):
    x, dt, a, b, c, starts = residuals
    dy, _ = cotangents  # the state at a row's end takes no gradient
    dx, ddt, da, db, dc = _scan_backward(
        x, dt, a, b, c, starts, dy.astype(x.dtype), chunk, block_d)
    return (dx, ddt.astype(dt.dtype), da.astype(a.dtype), db.astype(b.dtype),
            dc.astype(c.dtype))


_scan_kernel.defvjp(_scan_kernel_fwd, _scan_kernel_bwd)


def _block_d(width: int, block_d: int) -> int:
    """The widest whole-lane-group divisor of ``width`` up to ``block_d``."""
    return max(d for d in range(_LANES, min(block_d, width) + 1, _LANES)
               if width % d == 0)


def scan_kernel(x, dt, a, b, c, *, chunk: int = CHUNK,
                block_d: int = BLOCK_D):
    """The recurrence as the Pallas kernel pair (``L`` whole chunks, ``D``
    whole 128-lane groups, ``N`` whole sublane tiles); differentiable."""
    _check(x, dt, a, b, c, chunk)
    width, states = a.shape
    if width % _LANES or states % _ROWS or min(chunk, x.shape[1]) % _ROWS:
        raise ValueError(
            f"the scan kernel takes channels in whole groups of {_LANES} and"
            f" states and a chunk's tokens in whole groups of {_ROWS}; got "
            f"{width}, {states} and {min(chunk, x.shape[1])}")
    return _scan_kernel(x, dt, a, b, c, min(chunk, x.shape[1]),
                        _block_d(width, block_d))


def scan_fused_applies(seq: int, width: int, states: int,
                       platform: Optional[str] = None) -> bool:
    """The rule by which a state-space layer's scan runs the kernel: on a
    TPU, a row of whole chunks, channels in whole lane groups, states in
    whole sublane tiles, in a process with one device (XLA cannot partition
    a Mosaic call, and the scan has met no mesh). Everything else is the
    plain chunked form."""
    if (platform or jax.default_backend()) != "tpu":
        return False
    if seq % CHUNK or width % _LANES or states % _ROWS:
        return False
    return jax.device_count() == 1


def selective_scan(x, dt, a, b, c):
    """``(y, h_last)`` by the kernel where :func:`scan_fused_applies` says
    so for these shapes, by the plain chunked form elsewhere."""
    if scan_fused_applies(x.shape[1], a.shape[0], a.shape[1]):
        return scan_kernel(x, dt, a, b, c)
    return scan_chunked(x, dt, a, b, c, chunk=math.gcd(CHUNK, x.shape[1]))
