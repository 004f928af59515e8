"""The state-space dual (Mamba-2; Dao and Gu, arXiv:2405.21060): the
recurrence of a state-space layer whose decay is a scalar a head and whose
state is a matrix a head, which :mod:`.scan`'s elementwise state (a decay a
channel and state) and :mod:`.delta`'s rule (a triangular inverse) are not.

    h_t = exp(dt_t a) h_{t-1} + (dt_t x_t) outer b_t        h: [P, N], h_0 = 0
    y_t = h_t c_t + d x_t

per row and head, with ``x`` ``[B, S, H, P]``, ``dt`` ``[B, S, H]`` (after
its softplus, > 0), ``a`` ``[H]`` (negative), ``b``, ``c`` ``[B, S, N]`` (one
group: every head reads the same two), ``d`` ``[H]``. Token by token that is
``S`` dependent steps over ``[H, P, N]``; a chunk of ``L`` tokens at a time
(``A_t`` the running sum of ``dt a`` inside the chunk) it is products:

    y   = ((c b') * exp(A_t - A_s) * dt_s, s <= t) x        inside a chunk
        + exp(A_t) * (c_t h_start)                          what came before
    h_end = exp(A_L) h_start + sum_s exp(A_L - A_s) dt_s x_s outer b_s

The published plain form builds the decay masks for every head at once,
``[S / L, H, L, L]`` float32 (537 MB a layer and row of 8,192 tokens at 64
heads and chunks of 256, several times over with its derivative). Here:

* :func:`ssd_recurrence`: the definition, token by token in float32; what
  the tests hold the chunked form to;
* :func:`ssd_chunked`: plain ``jax.numpy``, differentiated by JAX, a group
  of ``group`` heads at a time (``lax.map``, a group's forward made again in
  the backward pass), so that nothing as large as ``[S / L, H, L, L]`` exists
  whole; the states carried from chunk to chunk by a ``lax.scan``. ``c b'``
  is made once and shared by the groups. What runs off the TPU, and what the
  kernels are held to;
* :func:`ssd_kernel`: a Pallas kernel pair with its own differentiation
  rule over grid (row, block of ``BLOCK_H`` heads, chunk), the chunks of a
  block following each other and the block's states ``[BLOCK_H, P, N]``
  riding from one to the next in float32 scratch. A step reads the chunk's
  ``x`` ``[L, BLOCK_H * P]`` (a head is a group of lanes), ``b`` and ``c``
  ``[L, N]`` as the layer has them (``x``, ``b``, ``c`` may be columns of
  one array, the layer's convolved projection, which the block
  specifications walk where they lie) and the tokens' ``A_t`` and ``dt``
  twice, down the sublanes (``[L, heads]``: a token's scalar for its row of
  a matrix) and along the lanes (``[heads, L]``: for its column), makes ``c
  b'`` once and each head's decay mask in VMEM, and nothing ``[L, L]``
  reaches HBM. ``ssd_fwd`` keeps, for the backward pass alone, the state
  each chunk starts from (``[S / L, H, P, N]`` float32); ``ssd_bwd`` walks
  the chunks in reverse with the state's cotangent in scratch, makes a
  chunk's masks again and writes ``dx`` in ``x``'s type, a block's share of
  ``db`` and ``dc`` and the cotangents of ``A_t`` and ``dt`` in both
  layouts. Only the running sum that makes ``A_t`` of ``dt a``, its
  transpose, the two layouts of the per-token scalars and the sums over the
  head blocks stay in XLA.

``dt``, the running sums, every exponential and the state are float32
whatever ``x``'s type; the operands of the products are in ``x``'s type with
float32 accumulation (the masked scores rounded once, with ``dt_s`` inside;
the state rounded for its read-out, never in the carry), which is what
:mod:`.delta` says of the delta rule. :func:`ssd` chooses the form from the
platform and the shapes (:func:`ssd_fused_applies`), as :mod:`.scan` and
:mod:`.delta` do: no flag. Both return ``(y, h_last)``: ``h_last`` ``[B, H,
P, N]`` is the state at each row's end, for a gauge; it takes no gradient.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .scan import _pallas  # the same grid's semantics: the last axis in order

__all__ = ["ssd", "ssd_packed", "ssd_chunked", "ssd_recurrence",
           "ssd_kernel", "ssd_kernel_packed", "ssd_fused_applies", "CHUNK",
           "BLOCK_H", "PLAIN_CHUNK", "GROUP_H"]

CHUNK = 256  # tokens a grid step of the kernels takes: the published
# schedule's chunk, and with BLOCK_H the fastest pair timed on the v5e
BLOCK_H = 16  # heads a grid step of the kernels holds
PLAIN_CHUNK = 128  # tokens a chunk of the plain form, and
GROUP_H = 16  # its heads whose decay masks are alive at once (the plain
# form's fastest pair: PERF.md section 6, PR 49, has both tables)
_LANES = 128
_ROWS = 8  # sublanes of a float32 tile
_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _check(x, dt, a, b, c, d):
    rows, seq, heads, _ = x.shape
    if dt.shape != (rows, seq, heads) or a.shape != (heads,) \
            or d.shape != (heads,) or b.shape != c.shape \
            or b.shape[:2] != (rows, seq) or b.ndim != 3:
        raise ValueError(
            "the state-space dual takes x [B, S, H, P], dt [B, S, H], a, d "
            f"[H], b, c [B, S, N]; got {x.shape}, {dt.shape}, {a.shape}, "
            f"{d.shape}, {b.shape}, {c.shape}")


def ssd_recurrence(x, dt, a, b, c, d):
    """The definition: one token after the other, everything float32."""
    _check(x, dt, a, b, c, d)
    rows, _, heads, width = x.shape
    x, dt, b, c = (t.astype(_F32) for t in (x, dt, b, c))

    def token(h, parts):
        x_t, dt_t, b_t, c_t = parts  # [B, H, P], [B, H], [B, N], [B, N]
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, c_t,
                             precision="highest") + d[:, None] * x_t

    last, y = jax.lax.scan(
        token, jnp.zeros((rows, heads, width, b.shape[-1]), _F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), jax.lax.stop_gradient(last)


def _group(x, dt, a, d, b, c, scores):
    """One group of heads, all chunks: ``x`` ``[B, Z, L, G, P]``, ``dt``
    ``[B, Z, L, G]`` f32, ``a``, ``d`` ``[G]``, ``b``, ``c`` ``[B, Z, L, N]``,
    ``scores`` ``[B, Z, L, L]`` f32 (``c b'``) -> ``(y [B, Z, L, G, P] in
    x's type, h_last [B, G, P, N])``."""
    dtype = x.dtype
    f32_out = dict(preferred_element_type=_F32)
    rows, _, step, heads, width = x.shape
    # heads ahead of the chunk's tokens: [B, Z, G, L]
    dt = jnp.moveaxis(dt, 3, 2)
    total = jnp.cumsum(dt * a[:, None], axis=-1)
    at = jnp.arange(step)
    # exp of a difference that is <= 0 wherever it is used; the others never
    # reach the exponential (they would overflow where a chunk decays far)
    decay = jnp.exp(jnp.where(at[:, None] >= at[None, :],
                              total[..., :, None] - total[..., None, :],
                              -jnp.inf))
    mask = (scores[:, :, None] * decay * dt[..., None, :]).astype(dtype)
    y = jnp.einsum("bzgls,bzsgp->bzlgp", mask, x, **f32_out)
    # what a chunk adds to the state, and what it leaves of the one before
    to_end = jnp.exp(total[..., -1:] - total) * dt  # [B, Z, G, L]
    fed = (x.astype(_F32) * jnp.moveaxis(to_end, 2, 3)[..., None]).astype(
        dtype)
    added = jnp.einsum("bzsgp,bzsn->zbgpn", fed, b, **f32_out)
    kept = jnp.moveaxis(jnp.exp(total[..., -1]), 1, 0)  # [Z, B, G]

    def carry(h, parts):
        added_z, kept_z = parts
        return kept_z[..., None, None] * h + added_z, h

    last, starts = jax.lax.scan(
        carry, jnp.zeros((rows, heads, width, b.shape[-1]), _F32),
        (added, kept))
    before = jnp.einsum("bzln,zbgpn->bzlgp", c, starts.astype(dtype),
                        **f32_out)
    y = (y + before * jnp.moveaxis(jnp.exp(total), 2, 3)[..., None]
         + d[:, None] * x.astype(_F32))
    return y.astype(dtype), last


def ssd_chunked(x, dt, a, b, c, d, *, chunk: int = PLAIN_CHUNK,
                group: int = GROUP_H):
    """The dual form in plain ``jax.numpy``, ``chunk`` tokens and ``group``
    heads at a time (all heads at once where ``group`` does not divide
    them). A row is padded to whole chunks with tokens that leave the state
    as it is."""
    _check(x, dt, a, b, c, d)
    rows, seq, heads, width = x.shape
    step = min(chunk, seq)
    pad = -seq % step
    dt, a, d = dt.astype(_F32), a.astype(_F32), d.astype(_F32)
    b, c = b.astype(x.dtype), c.astype(x.dtype)
    if pad:  # dt = 0: a decay of 1 and nothing fed
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (
            t.ndim - 2)) for t in (x, dt, b, c))
    n = (seq + pad) // step

    def chunks(t):  # [B, S, ...] -> [B, Z, L, ...]
        return t.reshape(rows, n, step, *t.shape[2:])

    x, dt, b, c = chunks(x), chunks(dt), chunks(b), chunks(c)
    scores = jnp.einsum("bzln,bzsn->bzls", c, b, preferred_element_type=_F32)
    form = jax.checkpoint(_group)
    if heads <= group or heads % group:
        y, last = form(x, dt, a, d, b, c, scores)
    else:
        def split(t, axis):  # groups first: [H / G, ..., G, ...]
            return jnp.moveaxis(t.reshape(
                *t.shape[:axis], heads // group, group, *t.shape[axis + 1:]),
                axis, 0)

        y, last = jax.lax.map(
            lambda parts: form(*parts, b, c, scores),
            (split(x, 3), split(dt, 3), split(a, 0), split(d, 0)))
        y = jnp.moveaxis(y, 0, 3).reshape(x.shape)
        last = jnp.moveaxis(last, 0, 1).reshape(rows, heads, width, -1)
    y = y.reshape(rows, seq + pad, heads, width)[:, :seq]
    return y, jax.lax.stop_gradient(last)


# -- the kernels ---------------------------------------------------------------
#
# A grid step is (row, block of heads, chunk). Of a chunk's ``[L, L]``
# matrices only ``c b'`` is shared by the heads; each head's decay mask is
# made from the two layouts of its running sum (a column ``[L, 1]`` less a
# row ``[1, L]``: Mosaic turns no vector of L), used and dropped. The heads of
# a block are independent chains (a mask, three products, the state's update)
# that the scheduler lays side by side.


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _lower(step):
    at = jax.lax.broadcasted_iota(jnp.int32, (step, step), 0)
    return at >= jax.lax.broadcasted_iota(jnp.int32, (step, step), 1)


def _at_end(total_r):
    """The running sum at the chunk's last token, ``[1, 1]``: a masked sum
    down the sublanes (Mosaic spreads no scalar sliced off row ``L - 1``
    over both sublanes and lanes)."""
    step = total_r.shape[0]
    last = jax.lax.broadcasted_iota(jnp.int32, (step, 1), 0) == step - 1
    return jnp.sum(jnp.where(last, total_r, 0.0), 0, keepdims=True)


def _decay(lower, total_r, total_c):
    """``exp(A_t - A_s)`` for ``s <= t``, 0 above the diagonal: ``[L, L]``
    from the running sum down the sublanes and along the lanes."""
    return jnp.exp(jnp.where(lower, total_r - total_c, -jnp.inf))


def _fwd_kernel(x_ref, b_ref, c_ref, total_r_ref, total_c_ref, dt_r_ref,
                dt_c_ref, d_ref, y_ref, *rest, heads, width, keep):
    from jax.experimental import pallas as pl

    start_ref = rest[0] if keep else None
    last_ref, h_ref = rest[-2:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    if keep:  # what the backward pass starts each chunk from
        start_ref[0, 0] = h_ref[...]
    b, c = b_ref[0], c_ref[0]  # [L, N]
    step = b.shape[0]
    scores = _dot(c, b, _NT)
    lower = _lower(step)
    for h in range(heads):
        lanes = slice(h * width, (h + 1) * width)
        x = x_ref[0, :, lanes]
        total_r, total_c = total_r_ref[0, 0, :, h:h + 1], total_c_ref[
            0, 0, h:h + 1, :]
        dt_r, dt_c = dt_r_ref[0, 0, :, h:h + 1], dt_c_ref[0, 0, h:h + 1, :]
        mask = (scores * _decay(lower, total_r, total_c) * dt_c).astype(
            x.dtype)
        state = h_ref[h]  # [P, N] f32
        x32 = x.astype(_F32)
        y = (_dot(mask, x, _NN)
             + jnp.exp(total_r) * _dot(c, state.astype(x.dtype), _NT)
             + d_ref[0, :, h:h + 1] * x32)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        end = _at_end(total_r)
        fed = (x32 * (jnp.exp(end - total_r) * dt_r)).astype(x.dtype)
        h_ref[h] = jnp.exp(end) * state + _dot(fed, b, _TN)
    last_ref[0] = h_ref[...]


def _bwd_kernel(x_ref, b_ref, c_ref, total_r_ref, total_c_ref, dt_r_ref,
                dt_c_ref, d_ref, dy_ref, start_ref, dx_ref, db_ref, dc_ref,
                dtotal_r_ref, dtotal_c_ref, ddt_r_ref, ddt_c_ref, dd_ref,
                carry_ref, *, heads, width):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)  # the row's last chunk comes first
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    b, c = b_ref[0], c_ref[0]
    step = b.shape[0]
    dtype = b.dtype
    scores = _dot(c, b, _NT)
    lower = _lower(step)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (step, 1), 0) == step - 1
    d_scores = jnp.zeros((step, step), _F32)
    db = jnp.zeros(b.shape, _F32)
    dc = jnp.zeros(c.shape, _F32)
    for h in range(heads):
        lanes = slice(h * width, (h + 1) * width)
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        x32, dy32 = x.astype(_F32), dy.astype(_F32)
        total_r, total_c = total_r_ref[0, 0, :, h:h + 1], total_c_ref[
            0, 0, h:h + 1, :]
        dt_r, dt_c = dt_r_ref[0, 0, :, h:h + 1], dt_c_ref[0, 0, h:h + 1, :]
        skip = d_ref[0, :, h:h + 1]
        start = start_ref[0, 0, h]  # [P, N] f32: the state before the chunk
        d_end = carry_ref[h]  # the cotangent of the state after it
        decay = _decay(lower, total_r, total_c)
        mask = (scores * decay * dt_c).astype(dtype)
        # inside the chunk: y = mask x
        t = _dot(dy, x, _NT) * decay  # d mask, under the mask
        d_scores += t * dt_c
        u = t * scores
        ddt_c = jnp.sum(u, 0, keepdims=True)
        d_seg = u * dt_c
        dtotal_r = jnp.sum(d_seg, 1, keepdims=True)
        dtotal_c_ref[0, 0, h:h + 1, :] = -jnp.sum(d_seg, 0, keepdims=True)
        ddt_c_ref[0, 0, h:h + 1, :] = ddt_c
        # what came before: y += exp(A_t) c_t h_start
        grown = jnp.exp(total_r)
        start_op = start.astype(dtype)
        dtotal_r += grown * jnp.sum(dy32 * _dot(c, start_op, _NT), 1,
                                    keepdims=True)
        dy_grown = (dy32 * grown).astype(dtype)
        dc += _dot(dy_grown, start_op, _NN)
        d_start = _dot(dy_grown, c, _TN)
        # the state's update: h_end = exp(A_L) h_start + fed' b
        end = _at_end(total_r)
        kept = jnp.exp(end)
        to_end = jnp.exp(end - total_r)
        weight = to_end * dt_r
        d_start += kept * d_end
        d_end_op = d_end.astype(dtype)
        d_fed = _dot(b, d_end_op, _NT)  # [L, P]
        db += _dot((x32 * weight).astype(dtype), d_end_op, _NN)
        d_weight = jnp.sum(d_fed * x32, 1, keepdims=True)
        carried = d_weight * weight
        d_at_end = (kept * jnp.sum(d_end * start, keepdims=True)
                    + jnp.sum(carried, 0, keepdims=True))
        dtotal_r_ref[0, 0, :, h:h + 1] = (
            dtotal_r - carried + jnp.where(last_row, d_at_end, 0.0))
        ddt_r_ref[0, 0, :, h:h + 1] = d_weight * to_end
        dx_ref[0, :, lanes] = (_dot(mask, dy, _TN) + skip * dy32
                               + d_fed * weight).astype(dx_ref.dtype)
        dd_ref[0, 0, 0:1, h:h + 1] += jnp.sum(dy32 * x32, keepdims=True)
        carry_ref[h] = d_start
    d_scores = d_scores.astype(dtype)
    db_ref[0, 0] = db + _dot(d_scores, c, _TN)
    dc_ref[0, 0] = dc + _dot(d_scores, b, _NN)


class _Layout(NamedTuple):
    """What the kernels' block specifications are made from: the heads and
    their width, the states, the chunk, the heads a grid step holds, and the
    column block (of ``states`` columns) at which ``b`` and ``c`` start in
    their arrays: 0 in an array of its own, behind ``x`` in a layer's ``[x;
    b; c]``."""
    heads: int
    width: int
    states: int
    chunk: int
    block_h: int
    b_at: int = 0
    c_at: int = 0


def _specs(layout, chunk_of):
    """The block specifications of ``x`` (and ``y``, ``dy``, ``dx``), ``b``,
    ``c``, the four per-token scalars in their two layouts and ``d``;
    ``chunk_of(k)`` is the chunk a grid step's third index stands for."""
    from jax.experimental import pallas as pl

    step, heads, states = layout.chunk, layout.block_h, layout.states
    wide = heads * layout.width

    def columns(width, at):
        return pl.BlockSpec((1, step, width),
                            lambda i, j, k: (i, chunk_of(k), at(j)))

    rows = pl.BlockSpec((1, 1, step, heads),
                        lambda i, j, k: (i, j, chunk_of(k), 0))
    cols = pl.BlockSpec((1, 1, heads, step),
                        lambda i, j, k: (i, j, 0, chunk_of(k)))
    return {
        "x": columns(wide, lambda j: j),
        "b": columns(states, lambda j: layout.b_at),
        "c": columns(states, lambda j: layout.c_at),
        "rows": rows, "cols": cols,
        "d": pl.BlockSpec((1, 1, heads), lambda i, j, k: (j, 0, 0)),
        "shared": pl.BlockSpec((1, 1, step, states),
                               lambda i, j, k: (i, j, chunk_of(k), 0)),
        "start": pl.BlockSpec(
            (1, 1, heads, layout.width, states),
            lambda i, j, k: (i, chunk_of(k), j, 0, 0)),
        "state": pl.BlockSpec((1, heads, layout.width, states),
                              lambda i, j, k: (i, j, 0, 0)),
    }


def _scalars(dt, a, layout):
    """The running sum of ``dt a`` inside each chunk and ``dt`` itself, each
    down the sublanes ``[B, H / block, S, block]`` and along the lanes ``[B,
    H / block, block, S]``."""
    rows, seq, heads = dt.shape
    total = jnp.cumsum((dt * a).reshape(rows, seq // layout.chunk,
                                        layout.chunk, heads), axis=2)

    def by_rows(t):
        return jnp.moveaxis(t.reshape(rows, seq, heads // layout.block_h,
                                      layout.block_h), 2, 1)

    total, dt = by_rows(total), by_rows(dt)
    return total, jnp.swapaxes(total, 2, 3), dt, jnp.swapaxes(dt, 2, 3)


def _operands(arrays):
    """``(x, b, c)`` as the kernels take them: three arrays, or the one
    whose columns they are three times over."""
    return arrays if len(arrays) == 3 else arrays * 3


def _grid(arrays, layout):
    rows, seq = arrays[0].shape[:2]
    return (rows, layout.heads // layout.block_h, seq // layout.chunk)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _ssd_forward(arrays, dt, a, d, layout, keep):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, seq = arrays[0].shape[:2]
    heads, width, states = layout.heads, layout.width, layout.states
    grid = _grid(arrays, layout)
    spec = _specs(layout, lambda k: k)
    dtype = arrays[0].dtype
    state = jax.ShapeDtypeStruct((rows, heads, width, states), _F32)
    starts = jax.ShapeDtypeStruct((rows, grid[2], heads, width, states), _F32)
    out = _pallas(
        functools.partial(_fwd_kernel, heads=layout.block_h, width=width,
                          keep=keep),
        grid,
        [spec["x"], spec["b"], spec["c"], spec["rows"], spec["cols"],
         spec["rows"], spec["cols"], spec["d"]],
        [spec["x"], *([spec["start"]] if keep else []), spec["state"]],
        [jax.ShapeDtypeStruct((rows, seq, heads * width), dtype),
         *([starts] if keep else []), state],
        [pltpu.VMEM((layout.block_h, width, states), _F32)], "ssd_fwd",
        pl.CostEstimate(
            flops=2 * rows * seq * heads * (
                layout.chunk * width + 2 * width * states)
            + 2 * rows * seq * layout.chunk * states * grid[1],
            transcendentals=rows * seq * heads * (layout.chunk + 3),
            bytes_accessed=rows * seq * (
                2 * heads * width * dtype.itemsize
                + grid[1] * 2 * states * dtype.itemsize + 16 * heads)),
    )(*_operands(arrays), *_scalars(dt, a, layout),
      d.astype(_F32).reshape(grid[1], 1, layout.block_h))
    return out


@functools.partial(jax.jit, static_argnums=(6,))
def _ssd_backward(arrays, dt, a, d, starts, dy, layout):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, seq = arrays[0].shape[:2]
    heads, width, states = layout.heads, layout.width, layout.states
    grid = _grid(arrays, layout)
    blocks, chunks = grid[1], grid[2]
    spec = _specs(layout, lambda k: chunks - 1 - k)
    dtype = arrays[0].dtype
    by_rows = jax.ShapeDtypeStruct((rows, blocks, seq, layout.block_h), _F32)
    by_cols = jax.ShapeDtypeStruct((rows, blocks, layout.block_h, seq), _F32)
    shared = jax.ShapeDtypeStruct((rows, blocks, seq, states), _F32)
    dx, db, dc, dtotal_r, dtotal_c, ddt_r, ddt_c, dd = _pallas(
        functools.partial(_bwd_kernel, heads=layout.block_h, width=width),
        grid,
        [spec["x"], spec["b"], spec["c"], spec["rows"], spec["cols"],
         spec["rows"], spec["cols"], spec["d"], spec["x"], spec["start"]],
        [spec["x"], spec["shared"], spec["shared"], spec["rows"],
         spec["cols"], spec["rows"], spec["cols"],
         pl.BlockSpec((1, 1, _ROWS, layout.block_h),
                      lambda i, j, k: (i, j, 0, 0))],
        [jax.ShapeDtypeStruct((rows, seq, heads * width), dtype), shared,
         shared, by_rows, by_cols, by_rows, by_cols,
         jax.ShapeDtypeStruct((rows, blocks, _ROWS, layout.block_h), _F32)],
        [pltpu.VMEM((layout.block_h, width, states), _F32)], "ssd_bwd",
        pl.CostEstimate(
            flops=2 * rows * seq * heads * (
                3 * layout.chunk * width + 5 * width * states)
            + 6 * rows * seq * layout.chunk * states * blocks,
            transcendentals=rows * seq * heads * (layout.chunk + 4),
            bytes_accessed=rows * seq * (
                4 * heads * width * dtype.itemsize
                + blocks * 2 * states * (dtype.itemsize + 4) + 48 * heads)
            + rows * chunks * heads * width * states * 4),
    )(*_operands(arrays), *_scalars(dt, a, layout),
      d.astype(_F32).reshape(blocks, 1, layout.block_h), dy, starts)

    def per_token(by_rows, by_cols):  # [B, S, H] of the two layouts' sum
        both = by_rows + jnp.swapaxes(by_cols, 2, 3)
        return jnp.moveaxis(both, 1, 2).reshape(rows, seq, heads)

    # back through the running sum: a token's dt a reaches every later
    # token's sum inside its chunk
    dtotal = per_token(dtotal_r, dtotal_c).reshape(
        rows, chunks, layout.chunk, heads)
    d_da = jnp.flip(jnp.cumsum(jnp.flip(dtotal, 2), axis=2), 2).reshape(
        rows, seq, heads)
    ddt = per_token(ddt_r, ddt_c) + d_da * a
    return (dx, db.sum(1), dc.sum(1), ddt, (d_da * dt).sum((0, 1)),
            dd[:, :, 0].sum(0).reshape(heads))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dual(arrays, dt, a, d, layout):
    y, last = _ssd_forward(arrays, dt, a, d, layout, False)
    return y, last


def _dual_fwd(arrays, dt, a, d, layout):
    y, starts, last = _ssd_forward(arrays, dt, a, d, layout, True)
    return (y, last), (arrays, dt, a, d, starts)


def _dual_bwd(layout, residuals, cotangents):
    arrays, dt, a, d, starts = residuals
    dy, _ = cotangents  # the state at a row's end takes no gradient
    dx, db, dc, ddt, da, dd = _ssd_backward(
        arrays, dt, a, d, starts, dy.astype(arrays[0].dtype), layout)
    d_arrays = (dx, db.astype(dx.dtype), dc.astype(dx.dtype))
    if len(arrays) == 1:  # one array's columns: the cotangents side by side
        d_arrays = (jnp.concatenate(d_arrays, axis=-1),)
    return d_arrays, ddt.astype(dt.dtype), da.astype(a.dtype), \
        dd.astype(d.dtype)


_dual.defvjp(_dual_fwd, _dual_bwd)


def _block_h(heads: int, width: int, block_h: int) -> int:
    """The most heads up to ``block_h`` that divide ``heads`` and fill whole
    lane groups; 0 if no number of them does."""
    return max((n for n in range(1, min(block_h, heads) + 1)
                if heads % n == 0 and n * width % _LANES == 0), default=0)


def _layout(seq, heads, width, states, chunk, block_h, **at) -> _Layout:
    """The kernels' layout for these shapes, or their refusal by name."""
    if seq % chunk or chunk % _LANES or states % _LANES:
        raise ValueError(
            f"the dual's kernels take rows of whole chunks of {chunk} (whole "
            f"groups of {_LANES} tokens) and states in whole groups of "
            f"{_LANES} lanes; got {seq} tokens and {states} states")
    block = _block_h(heads, width, block_h)
    if not block:
        raise ValueError(
            f"no block of up to {block_h} heads of {width} divides {heads} "
            f"heads in whole groups of {_LANES} lanes")
    return _Layout(heads, width, states, chunk, block, **at)


def _columns(xbc, heads: int, head_dim: int):
    """``x`` ``[B, S, H, P]``, ``b`` and ``c`` ``[B, S, N]`` sliced out of
    ``xbc`` ``[B, S, H P + 2 N]``."""
    rows, seq, wide = xbc.shape
    inner = heads * head_dim
    states = (wide - inner) // 2
    return (xbc[..., :inner].reshape(rows, seq, heads, head_dim),
            xbc[..., inner:inner + states], xbc[..., inner + states:])


def ssd_kernel(x, dt, a, b, c, d, *, chunk: int = CHUNK,
               block_h: int = BLOCK_H):
    """The dual form as the Pallas kernel pair (``S`` whole chunks of whole
    128-token groups, ``N`` whole lane groups, a block of heads whole lane
    groups); differentiable, by its own rule."""
    _check(x, dt, a, b, c, d)
    rows, seq, heads, width = x.shape
    layout = _layout(seq, heads, width, b.shape[2], chunk, block_h)
    y, last = _dual((x.reshape(rows, seq, heads * width), b.astype(x.dtype),
                     c.astype(x.dtype)), dt.astype(_F32), a.astype(_F32),
                    d.astype(_F32), layout)
    return y.reshape(x.shape), jax.lax.stop_gradient(last)


def ssd_kernel_packed(xbc, dt, a, d, *, head_dim: int, chunk: int = CHUNK,
                      block_h: int = BLOCK_H):
    """:func:`ssd_kernel` on ``xbc`` ``[B, S, H P + 2 N]``, the columns ``[x;
    b; c]`` of one array and nothing else (a layer's convolved projection),
    read where they lie where ``b`` starts at a whole block of ``N`` columns
    (else sliced out, as anywhere the kernels refuse)."""
    rows, seq, wide = xbc.shape
    heads = dt.shape[2]
    inner = heads * head_dim
    states = (wide - inner) // 2
    if inner % states:
        x, b, c = _columns(xbc, heads, head_dim)
        return ssd_kernel(x, dt, a, b, c, d, chunk=chunk, block_h=block_h)
    layout = _layout(seq, heads, head_dim, states, chunk, block_h,
                     b_at=inner // states, c_at=inner // states + 1)
    y, last = _dual((xbc,), dt.astype(_F32), a.astype(_F32), d.astype(_F32),
                    layout)
    return y.reshape(rows, seq, heads, head_dim), jax.lax.stop_gradient(last)


def ssd_fused_applies(seq: int, heads: int, head_dim: int, states: int,
                      mesh=None, platform: Optional[str] = None) -> bool:
    """The rule by which a Mamba-2 layer's recurrence runs the kernel pair:
    on a TPU, a row of whole chunks, states in whole lane groups, heads that
    fill whole lane groups some at a time, over one device or a mesh of one
    (XLA cannot partition a Mosaic call, and the dual has met no mesh).
    Everything else is the plain chunked form."""
    if (platform or jax.default_backend()) != "tpu":
        return False
    if seq % CHUNK or states % _LANES:
        return False
    if not _block_h(heads, head_dim, BLOCK_H):
        return False
    if mesh is not None and mesh.size > 1:
        return False
    return jax.device_count() == 1


def ssd(x, dt, a, b, c, d):
    """``(y, h_last)`` by the kernel pair where :func:`ssd_fused_applies`
    says so for these shapes; elsewhere :func:`ssd_chunked`, at chunks of
    ``PLAIN_CHUNK`` and ``GROUP_H`` heads at a time."""
    _check(x, dt, a, b, c, d)
    if ssd_fused_applies(x.shape[1], x.shape[2], x.shape[3], b.shape[2]):
        return ssd_kernel(x, dt, a, b, c, d)
    return ssd_chunked(x, dt, a, b, c, d)


def ssd_packed(xbc, dt, a, d, *, head_dim: int):
    """:func:`ssd` for a layer that holds ``x``, ``b`` and ``c`` as the
    columns ``[x; b; c]`` of one array ``[B, S, H P + 2 N]`` (its convolved
    projection). Where the kernels run they read the three where they lie;
    elsewhere the slices and the plain form."""
    heads = dt.shape[2]
    states = (xbc.shape[2] - heads * head_dim) // 2
    if ssd_fused_applies(xbc.shape[1], heads, head_dim, states):
        return ssd_kernel_packed(xbc, dt, a, d, head_dim=head_dim)
    x, b, c = _columns(xbc, heads, head_dim)
    return ssd_chunked(x, dt, a, b, c, d)
