"""Rows -> tokens under a rank's share of an expert layer: each token the sum
of its live built rows,

    y[t] = sum_j valid[t, j] * w[t, j] * rows[pos[t, j]]          (w absent: 1)

with ``rows`` ``[R, H]`` in the layer's type (the sorted list's built rows, an
expert's output or a cotangent), ``pos`` / ``valid`` ``[T, k]`` where each of
a token's k assignments sits in the list and whether it sits in a held
expert's group, ``w`` ``[T, k]`` f32; the products and the sums in float32
whatever the rows' type, nothing rounded between them, one cast to ``dtype``
at the end. :class:`Way` carries the integers both directions share. Two
forms:

* :func:`sum_slots`, plain ``jax.numpy``: a loop over the k slots, each turn a
  gather of ``[T, H]`` with a token's absent slot masked away, added to an f32
  ``[T, H]`` sum that is read and written every turn: ``k * 10 * T * H`` bytes
  whatever the routing, near the memory's rate. What runs off the TPU, what
  the tests and the benchmark's references are held to, and what the kernel
  is held to;
* :func:`rows_kernel`: the built rows brought into the tokens' order once
  (one R-long sort of integers, one gather of ``[R, H]`` in the rows' own
  type, in XLA) and a Pallas kernel that reads each of them once and writes
  each token once. Its grid walks pairs of (a block of ``block_t`` tokens, a
  chunk of ``block_r`` sorted rows that holds rows of that block), in order,
  from tables made in XLA and prefetched as scalars: the chunk's tokens and
  weights become a ``[block_t, block_r]`` matrix with a row's weight where the
  row is the token's and zeros elsewhere, and the matrix unit sums the rows
  into an f32 block in VMEM. A bf16 row is exact on the matrix unit; an f32
  weight goes in as three bf16 pieces that add up to it exactly, so every
  product is exact in f32 and the sums are the unit's f32 sums. Nothing is
  ``[T, k, H]`` or ``[k, T, H]``, no f32 ``[T, H]`` sum is read back, and
  the bytes are ``3 * R * H`` in the rows' type and one ``[T, H]`` write.

:func:`sum_rows` chooses between them from the platform and the shapes
(:func:`rows_sum_applies`), as :mod:`.conv` does: no flag. Neither form is
differentiated through: :mod:`..models.moe`'s ``_rows_to_tokens`` and
``_sum_back`` carry their own rules.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["Way", "sum_rows", "sum_slots", "rows_kernel", "rows_sum_applies",
           "BLOCK_T", "BLOCK_R"]

# timed on the v5e (PERF.md section 6, PR 47)
BLOCK_T = 256  # tokens a grid step sums into
BLOCK_R = 128  # sorted rows a grid step reads
_LANES = 128
_F32 = jnp.float32
_TYPES = (jnp.bfloat16, jnp.float32)  # the rows the matrix unit sums exactly
_DEAD = jnp.iinfo(jnp.int32).max  # a dead row's key: after every live one


class Way(NamedTuple):
    """Where the ``R`` built rows of a rank's sorted list and the ``T`` tokens
    find each other: integers and booleans only."""

    head: jax.Array  # [R] the assignment (token * k + slot) a row stands for
    live: jax.Array  # [R] the row is in a held expert's group
    pos: jax.Array  # [T, k] where each assignment sits in the whole list
    valid: jax.Array  # [T, k] it sits in a held expert's group


def sum_slots(rows, way, weights=None):
    """Each token's live rows summed in f32, [R, H] -> [T, H] f32, each row
    times its assignment's weight (``weights`` [T, k]) first if given: one
    loop over the k slots, each turn a gather of ``[T, H]`` in the rows' own
    type with a token's absent slot left out. Nothing is ``[T, k, H]``, and
    nothing is rounded between the product and the sum. (Written out as k
    gathers it ran no faster and cost the compiler 10 s a start: PERF.md.)"""
    t, k = way.pos.shape
    pos = jnp.minimum(way.pos, rows.shape[0] - 1).T  # [k, T]
    held = way.valid.T
    scale = None if weights is None else jnp.asarray(weights).T

    def slot(j, y):
        row = jnp.take(rows, pos[j], axis=0).astype(jnp.float32)
        if scale is not None:
            row = row * scale[j][:, None]
        return y + jnp.where(held[j][:, None], row, 0)

    zero = jnp.zeros((t, rows.shape[1]), jnp.float32)
    return slot(0, zero) if k == 1 else jax.lax.fori_loop(0, k, slot, zero)


def _sum_kernel(blk_ref, chk_ref, n_ref, *refs, weighted, block_t):
    from jax.experimental import pallas as pl

    tok_ref = refs[0]
    w_ref = refs[1] if weighted else None
    rows_ref, out_ref, acc_ref = refs[-3:]
    i = pl.program_id(0)
    block = blk_ref[i]

    @pl.when((i == 0) | (blk_ref[jnp.maximum(i - 1, 0)] != block))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_ref[0])
    def _():
        # [block_t, block_r]: row c of the chunk is token t of the block
        mine = (tok_ref[...] - block * block_t
                == jax.lax.broadcasted_iota(
                    jnp.int32, (block_t, tok_ref.shape[1]), 0))
        rows = rows_ref[...]
        if rows.dtype == _F32:
            acc_ref[...] += jax.lax.dot(
                jnp.where(mine, w_ref[...] if weighted else 1.0, 0.0), rows,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=_F32)
            return
        dot = functools.partial(jax.lax.dot, preferred_element_type=_F32)
        if not weighted:
            acc_ref[...] += dot(mine.astype(rows.dtype), rows)
            return
        # an f32 weight as three bf16 pieces that add up to it: each product
        # with a bf16 row is exact in f32
        p = jnp.where(mine, w_ref[...], 0.0)
        hi = p.astype(rows.dtype)
        p = p - hi.astype(_F32)
        mid = p.astype(rows.dtype)
        low = (p - mid.astype(_F32)).astype(rows.dtype)
        acc_ref[...] += dot(low, rows) + dot(mid, rows) + dot(hi, rows)

    @pl.when((i == n_ref[0] - 1)
             | (blk_ref[jnp.minimum(i + 1, pl.num_programs(0) - 1)] != block))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _pairs(key, tokens, k, block_t, block_r):
    """The grid's tables from the sorted keys (token * k + slot, the dead
    rows' last): for each step its block of tokens and its chunk of sorted
    rows, and how many steps are real. A block's rows are the chunks from
    the one its first row is in to the one its last row is in, one chunk at
    least (a block with no live row is still written, as zeros); at most
    ``blocks + chunks`` steps, the ones past the real ones standing on the
    last pair."""
    blocks, chunks = -(-tokens // block_t), key.shape[0] // block_r
    bounds = jnp.arange(blocks + 1, dtype=jnp.int32) * (block_t * k)
    starts = (key[None, :] < bounds[:, None]).sum(1, dtype=jnp.int32)
    first = jnp.minimum(starts[:-1] // block_r, chunks - 1)
    count = jnp.maximum(-(-starts[1:] // block_r) - first, 1)
    ends = jnp.cumsum(count)
    step = jnp.arange(blocks + chunks, dtype=jnp.int32)
    block = jnp.minimum((step[:, None] >= ends[None, :]).sum(
        1, dtype=jnp.int32), blocks - 1)
    chunk = first[block] + jnp.minimum(
        step - (ends - count)[block], count[block] - 1)
    return block, chunk, ends[-1:]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _rows_sum(rows, way, weights, dtype, block_t, block_r):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, h = rows.shape
    tokens, k = way.pos.shape  # all the kernel's side reads of pos, valid
    head, live = way.head.astype(jnp.int32), way.live
    weighted = weights is not None
    key, by_token = jax.lax.sort_key_val(
        jnp.where(live, head, _DEAD), jnp.arange(r, dtype=jnp.int32))
    alive = key != _DEAD
    # the rows in their tokens' order (the indices a permutation: a gather
    # that need not fill is twice as fast), the dead ones' places zeros
    ordered = jnp.where(alive[:, None], jnp.take(
        rows, by_token, axis=0, mode="clip"), 0)
    tok = jnp.where(alive, key // k, -1).reshape(r // block_r, 1, block_r)
    block, chunk, real = _pairs(key, tokens, k, block_t, block_r)
    per_chunk = pl.BlockSpec((None, 1, block_r),
                             lambda i, blk, chk, n: (chk[i], 0, 0))
    operands, specs = [tok], [per_chunk]
    if weighted:
        operands.append(jnp.where(alive, jnp.take(
            weights.reshape(-1).astype(_F32), key, mode="clip"), 0).reshape(
                r // block_r, 1, block_r))
        specs.append(per_chunk)
    passes = 3 if weighted else 1
    return pl.pallas_call(
        functools.partial(_sum_kernel, weighted=weighted, block_t=block_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(block.shape[0],),
            in_specs=[*specs, pl.BlockSpec(
                (block_r, h), lambda i, blk, chk, n: (chk[i], 0))],
            out_specs=pl.BlockSpec((block_t, h),
                                   lambda i, blk, chk, n: (blk[i], 0)),
            scratch_shapes=[pltpu.VMEM((block_t, h), _F32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, h), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * passes * block_t * r * h, transcendentals=0,
            bytes_accessed=r * h * rows.dtype.itemsize
            + tokens * h * jnp.dtype(dtype).itemsize),
        name="rows_sum",
    )(block, chunk, real, *operands, ordered)


def rows_kernel(rows, way, weights=None, *, dtype=None,
                block_t: int = BLOCK_T, block_r: int = BLOCK_R):
    """:func:`sum_slots` cast to ``dtype`` (f32 if None) by the sort, the
    gather and the Pallas kernel: ``R`` in whole chunks of ``block_r`` rows,
    ``H`` in whole 128-lane groups, bf16 or f32 rows. It reads ``way.head``
    and ``way.live`` (which rows are whose), not ``pos`` and ``valid`` (the
    same, told from the tokens' side)."""
    r, h = rows.shape
    if (r % block_r or block_r % _LANES or h % _LANES or block_t % 8
            or rows.dtype not in _TYPES):
        raise ValueError(
            f"the rows' kernel takes built rows in whole chunks of {block_r} "
            f"(whole {_LANES}s), widths in whole groups of {_LANES} lanes, "
            f"token blocks in eights and bf16 or f32 rows; got {r} rows of "
            f"{h} in {rows.dtype} and blocks of {block_t} tokens")
    return _rows_sum(rows, way, weights, jnp.dtype(dtype or _F32), block_t,
                     block_r)


def rows_sum_applies(tokens: int, rows: int, width: int, k: int, mesh=None,
                     platform: Optional[str] = None) -> bool:
    """The rule by which a share's rows -> tokens runs the kernel: on a TPU,
    more than one slot a token (with one, the plain form is one gather and
    there is no loop to remove), a list of at most half the assignments (the
    usual list: the longer the list, the less the loop's masked gathers
    waste, and the worst-case list of ``T * k`` rows, which sizes the step's
    memory and hardly ever runs, would hold a second copy of itself), the
    built rows in whole chunks and the width in whole lane groups, tokens in
    eights, over one device or a mesh of one (XLA cannot partition a Mosaic
    call, and under a ``'model'`` axis the rows are another device's).
    Everything else is the plain form."""
    if (platform or jax.default_backend()) != "tpu":
        return False
    if k < 2 or 2 * rows > tokens * k:
        return False
    if rows % BLOCK_R or width % _LANES or tokens % 8:
        return False
    if mesh is not None and mesh.size > 1:
        return False
    return jax.device_count() == 1


def sum_rows(rows, way, weights=None, *, dtype=None):
    """Each token's live rows summed in f32 and cast to ``dtype`` (f32 if
    None), [R, H] -> [T, H], each row times its assignment's weight
    (``weights`` [T, k] f32) first if given. By the kernel where
    :func:`rows_sum_applies` says so for these shapes and bf16 or f32 rows,
    and the gauge ``rows_sum_fused`` then reads 1 (``train()`` sets it to 0
    for a model with expert layers and puts it on every log line);
    elsewhere ``sum_slots(...).astype(dtype)`` to the letter."""
    tokens, k = way.pos.shape
    if (rows_sum_applies(tokens, rows.shape[0], rows.shape[1], k)
            and rows.dtype in _TYPES):
        from ..obs.registry import default_registry

        default_registry().gauge("rows_sum_fused").set(1.0)
        return rows_kernel(rows, way, weights, dtype=dtype)
    return sum_slots(rows, way, weights).astype(dtype or jnp.float32)
