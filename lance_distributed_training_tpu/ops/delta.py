"""The gated delta rule (Gated Delta Networks, arXiv:2412.06464): the
recurrence of a linear-attention layer whose state is a matrix a head, the
first mixer here that :mod:`.scan`'s elementwise state cannot express.

    S <- exp(g_t) S                          S: [d_k, d_v], S_{-1} = 0
    S <- S + k_t (beta_t (v_t - S' k_t))'    a rank-one correction
    o_t = S' q_t

per row and value head, with ``q``, ``k`` ``[B, L, Hk, d_k]``, ``v`` ``[B, L,
Hv, d_v]``, ``g`` (<= 0) and ``beta`` ``[B, L, Hv]``; key head ``j`` serves
the value heads ``j * Hv / Hk`` onwards (``repeat_interleave``). ``q`` and
``k`` come normed and scaled, or the rule does it (``qk_norm``: a head's
``q / sqrt(|q|^2 + 1e-6) / sqrt(d_k)`` and ``k / sqrt(|k|^2 + 1e-6)`` in
float32, rounded once to the operands' type). Token by token that is ``L``
dependent steps of vector work; a chunk of ``C`` tokens at a time (``gamma``
the running sum of ``g`` inside the chunk, ``D_ij = exp(gamma_i - gamma_j)``
for ``i >= j``) it is matrix products:

    A  = strictly-lower(diag(beta) (K K' * D))      T = (I + A)^-1
    W  = T diag(beta) (exp(gamma) * K)              U = T diag(beta) V
    V* = U - W S        O = (exp(gamma) * Q) S + lower(Q K' * D) V*
    S <- exp(gamma_C) S + (exp(gamma_C - gamma) * K)' V*

``A``, ``T``, ``W``, ``U`` and the two masked score matrices do not depend
on ``S`` (``T`` by inverting the 16 x 16 diagonal blocks as the product ``(I
- A)(I + A^2)(I + A^4)(I + A^8)`` of a nilpotent block and merging them in
pairs, 16 -> 32 -> 64, ten products of ``[64, 64]`` a chunk, two more in the
backward pass: a product over the whole chunk's powers is exact too, but
their entries grow like binomials where keys are alike and cancel in
float32). What is left is sequential over the chunks, four products of ``[C,
128]`` by ``[128, 128]`` each. Two forms:

* :func:`delta_chunked`: plain ``jax.numpy``, differentiated by JAX.
  :func:`_prepare` makes what does not depend on the state for all ``L / C``
  chunks at once, in XLA, float32 ``[Hv, L, C]`` arrays in HBM between its
  fusions; a ``lax.scan`` walks a row's chunks, all heads a turn. What runs
  off the TPU, and what the kernels are held to;
* :func:`delta_kernel`: three Pallas kernels over grid (row, block of heads,
  pair of chunks) that read ``q``, ``k``, ``v`` as the layer has them (a
  head is a group of lanes of ``[B, L, H * d]``) and the per-token ``gamma``
  and ``beta``, make everything above from them in VMEM, two chunks' ``[C,
  C]`` matrices side by side along the 128 lanes, and carry a block of
  heads' states across a row's chunks in float32 scratch. Nothing as large
  as ``[Hv, L, C]`` exists in HBM in float32 but the inverses, and those
  only inside the backward pass. ``delta_rule_fwd`` gives ``o`` and the last
  state; the rule's own differentiation (``custom_vjp`` over ``q, k, v, g,
  beta``) keeps the inputs alone, makes the forward again
  (``delta_rule_fwd_kept``: the state each chunk starts from, in the
  operands' type, and each chunk's ``T``) and walks the chunks in reverse
  (``delta_rule_bwd``) with the state's cotangent in VMEM, carrying a
  chunk's cotangents through the recurrence and on through its preparation
  to ``dq``, ``dk``, ``dv``, ``d gamma`` and ``d beta`` (``-T' G T'`` for
  the inverse). Only the running sum that makes ``gamma`` of ``g``, its
  transpose and the two layouts of the per-token scalars stay in XLA. Under
  ``qk_norm`` the kernels norm each key head's ``[2C, d_k]`` tile as they
  load it and ``delta_rule_bwd`` turns the cotangents of the normed ``q``
  and ``k`` into those of the raw ones before it writes them, and ``q``,
  ``k``, ``v`` may be the columns ``[q; k; v]`` of one array (a layer's
  convolved projection, :func:`gated_delta_rule_packed`), which the block
  specifications walk where they lie: no slice and no normed copy is
  written out in front of the call.

Both forms cast where the other does: ``g``, its sums and exponentials, the
inverse (its products at the highest precision) and the state are float32;
the operands of every other product are in ``q``'s type with float32
accumulation. :func:`gated_delta_rule` chooses between the forms from the
platform and the shapes (:func:`delta_fused_applies`), as :mod:`.scan` and
:mod:`.flash` do: no flag. Both return ``(o, S_last)``: ``S_last`` ``[B, Hv,
d_k, d_v]`` is the state at each row's end, for a gauge; it takes no
gradient.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["gated_delta_rule", "gated_delta_rule_packed", "delta_chunked",
           "delta_kernel", "delta_fused_applies", "unit_heads", "CHUNK",
           "BLOCK_H", "GROUP_H"]

CHUNK = 64  # tokens a chunk: the published implementation's
BLOCK_H = 8  # heads a grid step holds (on the v5e 5% ahead of 4, 8% of 2:
# PERF.md section 6, PR 42)
GROUP_H = 8  # value heads whose preparation is alive at once
_BASE = 16  # the diagonal blocks inverted by powers
_LANES = 128
_PAIR = 2 * CHUNK  # tokens a grid step of the kernels: two chunks, whose
# [C, C] matrices lie side by side along the 128 lanes
_ABREAST = 4  # heads of a block whose chains of products a kernel writes
# side by side, step by step (the v5e has four matrix units)
_NORM_EPS = 1e-6  # under the root of a head's unit norm
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _check(q, k, v, g, beta):
    rows, seq, key_heads, _ = q.shape
    heads = v.shape[2]
    if k.shape != q.shape or v.shape[:2] != (rows, seq) or heads % key_heads \
            or g.shape != (rows, seq, heads) or beta.shape != g.shape:
        raise ValueError(
            "the gated delta rule takes q, k [B, L, Hk, d_k], v [B, L, Hv, "
            "d_v] with Hk dividing Hv, and g, beta [B, L, Hv]; got "
            f"{q.shape}, {k.shape}, {v.shape}, {g.shape}, {beta.shape}")


def _unit(t):
    """``(t r, r)`` float32 for ``r = (|t|^2 + 1e-6)^-1/2`` over the last
    axis: a head's unit vector and what made it one."""
    t = t.astype(_F32)
    r = jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + _NORM_EPS)
    return t * r, r


def unit_heads(t):
    """``t`` ``[..., d]`` in float32 over ``sqrt(|t|^2 + 1e-6)``: the plain
    form of a head's unit norm (the caller scales and rounds)."""
    return _unit(t)[0]


def _normed(t, is_key):
    """``q`` (``is_key`` 0) or ``k`` ``[B, L, Hk, d_k]`` raw as the rule
    takes it: a unit vector a head, ``q`` over ``sqrt(d_k)``, in its type."""
    unit = unit_heads(t)
    return (unit if is_key else unit * t.shape[-1] ** -0.5).astype(t.dtype)


# -- what does not depend on the state ----------------------------------------


def _grid(c, side_by_side=1):
    """The row and the column of every entry of a ``[c, c]`` matrix (``c`` a
    power of two), or of ``side_by_side`` of them along the lanes."""
    shape = (c, side_by_side * c)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1) & (c - 1))


def _blocks_inverses(mats):
    """``(I + a)^-1`` for each strictly lower triangular ``a`` ``[..., C,
    C]`` of a list, ``C`` a power of two, by blocks: the ``_BASE``-wide
    diagonal blocks at once as one block-diagonal matrix ``d`` (nilpotent of
    order ``_BASE``: ``(I - d)(I + d^2)(I + d^4)(I + d^8)`` is its inverse,
    and its powers stay block-diagonal), then neighbours merged in pairs,
    ``[[P, 0], [X, Q]]^-1 = T - T [[0, 0], [X, 0]] T`` for ``T = diag(P^-1,
    Q^-1)``, until one block is left. Every product is a whole ``[C, C]``
    one (the matrix unit takes a 16-wide block as it takes a 64-wide one).
    Each step is taken for every matrix of the list before the next: a
    matrix's ten products depend on each other, and a kernel's matrix units
    overlap products only across matrices and only where the program's
    order puts them side by side (PERF.md section 6, PR 42)."""
    c = mats[0].shape[-2]
    pair = mats[0].shape[-1] == 2 * c  # the kernels': [a0 | a1], as one

    def mm(x, y):
        return jnp.matmul(x, _stack(y) if pair else y, precision=_HIGHEST)

    row, col = _grid(c, 1 + pair)  # two-dimensional: a kernel's iota has to be
    eye = (row == col).astype(_F32)
    base = min(_BASE, c)
    powers = [jnp.where(row // base == col // base, a, 0.0) for a in mats]
    invs = [eye - p for p in powers]
    for _ in range(max(base.bit_length() - 2, 0)):
        powers = [mm(p, p) for p in powers]
        invs = [mm(i, eye + p) for i, p in zip(invs, powers)]
    size = base
    while size < c:
        between = (row // (2 * size) == col // (2 * size)) & (
            row // size != col // size)
        cross = [mm(i, jnp.where(between, a, 0.0))
                 for i, a in zip(invs, mats)]
        invs = [i - mm(x, i) for i, x in zip(invs, cross)]
        size *= 2
    return invs


def _blocks_inverse(a):
    return _blocks_inverses([a])[0]


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``T = (I + a)^-1`` for strictly lower triangular ``a`` ``[..., C, C]``
    in float32, every product at the highest precision. Its cotangent is
    ``-T' G T'``: two products, and ``T`` is all the backward pass keeps."""
    return _blocks_inverse(a)


def _unit_lower_inverse_bwd(t_inv, g):
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    t_t = jnp.swapaxes(t_inv, -1, -2)
    return (-mm(mm(t_t, g), t_t),)


_unit_lower_inverse.defvjp(lambda a: (_blocks_inverse(a),) * 2,
                           _unit_lower_inverse_bwd)


def _prepare(q, k, v, g, beta, chunk):
    """The chunks' ``(W, U, Qg, P, Kg, decay)``: ``W``, ``Qg``, ``Kg`` ``[B,
    Hv, N, C, d_k]`` and ``P`` ``[B, Hv, N, C, C]`` in ``q``'s type, ``U``
    ``[B, Hv, N, C, d_v]`` and ``decay`` ``[B, Hv, N]`` float32. A row is
    padded to whole chunks with tokens that leave the state as it is."""
    rows, seq, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    rep = heads // key_heads
    dtype = q.dtype
    pad = -seq % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (
            t.ndim - 2)) for t in (q, k, v, g, beta))
    n = (seq + pad) // chunk

    def chunks(t):  # [B, L, H, ...] -> [B, H, N, C, ...]
        return jnp.moveaxis(t.reshape(rows, n, chunk, *t.shape[2:]), 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    gamma = jnp.cumsum(chunks(g.astype(_F32)), axis=-1)  # [B, Hv, N, C]
    beta = chunks(beta.astype(_F32))
    at = jnp.arange(chunk)
    lower = at[:, None] >= at[None, :]
    # exp of a difference that is <= 0 wherever it is used; the others never
    # reach the exponential (they would overflow where a chunk decays far)
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    f32_out = dict(preferred_element_type=_F32)
    # one product a key head; D and beta are the value heads' own
    kk = jnp.einsum("bgnid,bgnjd->bgnij", k, k, **f32_out)
    qk = jnp.einsum("bgnid,bgnjd->bgnij", q, k, **f32_out)

    def per_value_head(t):  # [B, Hk, N, ...] -> [B, Hv, N, ...]
        return jnp.repeat(t, rep, axis=1) if rep > 1 else t

    a = jnp.where(at[:, None] > at[None, :],
                  beta[..., :, None] * per_value_head(kk) * decay, 0.0)
    t_inv = _unit_lower_inverse(a)
    # T diag(beta) and T diag(beta exp(gamma)): scaled columns, then one
    # product each with K and V (K repeated for its value heads: grouped as
    # one product a key head XLA:CPU is left a bf16 dot with f32 sums that
    # it cannot run)
    t_v = (t_inv * beta[..., None, :]).astype(dtype)
    t_k = (t_inv * (beta * jnp.exp(gamma))[..., None, :]).astype(dtype)
    k = per_value_head(k)
    w = jnp.einsum("bhnij,bhnjd->bhnid", t_k, k, **f32_out)
    u = jnp.einsum("bhnij,bhnjd->bhnid", t_v, v, **f32_out)
    p = (per_value_head(qk) * decay).astype(dtype)
    q_g = (per_value_head(q).astype(_F32) * jnp.exp(gamma)[..., None])
    k_g = k.astype(_F32) * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    return (w.astype(dtype), u, q_g.astype(dtype), p, k_g.astype(dtype),
            jnp.exp(gamma[..., -1]))


def _finish(o, last, seq, dtype):
    """``o`` [B, Hv, N, C, d_v] as ``[B, L, Hv, d_v]``, padding dropped."""
    rows, heads, n, chunk, d_v = o.shape
    o = jnp.moveaxis(o, 1, 3).reshape(rows, n * chunk, heads, d_v)
    return o[:, :seq].astype(dtype), jax.lax.stop_gradient(last)


# -- the plain chunked form ----------------------------------------------------


def delta_chunked(q, k, v, g, beta, *, chunk: int = CHUNK):
    """The rule in plain ``jax.numpy``, ``chunk`` tokens at a time."""
    _check(q, k, v, g, beta)
    dtype = q.dtype
    w, u, q_g, p, k_g, decay = _prepare(q, k, v, g, beta, chunk)
    f32_out = dict(preferred_element_type=_F32)

    @jax.checkpoint
    def body(s, parts):
        w_c, u_c, q_c, p_c, k_c, d_c = parts
        s_op = s.astype(dtype)
        v_new = (u_c - jnp.einsum("bhck,bhkv->bhcv", w_c, s_op, **f32_out)
                 ).astype(dtype)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_c, s_op, **f32_out)
             + jnp.einsum("bhcj,bhjv->bhcv", p_c, v_new, **f32_out))
        s = d_c[..., None, None] * s + jnp.einsum(
            "bhck,bhcv->bhkv", k_c, v_new, **f32_out)
        return s, o

    rows, heads = w.shape[:2]
    last, o = jax.lax.scan(
        body, jnp.zeros((rows, heads, w.shape[-1], u.shape[-1]), _F32),
        tuple(jnp.moveaxis(t, 2, 0) for t in (w, u, q_g, p, k_g, decay)))
    return _finish(jnp.moveaxis(o, 0, 2), last, q.shape[1], dtype)


# -- the kernels ---------------------------------------------------------------
#
# A grid step is (row, block of value heads, pair of chunks); a block's pairs
# follow each other and its states ride from one to the next in float32
# scratch. The step reads the pair's own q, k [2C, d_k] a key head and v [2C,
# d_v] a value head straight from the layer's [B, L, H * d] arrays (a head is
# a group of lanes) and the tokens' gamma and beta twice, down the sublanes
# ([2C, heads]: a token's scalar for its row of a matrix) and along the
# lanes ([heads, 2C]: for its column): Mosaic turns no vector of 64.
# Everything else of :func:`_prepare` is made from those in VMEM and dies
# there. The two chunks of a pair do not depend on each other until the
# state enters, so every [C, C] matrix of the preparation is made for both at
# once, side by side along the 128 lanes ([C, 2C]: the vector unit's tiles
# and the matrix unit's columns are full), and a product of two such pairs is
# one [C, 2C] by [2C, 2C] product with the right-hand pair laid out block-
# diagonally. The heads of a block are independent chains of dependent
# products, and the matrix units overlap only what the program's order puts
# side by side: the kernels take _ABREAST heads through every step together.


def _left(shape):
    """Where a ``[., 2C]`` pair holds its first chunk's matrix."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) < shape[1] // 2


def _halves(x):
    """A pair ``[x0 | x1]`` ``[C, 2C]`` as ``[x0 | 0]`` and ``[0 | x1]``."""
    left = _left(x.shape)
    return (jnp.where(left, x, jnp.zeros_like(x)),
            jnp.where(left, jnp.zeros_like(x), x))


def _stack(x):
    """A pair ``[x0 | x1]`` ``[C, 2C]`` as ``[[x0, 0], [0, x1]]``."""
    return jnp.concatenate(_halves(x), 0)


def _unstack(y):
    """The diagonal blocks of ``[2C, 2C]`` as a pair ``[C, 2C]``."""
    c = y.shape[0] // 2
    return jnp.where(_left((c, 2 * c)), y[:c], y[c:])


def _row_sums(x):
    """Each half of a pair summed along its rows: ``[2C, 1]``, the first
    chunk's tokens then the second's."""
    return jnp.concatenate(
        [jnp.sum(half, 1, keepdims=True) for half in _halves(x)], 0)


def _beside(col):
    """A pair's per-token column ``[2C, 1]`` spread along the rows of the
    pair's matrices: ``[C, 2C]``."""
    c = col.shape[0] // 2
    return jnp.where(_left((c, 2 * c)), col[:c], col[c:])


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _unit_tile(t, scale=None):
    """:func:`unit_heads` of one head's rows ``[2C, d]`` as a kernel loads
    them, times ``scale``, rounded once to their type; and what the way
    back needs of it, float32: the unit rows ``u = r t`` and ``r = (|t|^2 +
    1e-6)^-1/2`` times ``scale``."""
    unit, r = _unit(t)
    if scale is None:
        return unit.astype(t.dtype), (unit, r)
    return (unit * scale).astype(t.dtype), (unit, r * scale)


def _unit_tile_back(kept, d_normed):
    """The cotangent of a head's raw rows from that of their normed selves,
    float32: ``r (d - u sum(d * u))``."""
    unit, r = kept
    return r * (d_normed - unit * jnp.sum(d_normed * unit, 1, keepdims=True))


class _Head(NamedTuple):
    """What a grid step reads for one value head: its number in the block,
    its key head's ``q``, ``k`` ``[2C, d_k]`` and their two pairs of score
    matrices, its ``v`` ``[2C, d_v]``, and the tokens' ``gamma`` and
    ``beta`` down the sublanes (``_col``, ``[2C, 1]``) and along the lanes
    (``_row``, ``[1, 2C]``)."""
    h: int
    q: jax.Array
    k: jax.Array
    v: jax.Array
    kk: jax.Array  # K K' of both chunks, float32 [C, 2C]
    qk: jax.Array
    g_col: jax.Array
    g_row: jax.Array
    b_col: jax.Array
    b_row: jax.Array
    normed: Optional[tuple] = None  # of q and of k, for the way back


class _Pair(NamedTuple):
    """One value head's pair of chunks, as values in VMEM: ``[2C, .]`` the
    first chunk's tokens then the second's, ``[C, 2C]`` the two chunks'
    matrices side by side."""
    t_inv: jax.Array  # (I + A)^-1 of both, float32 [C, 2C]
    t_k: jax.Array  # T diag(beta exp(gamma)), in the operands' type
    t_v: jax.Array  # T diag(beta)
    w: jax.Array  # [2C, d_k]
    u: jax.Array  # [2C, d_v], float32
    q_g: jax.Array
    p: tuple  # [P0 | 0] and [0 | P1]: P_i v = p[i] @ [. ; v] or [v ; .]
    k_g: jax.Array
    mask: jax.Array  # D: exp(gamma_i - gamma_j) on and below the diagonal
    e_col: jax.Array  # exp(gamma), [2C, 1]
    e_row: jax.Array  # the same along the lanes, [1, 2C]
    e_left: jax.Array  # exp(gamma_C - gamma), [2C, 1]
    g_last: tuple  # gamma_C of each chunk, [1, 1]
    decay: tuple  # exp(gamma_C) of each chunk along the lanes, [1, d_v]


def _pairs(heads, t_invs=None):
    """:func:`_prepare` for one pair of chunks of each of some value heads,
    with its casts, every step for all heads before the next. The backward
    pass hands in the inverses it kept."""
    dtype = heads[0].k.dtype
    c = heads[0].k.shape[0] // 2
    row, col = _grid(c, 2)
    masks = [jnp.exp(jnp.where(row >= col, _beside(head.g_col) - head.g_row,
                               -jnp.inf)) for head in heads]
    if t_invs is None:
        t_invs = _blocks_inverses([
            jnp.where(row > col, _beside(head.b_col) * head.kk * mask, 0.0)
            for head, mask in zip(heads, masks)])
    e_rows = [jnp.exp(head.g_row) for head in heads]
    t_vs = [(t * head.b_row).astype(dtype) for t, head in zip(t_invs, heads)]
    t_ks = [(t * (head.b_row * e_row)).astype(dtype)
            for t, head, e_row in zip(t_invs, heads, e_rows)]
    ws = [_dot(_stack(t_k), head.k, _NN).astype(dtype)
          for t_k, head in zip(t_ks, heads)]
    us = [_dot(_stack(t_v), head.v, _NN) for t_v, head in zip(t_vs, heads)]
    parts = []
    for head, t_inv, t_k, t_v, w, u, mask, e_row in zip(
            heads, t_invs, t_ks, t_vs, ws, us, masks, e_rows):
        g_col = head.g_col
        first = jax.lax.broadcasted_iota(jnp.int32, g_col.shape, 0) < c
        g_last = (g_col[c - 1:c], g_col[2 * c - 1:])
        e_col = jnp.exp(g_col)
        e_left = jnp.exp(jnp.where(first, *g_last) - g_col)
        # gamma_C along the lanes, for the state: Mosaic spreads a [1, 1]
        # value over lanes or over sublanes, not over both at once
        at = jax.lax.broadcasted_iota(jnp.int32, head.v.shape, 0)
        decay = tuple(
            jnp.exp(jnp.sum(jnp.where(at == i, g_col, 0.0), 0, keepdims=True))
            for i in (c - 1, 2 * c - 1))
        parts.append(_Pair(
            t_inv, t_k, t_v, w, u,
            (head.q.astype(_F32) * e_col).astype(dtype),
            _halves((head.qk * mask).astype(dtype)),
            (head.k.astype(_F32) * e_left).astype(dtype), mask, e_col, e_row,
            e_left, g_last, decay))
    return parts


def _heads(refs, block_h, rep, d_k, d_v, qk_norm):
    """A block's value heads, ``_ABREAST`` or so at a time (whole key
    heads'), each with what the kernel reads for it; a key head's rows are
    normed (``qk_norm``) and its two pairs of score matrices made once for
    the value heads it serves."""
    q_ref, k_ref, v_ref, gc_ref, gr_ref, bc_ref, br_ref = refs
    g_cols, g_rows = gc_ref[0, 0, 0], gr_ref[0, 0, 0]  # [2C, bh], [bh, 2C]
    b_cols, b_rows = bc_ref[0, 0, 0], br_ref[0, 0, 0]
    abreast = rep * max(1, _ABREAST // rep)
    for start in range(0, block_h, abreast):
        heads = []
        for j in range(start // rep, min(start + abreast, block_h) // rep):
            q = q_ref[0, :, j * d_k:(j + 1) * d_k]
            k = k_ref[0, :, j * d_k:(j + 1) * d_k]
            normed = None
            if qk_norm:
                (q, k), normed = zip(_unit_tile(q, d_k ** -0.5),
                                     _unit_tile(k))
            kk, qk = _unstack(_dot(k, k, _NT)), _unstack(_dot(q, k, _NT))
            heads += [
                _Head(h, q, k, v_ref[0, :, h * d_v:(h + 1) * d_v], kk, qk,
                      g_cols[:, h:h + 1], g_rows[h:h + 1],
                      b_cols[:, h:h + 1], b_rows[h:h + 1], normed)
                for h in range(j * rep, (j + 1) * rep)]
        yield heads


def _fwd_kernel(*refs, block_h, rep, d_k, d_v, qk_norm, keep):
    """The rule forward over one pair of chunks: ``o`` and the state at the
    row's end or, for a backward pass (``keep``), the state each chunk
    starts from and the pair's inverses and nothing else."""
    from jax.experimental import pallas as pl

    ins, (first_ref, second_ref, s_ref) = refs[:7], refs[7:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    for heads in _heads(ins, block_h, rep, d_k, d_v, qk_norm):
        at_h = [head.h for head in heads]
        dtype = heads[0].k.dtype
        c = heads[0].k.shape[0] // 2
        parts = _pairs(heads)
        if keep:
            for h, part in zip(at_h, parts):
                second_ref[0, h, 0] = part.t_inv
        states = [s_ref[h] for h in at_h]
        firsts = None
        for i, at in enumerate((slice(0, c), slice(c, 2 * c))):
            s_ops = [s.astype(dtype) for s in states]
            v_news = [(part.u[at] - _dot(part.w[at], s_op, _NN)).astype(dtype)
                      for part, s_op in zip(parts, s_ops)]
            firsts = firsts or v_news
            for h, part, s_op, first, v_new in zip(at_h, parts, s_ops,
                                                   firsts, v_news):
                if keep:
                    first_ref[0, h, i] = s_op
                else:
                    first_ref[0, at, h * d_v:(h + 1) * d_v] = (
                        _dot(part.q_g[at], s_op, _NN) + _dot(
                            part.p[i], jnp.concatenate([first, v_new], 0),
                            _NN)).astype(first_ref.dtype)
            states = [part.decay[i] * s + _dot(part.k_g[at], v_new, _TN)
                      for part, s, v_new in zip(parts, states, v_news)]
        for h, s in zip(at_h, states):
            s_ref[h] = s

    if not keep:
        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _():
            second_ref[0] = s_ref[...]


def _place(into, h, part, axis):
    """``into`` with ``part`` ([2C, 1] for ``axis`` 1, [1, 2C] for 0) as its
    ``h``-th column or row."""
    at = jax.lax.broadcasted_iota(jnp.int32, into.shape, axis)
    return jnp.where(at == h, part, into)


def _bwd_kernel(*refs, block_h, rep, d_k, d_v, qk_norm):
    """A pair of chunks' cotangents, from ``do`` and the state's, carried
    through the recurrence (the second chunk, then the first) and on through
    the chunks' preparation to ``dq``, ``dk``, ``dv`` and, by columns and by
    rows, ``d gamma`` and ``d beta``: ``-T' G T'`` for the inverse, row and
    column sums of ``dD * D`` for the mask; under ``qk_norm`` on through
    the norm of ``q`` and ``k`` to the raw rows' cotangents."""
    from jax.experimental import pallas as pl

    ins = refs[:7]
    start_ref, t_ref, do_ref = refs[7:10]
    dq_ref, dk_ref, dv_ref, dgc_ref, dgr_ref, dbc_ref, dbr_ref, ds_ref = \
        refs[10:]

    @pl.when(pl.program_id(2) == 0)  # the row's last pair comes first
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    mm = functools.partial(jax.lax.dot_general, precision=_HIGHEST,
                           preferred_element_type=_F32)
    c = ins[0].shape[1] // 2
    row, col = _grid(c, 2)
    halves = (slice(0, c), slice(c, 2 * c))
    first = jax.lax.broadcasted_iota(jnp.int32, (2 * c, 1), 0) < c
    last = (jax.lax.broadcasted_iota(jnp.int32, (2 * c, 1), 0) & (c - 1)
            ) == c - 1
    dg_cols = jnp.zeros(dgc_ref.shape[3:], _F32)
    dg_rows = jnp.zeros(dgr_ref.shape[3:], _F32)
    db_cols, db_rows = dg_cols, dg_rows
    for heads in _heads(ins, block_h, rep, d_k, d_v, qk_norm):
        at_h = [head.h for head in heads]
        dtype = heads[0].k.dtype
        parts = _pairs(heads, [t_ref[0, h, 0] for h in at_h])
        # the recurrence, the heads abreast; both chunks' starts were kept,
        # so what hangs on the state alone is made for both at once
        dos = [do_ref[0, :, h * d_v:(h + 1) * d_v] for h in at_h]
        starts = [[start_ref[0, h, i] for i in (0, 1)] for h in at_h]
        v_news = [jnp.concatenate(
            [(part.u[at] - _dot(part.w[at], s_op, _NN)).astype(dtype)
             for at, s_op in zip(halves, start)], 0)
            for part, start in zip(parts, starts)]
        dus = [_dot(jnp.concatenate(part.p, 0), do, _TN)  # P' do, [2C, d_v]
               for part, do in zip(parts, dos)]
        dps = [_unstack(_dot(do, v_new, _NT))
               for do, v_new in zip(dos, v_news)]
        dss = [ds_ref[h] for h in at_h]  # dL/dS after this pair, float32
        chunks = [[None, None] for _ in heads]  # what a chunk hands on
        d_lasts = [[None, None] for _ in heads]  # d gamma_C by the decay
        for i in (1, 0):
            at = halves[i]
            ds_ops = [ds.astype(dtype) for ds in dss]
            du_ops = [(du[at] + _dot(part.k_g[at], ds_op, _NN)).astype(dtype)
                      for du, part, ds_op in zip(dus, parts, ds_ops)]
            for n, (part, start, do, v_new, ds, ds_op, du_op) in enumerate(
                    zip(parts, starts, dos, v_news, dss, ds_ops, du_ops)):
                s_op = start[i]
                chunks[n][i] = (  # dU, dW, dQg, dKg
                    du_op, (-_dot(du_op, s_op, _NT)).astype(dtype),
                    _dot(do[at], s_op, _NT), _dot(v_new[at], ds_op, _NT))
                d_lasts[n][i] = jnp.exp(part.g_last[i]) * jnp.sum(
                    ds * s_op.astype(_F32), keepdims=True)
            dss = [part.decay[i] * ds + _dot(part.q_g[at], do[at], _TN)
                   - _dot(part.w[at], du_op, _TN)
                   for part, ds, do, du_op in zip(parts, dss, dos, du_ops)]
        for h, ds in zip(at_h, dss):
            ds_ref[h] = ds
        # T = (I + A)^-1 of W = T diag(beta exp(gamma)) K, U = T diag(beta)
        # V: two dependent products a head, the heads abreast
        stacked = [[jnp.concatenate(both, 0) for both in zip(*chunk)]
                   for chunk in chunks]
        dt_ks = [_unstack(_dot(dw_op, head.k, _NT))
                 for (_, dw_op, _, _), head in zip(stacked, heads)]
        dt_vs = [_unstack(_dot(du_op, head.v, _NT))
                 for (du_op, _, _, _), head in zip(stacked, heads)]
        scaleds = [head.b_row * part.e_row  # beta exp(gamma), [1, 2C]
                   for head, part in zip(heads, parts)]
        das = [_unstack(mm(part.t_inv, dt_v * head.b_row + dt_k * scaled,
                           _TN))
               for part, head, dt_k, dt_v, scaled in zip(
                   parts, heads, dt_ks, dt_vs, scaleds)]
        das = [jnp.where(row > col, -mm(da, _stack(part.t_inv), _NT), 0.0)
               for da, part in zip(das, parts)]
        for (h, q, k, v, kk, qk, g_col, g_row, b_col, b_row, normed), part, \
                d_last, (du_op, dw_op, dq_g, dk_g), dp, dt_k, dt_v, scaled, \
                da in zip(heads, parts, d_lasts, stacked, dps, dt_ks, dt_vs,
                          scaleds, das):
            if h % rep == 0:
                dq = dk = jnp.zeros((2 * c, d_k), _F32)
                dkk = dqk = jnp.zeros((c, 2 * c), _F32)
            d_last = jnp.where(first, *d_last)  # [2C, 1]
            dk += _dot(_stack(part.t_k), dw_op, _TN)
            dv_ref[0, :, h * d_v:(h + 1) * d_v] = _dot(
                _stack(part.t_v), du_op, _TN).astype(dv_ref.dtype)
            d_scaled = jnp.sum(dt_k * part.t_inv, 0, keepdims=True)
            db_row = (jnp.sum(dt_v * part.t_inv, 0, keepdims=True)
                      + d_scaled * part.e_row)
            dg_row = d_scaled * scaled
            # A = strictly-lower(diag(beta) (K K' * D))
            da_mask = da * part.mask
            db_col = _row_sums(da_mask * kk)
            dkk += da_mask * _beside(b_col)
            # P = lower(Q K' * D); D enters A and P, gamma enters D twice
            dqk_h = dp * part.mask
            dqk += dqk_h
            d_mask = da_mask * kk * _beside(b_col) + dqk_h * qk  # dD * D
            dg_col = _row_sums(d_mask)
            dg_row -= jnp.sum(d_mask, 0, keepdims=True)
            # Qg = exp(gamma) * Q, Kg = exp(gamma_C - gamma) * K, exp(gamma_C)
            dq += dq_g * part.e_col
            dk += dk_g * part.e_left
            dg_col += jnp.sum(dq_g * q.astype(_F32), 1,
                              keepdims=True) * part.e_col
            left = jnp.sum(dk_g * k.astype(_F32), 1,
                           keepdims=True) * part.e_left
            sums = jnp.where(first, jnp.sum(left[:c], keepdims=True),
                             jnp.sum(left[c:], keepdims=True))
            dg_col += jnp.where(last, sums + d_last, 0.0) - left
            dg_cols, dg_rows = (_place(dg_cols, h, dg_col, 1),
                                _place(dg_rows, h, dg_row, 0))
            db_cols, db_rows = (_place(db_cols, h, db_col, 1),
                                _place(db_rows, h, db_row, 0))
            if h % rep == rep - 1:  # the key head's last value head
                at = (0, slice(None), slice(h // rep * d_k,
                                            (h // rep + 1) * d_k))
                dkk_op = _stack(dkk.astype(dtype))
                dqk_op = _stack(dqk.astype(dtype))
                dq = dq + _dot(dqk_op, k, _NN)
                dk = (dk + _dot(dkk_op, k, _NN) + _dot(dkk_op, k, _TN)
                      + _dot(dqk_op, q, _TN))
                if qk_norm:  # q and k are the normed rows: back to the raw
                    dq = _unit_tile_back(normed[0], dq)
                    dk = _unit_tile_back(normed[1], dk)
                dq_ref[at] = dq.astype(dq_ref.dtype)
                dk_ref[at] = dk.astype(dk_ref.dtype)
    dgc_ref[0, 0, 0], dgr_ref[0, 0, 0] = dg_cols, dg_rows
    dbc_ref[0, 0, 0], dbr_ref[0, 0, 0] = db_cols, db_rows


class _Layout(NamedTuple):
    """What the kernels' callers fix: the heads and their widths, the heads
    a grid step holds, whether the kernels norm ``q`` and ``k``, and whether
    ``q``, ``k``, ``v`` are the columns ``[q; k; v]`` of one array."""
    key_heads: int
    heads: int
    d_k: int
    d_v: int
    block_h: int
    qk_norm: bool
    packed: bool

    @property
    def rep(self):
        return self.heads // self.key_heads

    @property
    def keys(self):
        return self.key_heads * self.d_k

    @property
    def values(self):
        return self.heads * self.d_v

    @property
    def key_block(self):  # the lanes of a grid step's key heads
        return self.block_h // self.rep * self.d_k

    @property
    def value_block(self):
        return self.block_h * self.d_v

    def in_place(self, width: int) -> bool:
        """Do ``[q; k; v]`` in ``width`` columns start at whole blocks of a
        grid step's heads? (``k`` does: a block's key heads divide them.)"""
        return (width == 2 * self.keys + self.values
                and 2 * self.keys % self.value_block == 0)


def _pallas(kernel, name, operands, in_specs, out_specs, out_shape, state,
            flops_a_pair, packed):
    """One of the rule's kernels over grid (row, block of heads, pair of
    chunks), with a block's states (``state``: their shape) in scratch;
    ``packed``: the first three operands are one array."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, pairs, groups = operands[3].shape[:3]  # the scalars by columns
    steps = rows * pairs * groups * state[0]
    return pl.pallas_call(
        kernel, grid=(rows, groups, pairs), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(state, _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=steps * flops_a_pair,
            transcendentals=steps * _PAIR * (CHUNK + 4),
            bytes_accessed=sum(math.prod(t.shape) * t.dtype.itemsize
                               for t in (*operands[2 * packed:],
                                         *out_shape))),
        name=name)(*operands)


def _specs(layout, order):
    """The ``BlockSpec`` of each kind of array a kernel takes or gives, for
    grid (row, block of heads, step); ``order`` maps a step to its pair of
    chunks. ``ins`` reads ``q``, ``k``, ``v`` from arrays of their own or
    (``packed``) from the columns ``[q; k; v]`` of one."""
    from jax.experimental import pallas as pl

    def tokens(width, first=0):  # [B, L, .]: a pair of a block's heads' lanes
        return pl.BlockSpec((1, _PAIR, width),
                            lambda b, i, j: (b, order(j), first + i))

    def scalars(*shape):  # [B, N / 2, G, 2C, bh] or [B, N / 2, G, bh, 2C]
        return pl.BlockSpec((1, 1, 1, *shape),
                            lambda b, i, j: (b, order(j), i, 0, 0))

    def matrices(per_pair, *shape):  # [B, Hv, ., ., .]: some a head and pair
        return pl.BlockSpec((1, layout.block_h, per_pair, *shape),
                            lambda b, i, j: (b, i, order(j), 0, 0))

    block_h, d_k, d_v = layout.block_h, layout.d_k, layout.d_v
    keys, values = tokens(layout.key_block), tokens(layout.value_block)
    cols, rows = scalars(_PAIR, block_h), scalars(block_h, _PAIR)
    read = [keys, keys, values]
    if layout.packed:
        read = [keys, tokens(layout.key_block,
                             layout.keys // layout.key_block),
                tokens(layout.value_block,
                       2 * layout.keys // layout.value_block)]
    return dict(
        ins=read + [cols, rows, cols, rows], keys=keys,
        values=values, cols=cols, rows=rows, states=matrices(2, d_k, d_v),
        inverses=matrices(1, CHUNK, _PAIR),
        last=pl.BlockSpec((1, block_h, d_k, d_v),
                          lambda b, i, j: (b, i, 0, 0)))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _rule_forward(arrays, scalars, layout, keep):
    """``(o [B, L, Hv * d_v], S_last)`` or, for a backward pass (``keep``),
    the state each chunk starts from (in the operands' type: it is only
    ever an operand again) and each pair of chunks' inverses. ``arrays`` is
    ``(q, k, v)`` flat or, packed, the one array three times."""
    rows, pairs = scalars[0].shape[:2]
    heads, block_h, d_k, d_v = (layout.heads, layout.block_h, layout.d_k,
                                layout.d_v)
    rep, dtype = layout.rep, arrays[0].dtype
    spec = _specs(layout, lambda j: j)
    if keep:
        out_specs = [spec["states"], spec["inverses"]]
        out_shape = [
            jax.ShapeDtypeStruct((rows, heads, 2 * pairs, d_k, d_v), dtype),
            jax.ShapeDtypeStruct((rows, heads, pairs, CHUNK, _PAIR), _F32)]
    else:
        out_specs = [spec["values"], spec["last"]]
        out_shape = [jax.ShapeDtypeStruct(
            (rows, pairs * _PAIR, layout.values), dtype),
            jax.ShapeDtypeStruct((rows, heads, d_k, d_v), _F32)]
    return _pallas(
        functools.partial(_fwd_kernel, block_h=block_h, rep=rep, d_k=d_k,
                          d_v=d_v, qk_norm=layout.qk_norm, keep=keep),
        "delta_rule_fwd_kept" if keep else "delta_rule_fwd",
        (*arrays, *scalars), spec["ins"], out_specs, out_shape,
        (block_h, d_k, d_v),
        2 * _PAIR * ((_PAIR * d_k * (1 if keep else 2)) // rep
                     + 10 * 6 * _PAIR * CHUNK + _PAIR * (d_k + d_v)
                     + (2 if keep else 3) * d_k * d_v
                     + (0 if keep else _PAIR * d_v)), layout.packed)


@functools.partial(jax.jit, static_argnums=(5,))
def _rule_backward(arrays, scalars, starts, inverses, do, layout):
    rows, pairs = scalars[0].shape[:2]
    seq, dtype = pairs * _PAIR, arrays[0].dtype
    block_h, rep, d_k, d_v = (layout.block_h, layout.rep, layout.d_k,
                              layout.d_v)
    spec = _specs(layout, lambda j: pairs - 1 - j)
    return _pallas(
        functools.partial(_bwd_kernel, block_h=block_h, rep=rep, d_k=d_k,
                          d_v=d_v, qk_norm=layout.qk_norm),
        "delta_rule_bwd", (*arrays, *scalars, starts, inverses, do),
        spec["ins"] + [spec["states"], spec["inverses"], spec["values"]],
        [spec["keys"], spec["keys"], spec["values"], spec["cols"],
         spec["rows"], spec["cols"], spec["rows"]],
        [jax.ShapeDtypeStruct((rows, seq, width), dtype)
         for width in (layout.keys, layout.keys, layout.values)]
        + [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in scalars],
        (block_h, d_k, d_v),
        2 * _PAIR * ((6 * _PAIR * d_k) // rep + 2 * 6 * _PAIR * CHUNK
                     + 3 * _PAIR * (d_k + d_v) + 7 * d_k * d_v
                     + 2 * _PAIR * d_v), layout.packed)


def _scalars(g, beta, block_h):
    """``gamma`` (the running sum of ``g`` inside a chunk) and ``beta``,
    float32, each laid out for the kernels by columns ``[B, N / 2, G, 2C,
    bh]`` and by rows ``[B, N / 2, G, bh, 2C]`` for ``G`` blocks of ``bh``
    heads and pairs of chunks."""
    rows, seq, heads = g.shape
    shape = (rows, seq // _PAIR, _PAIR, heads // block_h, block_h)
    gamma = jnp.cumsum(g.astype(_F32).reshape(
        rows, seq // CHUNK, CHUNK, heads), axis=2).reshape(shape)
    cols = [jnp.moveaxis(t, 2, 3)
            for t in (gamma, beta.astype(_F32).reshape(shape))]
    by_rows = [jnp.swapaxes(t, 3, 4) for t in cols]
    return cols[0], by_rows[0], cols[1], by_rows[1]


def _per_token(cols, by_rows):
    """A cotangent by columns and by rows, summed, as ``[B, N, C, Hv]``."""
    both = cols + jnp.swapaxes(by_rows, 3, 4)  # [B, N / 2, G, 2C, bh]
    both = jnp.moveaxis(both, 2, 3)  # [B, N / 2, 2C, G, bh]
    return both.reshape(both.shape[0], -1, CHUNK,
                        both.shape[3] * both.shape[4])


def _operands(arrays, layout):
    """``(q, k, v)`` flat (a head is a group of lanes of ``[B, L, H * d]``)
    or, packed, the one array for each of the three."""
    return arrays * 3 if layout.packed else tuple(
        t.reshape(*t.shape[:2], -1) for t in arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rule(arrays, g, beta, layout):
    """``arrays``: ``(q, k, v)`` ``[B, L, H, d]`` or, packed, ``(qkv,)``
    ``[B, L, 2 keys + values]``. ``(o [B, L, Hv * d_v], S_last)``."""
    return _rule_forward(_operands(arrays, layout),
                         _scalars(g, beta, layout.block_h), layout, False)


def _rule_fwd(arrays, g, beta, layout):
    # nothing kept but the inputs: the layer makes its forward again in the
    # backward pass whatever is kept here, and a custom call's outputs are
    # written whether or not anything reads them
    return _rule(arrays, g, beta, layout), (arrays, g, beta)


def _rule_bwd(layout, residuals, cotangents):
    arrays, g, beta = residuals
    do, _ = cotangents  # the state at a row's end takes no gradient
    operands = _operands(arrays, layout)
    scalars = _scalars(g, beta, layout.block_h)
    starts, inverses = _rule_forward(operands, scalars, layout, True)
    do = do.astype(arrays[0].dtype)
    dq, dk, dv, dg_cols, dg_rows, db_cols, db_rows = _rule_backward(
        operands, scalars, starts, inverses, do, layout)
    # gamma is the running sum of g inside a chunk: g_t reaches every gamma
    # from t to the chunk's end
    dg = jax.lax.cumsum(_per_token(dg_cols, dg_rows), axis=2, reverse=True)
    if layout.packed:  # the one pass over the three that is left in XLA
        grads = (jnp.concatenate([dq, dk, dv], -1),)
    else:
        grads = tuple(d.reshape(t.shape) for d, t in zip((dq, dk, dv),
                                                         arrays))
    return (grads, dg.reshape(g.shape).astype(g.dtype),
            _per_token(db_cols, db_rows).reshape(g.shape).astype(beta.dtype))


_rule.defvjp(_rule_fwd, _rule_bwd)


def _block_h(heads: int, block_h: int, rep: int) -> int:
    """The most heads up to ``block_h`` that divide ``heads`` and are whole
    key heads' (``rep`` to a key head; one key head's if ``block_h`` is
    under that)."""
    return max(b for b in range(rep, max(block_h, rep) + 1, rep)
               if heads % b == 0)


def _refuse_ragged(seq, d_k, d_v):
    if seq % _PAIR or d_k % _LANES or d_v % _LANES:
        raise ValueError(
            f"the delta-rule kernel takes rows of whole chunks of {CHUNK}, "
            f"two at a time, and heads in whole groups of {_LANES} lanes; "
            f"got {seq} tokens and heads of {d_k} and {d_v}")


def delta_kernel(q, k, v, g, beta, *, block_h: int = BLOCK_H,
                 qk_norm: bool = False):
    """The rule in Pallas kernels, preparation and recurrence both (``L``
    whole pairs of chunks of ``CHUNK``, ``d_k`` and ``d_v`` whole 128-lane
    groups) and, under ``qk_norm``, the norm of ``q`` and ``k`` ahead of
    them; differentiable, by its own rule."""
    _check(q, k, v, g, beta)
    seq, key_heads, d_k = q.shape[1:]
    heads, d_v = v.shape[2:]
    _refuse_ragged(seq, d_k, d_v)
    layout = _Layout(key_heads, heads, d_k, d_v,
                     _block_h(heads, block_h, heads // key_heads),
                     bool(qk_norm), False)
    o, last = _rule((q, k, v), g, beta, layout)
    return o.reshape(v.shape), jax.lax.stop_gradient(last)


def delta_kernel_packed(qkv, g, beta, *, key_heads: int, key_dim: int,
                        block_h: int = BLOCK_H, qk_norm: bool = False):
    """:func:`delta_kernel` on ``qkv`` ``[B, L, 2 keys + values]``, the
    columns ``[q; k; v]`` of one array, read where they lie (each of the
    three starting at a whole block of a grid step's heads; else sliced
    out, as anywhere the kernels refuse)."""
    rows, seq, width = qkv.shape
    heads, keys = g.shape[2], key_heads * key_dim
    d_v = (width - 2 * keys) // heads
    _refuse_ragged(seq, key_dim, d_v)
    layout = _Layout(key_heads, heads, key_dim, d_v,
                     _block_h(heads, block_h, heads // key_heads),
                     bool(qk_norm), False)
    if layout.in_place(width):
        arrays, layout = (qkv,), layout._replace(packed=True)
    else:
        arrays = _columns(qkv, key_heads, key_dim, heads)
    o, last = _rule(arrays, g, beta, layout)
    return o.reshape(rows, seq, heads, d_v), jax.lax.stop_gradient(last)


def _columns(qkv, key_heads, key_dim, heads, normed=None):
    """``q``, ``k`` ``[B, L, Hk, d_k]`` and ``v`` ``[B, L, Hv, d_v]`` sliced
    out of ``qkv`` ``[B, L, 2 keys + values]``, each of the first two
    through ``normed`` before the next is sliced (the order a layer wrote
    them in before the rule took its projection whole)."""
    rows, seq, width = qkv.shape
    keys = key_heads * key_dim
    q_k = []
    for i in range(2):
        t = qkv[..., i * keys:(i + 1) * keys].reshape(rows, seq, key_heads,
                                                      key_dim)
        q_k.append(normed(t, i) if normed else t)
    return (*q_k, qkv[..., 2 * keys:].reshape(rows, seq, heads, -1))


def delta_fused_applies(seq: int, heads: int, d_k: int, d_v: int, mesh=None,
                        platform: Optional[str] = None) -> bool:
    """The rule by which a linear-attention layer's recurrence runs the
    kernels, preparation and all: on a TPU, a row of whole chunks in pairs
    (a grid step takes two), heads of whole lane groups in keys and values,
    over one device or a mesh that only has a ``'data'``
    axis of one (XLA cannot partition a Mosaic call, and the rule has met no
    mesh). Everything else is the plain chunked form."""
    del heads  # any number: a block takes a divisor of them
    if (platform or jax.default_backend()) != "tpu":
        return False
    if seq % _PAIR or d_k % _LANES or d_v % _LANES:
        return False
    if mesh is not None and mesh.size > 1:
        return False
    return jax.device_count() == 1


def gated_delta_rule(q, k, v, g, beta, *, qk_norm: bool = False):
    """``(o, S_last)`` by the kernels where :func:`delta_fused_applies` says
    so for these shapes: every head at once, nothing of a chunk's
    preparation outliving its grid step, and under ``qk_norm`` the norm of
    ``q`` and ``k`` inside them. Elsewhere by the plain chunked form (the
    norm ahead of it in ``jax.numpy``), whose preparation XLA keeps in
    float32 (the decay mask, ``A``, its inverse and every step of the
    inversion, each ``[Hv, L, C]``: 3.5 GiB a row for 32 heads of 128 at
    8,192 tokens if every head's is alive at once), so there a row's heads
    go ``GROUP_H`` value heads at a time, one group after the other, and a
    group's forward is made again in the backward pass."""
    _check(q, k, v, g, beta)
    rows, seq, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    if delta_fused_applies(seq, heads, d_k, d_v):
        return delta_kernel(q, k, v, g, beta, qk_norm=qk_norm)
    if qk_norm:
        q, k = _normed(q, 0), _normed(k, 1)
    form = jax.checkpoint(delta_chunked)
    rep = heads // key_heads
    if heads <= GROUP_H or heads % GROUP_H or GROUP_H % rep:
        return form(q, k, v, g, beta)

    def split(t, per):  # [B, L, H, ...] -> [B * groups, 1, L, per, ...]
        t = t.reshape(rows, seq, t.shape[2] // per, per, *t.shape[3:])
        return jnp.moveaxis(t, 2, 1).reshape(-1, 1, seq, per, *t.shape[4:])

    o, last = jax.lax.map(
        lambda parts: form(*parts),
        (split(q, GROUP_H // rep), split(k, GROUP_H // rep),
         split(v, GROUP_H), split(g, GROUP_H), split(beta, GROUP_H)))
    o = jnp.moveaxis(o.reshape(rows, heads // GROUP_H, seq, GROUP_H, d_v),
                     1, 2).reshape(rows, seq, heads, d_v)
    return o, last.reshape(rows, heads, d_k, d_v)


def gated_delta_rule_packed(qkv, g, beta, *, key_heads: int, key_dim: int,
                            qk_norm: bool = False):
    """:func:`gated_delta_rule` for a layer that holds ``q``, ``k`` and
    ``v`` as the columns ``[q; k; v]`` of one array ``[B, L, 2 keys +
    values]`` (its convolved projection). Where the kernels run they read
    the three where they lie; elsewhere the slices, the plain norm and the
    plain rule, in the order a layer wrote them before this existed."""
    rows, seq, width = qkv.shape
    heads = g.shape[2]
    d_v = (width - 2 * key_heads * key_dim) // heads
    if delta_fused_applies(seq, heads, key_dim, d_v):
        return delta_kernel_packed(qkv, g, beta, key_heads=key_heads,
                                   key_dim=key_dim, qk_norm=qk_norm)
    q, k, v = _columns(qkv, key_heads, key_dim, heads,
                       _normed if qk_norm else None)
    return gated_delta_rule(q, k, v, g, beta)
