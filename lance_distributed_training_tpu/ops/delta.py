"""The gated delta rule (Gated Delta Networks, arXiv:2412.06464): the
recurrence of a linear-attention layer whose state is a matrix a head, the
first mixer here that :mod:`.scan`'s elementwise state cannot express.

    S <- exp(g_t) S                          S: [d_k, d_v], S_{-1} = 0
    S <- S + k_t (beta_t (v_t - S' k_t))'    a rank-one correction
    o_t = S' q_t

per row and value head, with ``q``, ``k`` ``[B, L, Hk, d_k]``, ``v`` ``[B, L,
Hv, d_v]``, ``g`` (<= 0) and ``beta`` ``[B, L, Hv]``; key head ``j`` serves
the value heads ``j * Hv / Hk`` onwards (``repeat_interleave``). The caller
norms and scales ``q`` and ``k``. Token by token that is ``L`` dependent
steps of vector work; a chunk of ``C`` tokens at a time (``gamma`` the
running sum of ``g`` inside the chunk, ``D_ij = exp(gamma_i - gamma_j)`` for
``i >= j``) it is matrix products:

    A  = strictly-lower(diag(beta) (K K' * D))      T = (I + A)^-1
    W  = T diag(beta) (exp(gamma) * K)              U = T diag(beta) V
    V* = U - W S        O = (exp(gamma) * Q) S + lower(Q K' * D) V*
    S <- exp(gamma_C) S + (exp(gamma_C - gamma) * K)' V*

``A``, ``T``, ``W``, ``U`` and the two masked score matrices do not depend
on ``S``: :func:`_prepare` makes them for all ``L / C`` chunks at once, in
XLA (``T`` by inverting the 16 x 16 diagonal blocks as the product ``(I -
A)(I + A^2)(I + A^4)(I + A^8)`` of a nilpotent block and merging them in
pairs, 16 -> 32 -> 64, ten products of ``[64, 64]`` a chunk, two more in the
backward pass: a product over the whole chunk's powers is exact too, but
their entries grow like binomials where keys are alike and cancel in
float32). What is left is sequential over the chunks, four products of ``[C,
128]`` by ``[128, 128]`` each, and has two forms:

* :func:`delta_chunked`: a ``lax.scan`` over a row's chunks, all heads a
  turn, differentiated by JAX; what runs off the TPU and what the kernel is
  held to;
* :func:`delta_kernel`: a Pallas kernel pair over grid (row x heads, chunk)
  in which a block of heads' states stay in VMEM across the chunks of a
  row; the forward keeps the state each chunk starts from (in the operands'
  type: it is only ever an operand again), the backward walks the chunks in
  reverse with the state's cotangent in VMEM. Its differentiation rule
  covers the sequential part alone; the preparation is JAX's to
  differentiate in both forms.

``g``, its sums and exponentials, the inverse and the state are float32; the
products' operands are in ``q``'s type with float32 accumulation.
:func:`gated_delta_rule` chooses between the forms from the platform and the
shapes (:func:`delta_fused_applies`), as :mod:`.scan` and :mod:`.flash` do:
no flag. Both return ``(o, S_last)``: ``S_last`` ``[B, Hv, d_k, d_v]`` is
the state at each row's end, for a gauge; it takes no gradient.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["gated_delta_rule", "delta_chunked", "delta_kernel",
           "delta_fused_applies", "CHUNK", "BLOCK_H", "GROUP_H"]

CHUNK = 64  # tokens a chunk: the published implementation's
BLOCK_H = 8  # heads a grid step holds (2, 4 and 8 ran level on the v5e:
# PERF.md section 6, PR 41)
GROUP_H = 8  # value heads whose preparation is alive at once
_BASE = 16  # the diagonal blocks inverted by powers
_LANES = 128
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


# -- what does not depend on the state ----------------------------------------


def _blocks_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` ``[..., C, C]``,
    ``C`` a power of two, by blocks: the ``_BASE``-wide diagonal blocks at
    once as one block-diagonal matrix ``d`` (nilpotent of order ``_BASE``:
    ``(I - d)(I + d^2)(I + d^4)(I + d^8)`` is its inverse, and its powers
    stay block-diagonal), then neighbours merged in pairs, ``[[P, 0], [X,
    Q]]^-1 = T - T [[0, 0], [X, 0]] T`` for ``T = diag(P^-1, Q^-1)``, until
    one block is left. Every product is a whole ``[C, C]`` one (the matrix
    unit takes a 16-wide block as it takes a 64-wide one)."""
    c = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    eye = jnp.eye(c, dtype=_F32)
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    base = min(_BASE, c)
    power = jnp.where(row // base == col // base, a, 0.0)
    inv = eye - power
    for _ in range(max(base.bit_length() - 2, 0)):
        power = mm(power, power)
        inv = mm(inv, eye + power)
    size = base
    while size < c:
        cross = jnp.where((row // (2 * size) == col // (2 * size))
                          & (row // size != col // size), a, 0.0)
        inv = inv - mm(mm(inv, cross), inv)
        size *= 2
    return inv


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


# -- the kernel ----------------------------------------------------------------
#
# A grid step is (block of row x head, chunk); a block's chunks follow each
# other and its states ride from one to the next in float32 scratch. The
# heads of a block are independent chains, so that one's products fill the
# matrix unit while another's wait for their operands.


def _fwd_kernel(w_ref, u_ref, q_ref, p_ref, k_ref, d_ref, o_ref, start_ref,
                last_ref, s_ref, *, block_h):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    dtype = w_ref.dtype
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=_F32)
    for h in range(block_h):
        s = s_ref[h]
        s_op = s.astype(dtype)
        start_ref[h, 0] = s_op  # what the backward pass starts from
        v_new = (u_ref[h, 0] - dot(w_ref[h, 0], s_op, _NN)).astype(dtype)
        o_ref[h, 0] = (dot(q_ref[h, 0], s_op, _NN)
                       + dot(p_ref[h, 0], v_new, _NN)).astype(o_ref.dtype)
        s = d_ref[h, 0] * s + dot(k_ref[h, 0], v_new, _TN)
        s_ref[h] = s
        last_ref[h] = s


def _bwd_kernel(w_ref, u_ref, q_ref, p_ref, k_ref, d_ref, start_ref, do_ref,
                dw_ref, du_ref, dq_ref, dp_ref, dk_ref, dd_ref, ds_ref, *,
                block_h):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)  # the row's last chunk comes first
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dtype = w_ref.dtype
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=_F32)
    first_lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) == 0
    for h in range(block_h):
        s_op = start_ref[h, 0]
        w, q, p, k = w_ref[h, 0], q_ref[h, 0], p_ref[h, 0], k_ref[h, 0]
        do = do_ref[h, 0]
        ds = ds_ref[h]  # dL/dS after this chunk, float32
        ds_op = ds.astype(dtype)
        v_new = (u_ref[h, 0] - dot(w, s_op, _NN)).astype(dtype)
        dv_new = dot(p, do, _TN) + dot(k, ds_op, _NN)
        dv_op = dv_new.astype(dtype)
        du_ref[h, 0] = dv_new
        dw_ref[h, 0] = (-dot(dv_op, s_op, _NT)).astype(dtype)
        dq_ref[h, 0] = dot(do, s_op, _NT).astype(dtype)
        dp_ref[h, 0] = dot(do, v_new, _NT).astype(dtype)
        dk_ref[h, 0] = dot(v_new, ds_op, _NT).astype(dtype)
        dd_ref[h, 0] = jnp.where(
            first_lane, jnp.sum(ds * s_op.astype(_F32)), 0.0)
        ds_ref[h] = (d_ref[h, 0] * ds + dot(q, do, _TN)
                     - dot(w, dv_op, _TN))


def _pallas(kernel, grid, in_specs, out_specs, out_shape, scratch, name, cost):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        cost_estimate=cost, name=name)


def _specs(block_h, shapes, order):
    """A ``BlockSpec`` for each ``[BH, N, rows, cols]`` array: a block of
    heads, one chunk (``order`` maps the grid's second index to it)."""
    from jax.experimental import pallas as pl

    return [pl.BlockSpec((block_h, 1, *shape[2:]),
                         lambda i, j: (i, order(j), 0, 0))
            for shape in shapes]


@functools.partial(jax.jit, static_argnums=(6,))
def _sequential_forward(w, u, q_g, p, k_g, decay, block_h):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, n, chunk, d_k = w.shape
    d_v = u.shape[-1]
    operands = (w, u, q_g, p, k_g, decay)
    o, starts, last = _pallas(
        functools.partial(_fwd_kernel, block_h=block_h),
        (bh // block_h, n),
        _specs(block_h, [t.shape for t in operands], lambda j: j),
        [*_specs(block_h, [(bh, n, chunk, d_v), (bh, n, d_k, d_v)],
                 lambda j: j),
         pl.BlockSpec((block_h, d_k, d_v), lambda i, j: (i, 0, 0))],
        [jax.ShapeDtypeStruct((bh, n, chunk, d_v), w.dtype),
         jax.ShapeDtypeStruct((bh, n, d_k, d_v), w.dtype),
         jax.ShapeDtypeStruct((bh, d_k, d_v), _F32)],
        [pltpu.VMEM((block_h, d_k, d_v), _F32)],
        "delta_rule_fwd",
        pl.CostEstimate(
            flops=2 * bh * n * chunk * (3 * d_k * d_v + chunk * d_v),
            transcendentals=0,
            bytes_accessed=sum(t.size * t.dtype.itemsize for t in operands)
            + bh * n * (chunk + d_k) * d_v * w.dtype.itemsize),
    )(*operands)
    return o, starts, last


@functools.partial(jax.jit, static_argnums=(8,))
def _sequential_backward(w, u, q_g, p, k_g, decay, starts, do, block_h):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, n, chunk, d_k = w.shape
    d_v = u.shape[-1]
    operands = (w, u, q_g, p, k_g, decay, starts, do)
    grads = (w, u, q_g, p, k_g, decay)

    def back(j):
        return n - 1 - j

    return _pallas(
        functools.partial(_bwd_kernel, block_h=block_h),
        (bh // block_h, n),
        _specs(block_h, [t.shape for t in operands], back),
        _specs(block_h, [t.shape for t in grads], back),
        [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in grads],
        [pltpu.VMEM((block_h, d_k, d_v), _F32)],
        "delta_rule_bwd",
        pl.CostEstimate(
            flops=2 * bh * n * chunk * (7 * d_k * d_v + 2 * chunk * d_v),
            transcendentals=0,
            bytes_accessed=sum(t.size * t.dtype.itemsize
                               for t in operands + grads)),
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _sequential(w, u, q_g, p, k_g, decay, block_h):
    o, _, last = _sequential_forward(w, u, q_g, p, k_g, decay, block_h)
    return o, last


def _sequential_fwd(w, u, q_g, p, k_g, decay, block_h):
    o, starts, last = _sequential_forward(w, u, q_g, p, k_g, decay, block_h)
    return (o, last), (w, u, q_g, p, k_g, decay, starts)


def _sequential_bwd(block_h, residuals, cotangents):
    do, _ = cotangents  # the state at a row's end takes no gradient
    return _sequential_backward(*residuals, do.astype(residuals[0].dtype),
                                block_h)


_sequential.defvjp(_sequential_fwd, _sequential_bwd)


def _block_h(heads: int, block_h: int) -> int:
    """The most heads up to ``block_h`` that divide ``heads``."""
    return max(b for b in range(1, min(block_h, heads) + 1) if heads % b == 0)


def delta_kernel(q, k, v, g, beta, *, chunk: int = CHUNK,
                 block_h: int = BLOCK_H):
    """The rule with its sequential part as the Pallas kernel pair (``L``
    whole chunks, ``d_k`` and ``d_v`` whole 128-lane groups);
    differentiable."""
    _check(q, k, v, g, beta)
    rows, seq, _, d_k = q.shape
    heads, d_v = v.shape[2:]
    if seq % chunk or d_k % _LANES or d_v % _LANES or chunk % 8:
        raise ValueError(
            f"the delta-rule kernel takes rows of whole chunks of {chunk} "
            f"and heads in whole groups of {_LANES} lanes; got {seq} tokens "
            f"and heads of {d_k} and {d_v}")
    parts = _prepare(q, k, v, g, beta, chunk)
    n = seq // chunk
    # the decay a chunk, along the lanes of a row of its own: a scalar a
    # grid step that needs no scalar memory
    *mats, decay = (t.reshape(rows * heads, n, *t.shape[3:]) for t in parts)
    decay = jnp.broadcast_to(decay[..., None, None],
                             (rows * heads, n, 1, _LANES))
    o, last = _sequential(*mats, decay, _block_h(heads, block_h))
    return _finish(o.reshape(rows, heads, n, chunk, d_v),
                   last.reshape(rows, heads, d_k, d_v), seq, q.dtype)


def delta_fused_applies(seq: int, heads: int, d_k: int, d_v: int, mesh=None,
                        platform: Optional[str] = None) -> bool:
    """The rule by which a linear-attention layer's recurrence runs the
    kernel: on a TPU, a row of whole chunks, heads of whole lane groups in
    keys and values, over one device or a mesh that only has a ``'data'``
    axis of one (XLA cannot partition a Mosaic call, and the rule has met no
    mesh). Everything else is the plain chunked form."""
    del heads  # any number: a block takes a divisor of them
    if (platform or jax.default_backend()) != "tpu":
        return False
    if seq % CHUNK or d_k % _LANES or d_v % _LANES:
        return False
    if mesh is not None and mesh.size > 1:
        return False
    return jax.device_count() == 1


def gated_delta_rule(q, k, v, g, beta):
    """``(o, S_last)`` by the kernel where :func:`delta_fused_applies` says
    so for these shapes, by the plain chunked form elsewhere. Either way a
    row's heads go ``GROUP_H`` value heads at a time, one group after the
    other, and a group's forward is made again in the backward pass: what
    the preparation leaves in float32 (the decay mask, ``A``, its inverse
    and every step of the inversion, each ``[Hv, L, C]``) is 3.5 GiB a row
    for 32 heads of 128 at 8,192 tokens if every head's is alive at once."""
    _check(q, k, v, g, beta)
    rows, seq, key_heads, d_k = q.shape
    heads, d_v = v.shape[2:]
    form = jax.checkpoint(
        delta_kernel if delta_fused_applies(seq, heads, d_k, d_v)
        else delta_chunked)
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
