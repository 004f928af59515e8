"""Fused attention for the transformer tasks: the score matrix stays in VMEM.

Dense attention (:func:`..models.transformer.dot_product_attention`) writes
an f32 ``[B, H, S, S]`` score tensor to HBM, reads it back for the softmax
and keeps the weights for the backward pass. Two Pallas kernels avoid that:

* :func:`short_attention`, this file's own, for sequences of up to
  ``SHORT_SEQ`` tokens (BERT's 512): a head's whole row block of scores fits
  VMEM, so the softmax is the plain one and the backward pass is one kernel
  that keeps nothing but q, k and v;
* the library's blocked kernel with online softmax
  (``jax.experimental.pallas.ops.tpu.flash_attention``) for longer ones
  (OLMoE's 4,096);
* :func:`unequal_attention` for heads whose queries and keys are not as wide
  as their values (latent attention: 192 and 128; differential attention: 64
  and 128), which that kernel refuses ("V model dimension unequal to KV
  model dimension unsupported"): the library's splash kernel, which takes
  them as they are, at any length, and whose mask may be a causal band
  (``window``: blocks outside the band are skipped as the causal ones are).
  Keys and values may come in fewer heads than the queries (grouped heads),
  and grouped heads take this kernel whatever their widths: for 8 query
  heads over 2 key and value heads of 128 (compressed convolutional
  attention) it ran 1.28 times as fast as the blocked kernel on repeated
  keys and values (PERF.md section 6, PR 38). It takes them in the heads
  they have and finds a block's key head from its query head itself; a
  call that still copies them for it (keys in another number of heads than
  values) is counted in ``attention_kv_repeat_total``.
  Its forward, dkv and dq kernels each run the block sizes
  :func:`splash_tiling` has for the call's shapes: what the chip timed
  fastest for them, else the untuned square blocks of 512, counted in
  ``attention_tiling_fallback_total``; there is no flag for it.

``make_flash_attention()`` returns a drop-in ``attention_fn`` for
:class:`..models.transformer.SelfAttention`, in two strengths:

* ``forced`` (``--flash_attention``): on a TPU the kernel, or an error if it
  cannot be had; off a TPU (CPU tests, simulated meshes) exact dense
  attention, chosen by the platform and never by a failed import;
* not forced (what ``models.get_task`` binds when nobody chose an attention
  path): the kernel where :func:`fused_attention_applies` allows it for the
  shapes a call sees, dense attention elsewhere.

The key-validity mask is lowered to segment ids (valid tokens form segment
1, padding segment 0, so valid queries never attend padding; padding queries
attend only padding, and their outputs are dead: the loss masks them);
packed rows pass their segment ids straight through.

Composition note: a kernel sees one device's tile; over a data- (and
tensor-) parallel mesh it runs under ``shard_map``, since XLA cannot
partition a Mosaic call. For sequence parallelism use
:mod:`..parallel.ring_attention` instead, which ``--seq_parallelism`` binds.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["SplashTiling", "make_flash_attention", "segment_attention_mask",
           "short_attention", "splash_tiling", "splash_tilings_built",
           "unequal_attention"]


def segment_attention_mask(segment_ids: jax.Array) -> jax.Array:
    """Packed-sequence attention mask: ``[B, S]`` segment ids (1-based;
    0 = dead padding) → boolean ``[B, 1, S, S]`` where query q may attend
    key k iff they belong to the same live segment. The dense-attention
    form of what the Pallas kernel expresses natively via
    ``SegmentIds(q, kv)`` — the ragged token plane's device-side pack
    (:mod:`.token_device`) emits the ids, this builds the mask for the
    XLA einsum path (and composes with the causal triangle inside
    ``dot_product_attention``, which ANDs its own mask on top)."""
    seg = segment_ids.astype(jnp.int32)
    same = seg[:, None, :, None] == seg[:, None, None, :]
    live = (seg > 0)[:, None, None, :]
    return same & live


# -- the short-sequence kernel ------------------------------------------------
#
# Up to SHORT_SEQ keys a head's whole score row block [block_q, S] lives in
# VMEM (1 MB in f32 at 512 x 512), so the softmax is the plain one: no online
# rescaling, no statistics kept for the backward pass. The backward kernel
# recomputes the weights from q and k once and makes dq, dk and dv from that
# one recomputation (five products a head where the library's dq and dkv
# kernels make seven and exponentiate twice); its residuals are q, k and v.
# The kernels read and write ``[B, S, H * D]``, the layout the projections
# produce and consume, so no transpose or 64-lane-padded copy stands between
# them: a grid step takes one row's slab of several heads, a head is a lane
# slice of it, and the mask, which is the same for all of them, is built once
# a step. Arithmetic as the dense path's: bf16 operands, f32 accumulation,
# f32 softmax, weights rounded to bf16 for their products.

SHORT_SEQ = 1024
_LANES, _SUBLANES = 128, 8
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)  # not -inf: exp is finite
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _score_bias(qid_ref, kid_ref, start, rows, seq, causal):
    """Additive f32 ``[rows, seq]`` mask of the queries ``start..start+rows``
    (0 where a query may attend a key: same segment id, and not ahead of it
    when causal), or None where every pair may."""
    mask = None
    if qid_ref is not None:
        q_ids = jnp.tile(qid_ref[0, start:start + rows, :],
                         (1, seq // _LANES))  # [rows, seq]
        mask = q_ids == kid_ref[0, :1, :]  # against [1, seq]
    if causal:
        row = start + jax.lax.broadcasted_iota(jnp.int32, (rows, seq), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, seq), 1)
        mask = col <= row if mask is None else mask & (col <= row)
    return None if mask is None else jnp.where(mask, 0.0, _MASKED)


def _weights(q, k, bias, scale):
    """Softmax weights ``[rows, seq]`` in f32 of one head's query block."""
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
    return p * (1.0 / jnp.sum(p, axis=1, keepdims=True))


def _split_refs(refs, n_in, has_ids):
    ins, rest = refs[:n_in], refs[n_in:]
    ids = rest[:2] if has_ids else (None, None)
    return ins, ids, rest[2 if has_ids else 0:]


def _short_fwd_kernel(*refs, scale, causal, block_q, dim, has_ids):
    (q_ref, k_ref, v_ref), (qid_ref, kid_ref), (o_ref,) = _split_refs(
        refs, 3, has_ids)
    seq, width = q_ref.shape[1], q_ref.shape[2]
    for start in range(0, seq, block_q):
        rows = slice(start, start + block_q)
        bias = _score_bias(qid_ref, kid_ref, start, block_q, seq, causal)
        for lanes in (slice(at, at + dim) for at in range(0, width, dim)):
            v = v_ref[0, :, lanes]
            p = _weights(q_ref[0, rows, lanes], k_ref[0, :, lanes], bias,
                         scale)
            o_ref[0, rows, lanes] = jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            ).astype(o_ref.dtype)


def _short_bwd_kernel(*refs, scale, causal, block_q, dim, has_ids):
    ((q_ref, k_ref, v_ref, do_ref), (qid_ref, kid_ref),
     (dq_ref, dk_ref, dv_ref, *acc)) = _split_refs(refs, 4, has_ids)
    seq, width = q_ref.shape[1], q_ref.shape[2]
    for start in range(0, seq, block_q):
        rows = slice(start, start + block_q)
        bias = _score_bias(qid_ref, kid_ref, start, block_q, seq, causal)
        for lanes in (slice(at, at + dim) for at in range(0, width, dim)):
            q, do = q_ref[0, rows, lanes], do_ref[0, rows, lanes]
            k, v = k_ref[0, :, lanes], v_ref[0, :, lanes]
            p = _weights(q, k, bias, scale)
            dp = jax.lax.dot_general(do, v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - jnp.sum(p * dp, axis=1, keepdims=True))
                  ).astype(q.dtype)  # the scale goes on the products of it
            dq_ref[0, rows, lanes] = (scale * jnp.dot(
                ds, k, preferred_element_type=jnp.float32)
            ).astype(dq_ref.dtype)
            dv = jax.lax.dot_general(p.astype(do.dtype), do, _TN,
                                     preferred_element_type=jnp.float32)
            dk = scale * jax.lax.dot_general(
                ds, q, _TN, preferred_element_type=jnp.float32)
            if acc:  # several query blocks: summed in f32 scratch
                dk_acc, dv_acc = acc
                if start:
                    dk, dv = dk + dk_acc[:, lanes], dv + dv_acc[:, lanes]
                if start + block_q < seq:
                    dk_acc[:, lanes], dv_acc[:, lanes] = dk, dv
                    continue
            dk_ref[0, :, lanes] = dk.astype(dk_ref.dtype)
            dv_ref[0, :, lanes] = dv.astype(dv_ref.dtype)


def _heads_per_step(heads: int, seq: int, dim: int) -> int:
    """Heads a grid step takes: a divisor of ``heads`` whose lanes are whole
    128-lane tiles (or all of them), the most with up to 2,048 query rows a
    step, else the fewest."""
    fit = [g for g in range(1, heads + 1)
           if heads % g == 0 and (g * dim % _LANES == 0 or g == heads)]
    return max((g for g in fit if g * seq <= 2048), default=fit[0])


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5, 6, 7, 8))
def _short_call(kernel_fn, arrays, ids, n_out, heads, causal, block_q,
                products, heads_per_step=None):
    """One of the two kernels over ``[B, S, H * D]`` arrays, the layout the
    projections write and read: a grid step takes one row's ``[S, G * D]``
    slab of G heads, and a head is a lane slice of it. Jitted, so that a
    model's layers share one trace and one Mosaic lowering of each kernel
    (a warm start still traces and lowers its programs: 24 calls a BERT
    step, 0.1 s of host time each when every layer lowers its own)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, width = arrays[0].shape
    d = width // heads
    g = heads_per_step or _heads_per_step(heads, s, d)
    block_q = min(block_q, s)
    if s % _LANES or s % block_q or heads % g:
        raise ValueError(
            f"short-sequence attention takes sequences that are multiples "
            f"of {_LANES} (and of block_q {block_q}); got {s}")
    tile = pl.BlockSpec((1, s, g * d), lambda i, j: (i, 0, j))
    in_specs, operands = [tile] * len(arrays), list(arrays)
    if ids is not None:
        # as the library lays them out: the queries' ids down the sublanes
        # of a lane-wide tile, the keys' ids along the lanes
        operands += [jnp.broadcast_to(ids[:, :, None], (b, s, _LANES)),
                     jnp.broadcast_to(ids[:, None, :], (b, _SUBLANES, s))]
        in_specs += [pl.BlockSpec((1, s, _LANES), lambda i, j: (i, 0, 0)),
                     pl.BlockSpec((1, _SUBLANES, s), lambda i, j: (i, 0, 0))]
    scratch = []
    if n_out == 3 and block_q < s:
        scratch = [pltpu.VMEM((s, g * d), jnp.float32)] * 2
    shape = jax.ShapeDtypeStruct(arrays[0].shape, arrays[0].dtype)
    out = pl.pallas_call(
        functools.partial(kernel_fn, scale=1.0 / float(d) ** 0.5,
                          causal=causal, block_q=block_q, dim=d,
                          has_ids=ids is not None),
        grid=(b, heads // g), in_specs=in_specs,
        out_specs=[tile] * n_out, out_shape=[shape] * n_out,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 2 ** 20),
        cost_estimate=pl.CostEstimate(
            flops=2 * products * b * s * s * width,
            transcendentals=b * heads * s * s,
            bytes_accessed=(len(arrays) + n_out) * b * s * width
            * arrays[0].dtype.itemsize),
        name=kernel_fn.__name__.strip("_").removesuffix("_kernel"),
    )(*operands)
    return out[0] if n_out == 1 else tuple(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _short_attention(q, k, v, ids, heads, causal, block_q, heads_per_step):
    return _short_call(_short_fwd_kernel, (q, k, v), ids, 1, heads, causal,
                       block_q, 2, heads_per_step)


def _short_attention_fwd(q, k, v, ids, heads, causal, block_q,
                         heads_per_step):
    out = _short_attention(q, k, v, ids, heads, causal, block_q,
                           heads_per_step)
    return out, (q, k, v, ids)


def _short_attention_bwd(heads, causal, block_q, heads_per_step, residuals,
                         do):
    q, k, v, ids = residuals
    return *_short_call(_short_bwd_kernel, (q, k, v, do.astype(q.dtype)),
                        ids, 3, heads, causal, block_q, 5,
                        heads_per_step), None


_short_attention.defvjp(_short_attention_fwd, _short_attention_bwd)


def short_attention(q, k, v, segment_ids=None, *, causal: bool = False,
                    block_q: int = 512, heads_per_step: Optional[int] = None):
    """Fused attention for sequences of up to ``SHORT_SEQ`` tokens: q, k, v
    ``[B, H, S, D]`` (S a multiple of 128), ``segment_ids`` ``[B, S]`` int32
    or None (a token attends the tokens of its own id), output like q. The
    score matrix never leaves VMEM, forward or backward.

    The kernels work on ``[B, S, H * D]``, so the transposes here undo the
    ones ``SelfAttention`` makes around its attention function and XLA drops
    both: the projections feed the kernel, and read its result, as they are."""
    b, h, s, d = q.shape
    ids = None if segment_ids is None else segment_ids.astype(jnp.int32)
    out = _short_attention(
        *(t.transpose(0, 2, 1, 3).reshape(b, s, h * d) for t in (q, k, v)),
        ids, h, causal, block_q, heads_per_step)
    return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)


# -- heads of unequal width ----------------------------------------------------
#
# Latent attention's heads carry 128 + 64 rotary elements in queries and keys
# and 128 in values. Padding v to 192 would satisfy the blocked kernel above
# and waste a third of the context product; the library's splash kernel takes
# ``d_qk != d_v`` (blocked online softmax, the causal upper blocks skipped,
# logsumexp kept as ``[H, S]``). It is three kernels with a tiling each, and
# what the v5e ran fastest (PERF.md section 6, PR 34) is not one block for
# all: the forward wants query blocks of 1,024 to 2,048 with the scores made
# 256 keys at a time, dkv and dq square blocks of 1,024: 13.50 ms a Moonlight
# layer for the whole call where blocks of 512 take 15.34. Under a window
# smaller and larger blocks are both slower, and square blocks of 512 stay.
#
# The library's fused backward (one kernel computes the scores once for dk,
# dv and dq) is faster again, 12.04 ms at key blocks of 1,024, and no entry
# takes it: it writes dq as one partial a key block in the queries' dtype
# (eight bf16 partials a row of 8,192 tokens, 403 MB for Moonlight's 16
# heads of 192, for each row of the batch) and XLA sums them, where the dq
# kernel accumulates over all key blocks in f32 scratch and rounds once.
# That is a lower intermediate precision, not another order of f32 sums
# (PERF.md section 6 has dq's error against an f32 reference for both).


class SplashTiling(NamedTuple):
    """The library's ``BlockSizes``, a kernel at a time: ``fwd`` is
    ``(block_q, block_kv, block_kv_compute)``, ``dkv`` the same three of the
    dkv kernel, ``dq`` ``(block_q_dq, block_kv_dq)``. ``dq`` None is the
    fused backward, for a test or a timing: dkv's kernel then writes dq's
    partials too, one a key block and rounded to the queries' dtype."""
    fwd: tuple
    dkv: tuple
    dq: Optional[tuple]

    @property
    def blocks(self) -> tuple:
        """Every block size, in the order of ``BlockSizes``'s fields."""
        return (*self.fwd, *self.dkv, *(self.dq or ()))


class _Shape(NamedTuple):
    """What a call can observe, and so what a tiling is chosen from (not the
    rows of the batch: the kernels take one row at a time)."""
    seq: int
    d_qk: int
    d_v: int
    heads: int
    causal: bool
    window: int


def _square(block: int) -> SplashTiling:
    return SplashTiling((block,) * 3, (block,) * 3, (block,) * 2)


# What the v5e ran fastest, kernel by kernel, at the shapes the cells call
# (scripts/splash_tiling_sweep.py; its tables are in PERF.md section 6), each
# inside its cell's step too: a tiling that compiles alone can be refused
# there (scoped VMEM), so an entry is one a step has run. The grouped shapes'
# times below were read with keys and values repeated a head a query head
# outside the kernels, on both sides of every comparison; with the kernels
# taking them in their own heads Laguna's band was timed again (12.05 ms a
# call for its entry, 12.08 for square 512s) and the others were not.
_TIMED = {
    # Moonlight's latent attention
    _Shape(8192, 192, 128, 16, True, 0): SplashTiling(
        (1024, 1024, 256), (1024, 1024, 512), (1024, 1024)),
    # Phi-4-mini-flash's differential attention: F* and X, then S's band
    _Shape(8192, 64, 128, 40, True, 0): SplashTiling(
        (2048, 2048, 256), (1024, 1024, 1024), (1024, 1024)),
    _Shape(8192, 64, 128, 40, True, 512): _square(512),
    # ZAYA1's compressed convolutional attention: 8 query heads over 2 key
    # and value heads, all 128 wide (4.14 ms a layer where square 512s take
    # 4.58 and the library's blocked kernel on repeated keys 5.29 to 5.79)
    _Shape(8192, 128, 128, 8, True, 0): SplashTiling(
        (1024, 1024, 512), (1024, 1024, 512), (1024, 1024)),
    # Qwen3-Next's gated attention: 16 query heads over 2 key and value
    # heads, all 256 wide (17.09 ms a layer where square 512s take 18.71 and
    # the library's blocked kernel on repeated keys 20.25; twice the VMEM a
    # block of 128-wide heads: Mosaic refuses every block of 2,048)
    _Shape(8192, 256, 256, 16, True, 0): SplashTiling(
        (1024, 1024, 256), (1024, 1024, 1024), (1024, 1024)),
    # SmallThinker's grouped attention, 28 query heads over 4 key and value
    # heads of 128 at its 16,384-token row. The full layer: 59.24 ms a call
    # where square 512s take 82.28 (every block of 2,048 queries but dkv's
    # is refused, and every forward key block of 4,096 beside 2,048 queries)
    _Shape(16384, 128, 128, 28, True, 0): SplashTiling(
        (1024, 2048, 512), (2048, 2048, 512), (1024, 2048)),
    # its band of 4,096 keys, eight 512-blocks wide: 32.13 ms a call where
    # square 512s take 36.15; wider than Phi-4's one block, it wants square
    # blocks of 1,024 as the causal shapes do, and key blocks of 2,048 lose
    # again (more of a block lies outside the band)
    _Shape(16384, 128, 128, 28, True, 4096): SplashTiling(
        (1024, 1024, 512), (1024, 1024, 256), (1024, 1024)),
    # Laguna's gated grouped attention over 8 key and value heads of 128 at
    # 8,192 tokens. The window layers' 72 query heads in a band of 512: one
    # block wide, as Phi-4's, and square 512s stay but for dkv's scores 256
    # keys at a time: 13.91 ms a call where square 512s take 13.96 (every
    # block of 128 or 256 is slower)
    _Shape(8192, 128, 128, 72, True, 512): SplashTiling(
        (512, 512, 512), (512, 512, 256), (512, 512)),
    # the full layers' 48 query heads over the whole causal row: 27.77 ms a
    # call where square 512s take 34.04 (every forward block of 2,048 queries
    # beside 1,024 keys or more is refused, and every key block of 4,096)
    _Shape(8192, 128, 128, 48, True, 0): SplashTiling(
        (1024, 2048, 512), (1024, 1024, 1024), (1024, 1024)),
}


def splash_tiling(seq: int, d_qk: int, d_v: int, heads: int, causal: bool,
                  window: int = 0):
    """``(tiling, timed)`` for one call's shapes: the entry the chip timed
    for exactly these shapes, else the untuned square blocks of 512 (of 256
    or 128 where 512 does not divide ``seq``), which every shape so far has
    compiled and run."""
    shape = _Shape(seq, d_qk, d_v, heads, causal, window)
    if shape in _TIMED:
        return _TIMED[shape], True
    return _square(next((b for b in (512, 256) if seq % b == 0),
                        _LANES)), False


_built: list = []


def splash_tilings_built() -> list:
    """A log line for each kernel built since the last call: its shapes, the
    tiling it got, and whether the chip timed that tiling at these shapes
    (``train()`` logs them at its next log point)."""
    lines = _built[:]
    del _built[:len(lines)]
    return lines


@functools.lru_cache(maxsize=None)  # a few KB of block tables a mask: every
# mask a process builds stays, so no program's kernel is built twice
def _splash_kernel(shape: _Shape, tiling: Optional[SplashTiling] = None,
                   kv_heads: Optional[int] = None, repeat: int = 1):
    """The library's kernel object for one call's shapes, at the tiling
    :func:`splash_tiling` has for them (a test may hand it one). The object
    is a mask a query head and knows nothing of ``kv_heads``, the heads its
    keys and values will come in (None: one a query head), nor of ``repeat``,
    the most either was repeated outside it to get there: they are here for
    the call's log line and its counter."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    from ..obs.registry import default_registry

    source = "given"
    if tiling is None:
        tiling, timed = splash_tiling(*shape)
        source = "timed" if timed else "rule"
    # counts kernels that run a tiling nobody timed at their shapes: a new
    # model that reads non-zero here is running untuned
    default_registry().counter("attention_tiling_fallback_total").inc(
        source == "rule")
    # counts calls that still copy keys and values for their groups in XLA
    # (differential attention's: half as many value heads as key heads)
    default_registry().counter("attention_kv_repeat_total").inc(repeat > 1)
    _built.append({
        "attention_tiling": " ".join(f"{k}={v}" for k, v in zip(
            shape._fields, shape)),
        "kv_heads": kv_heads or shape.heads, "repeat": repeat,
        "fwd": "/".join(map(str, tiling.fwd)),
        "dkv": "/".join(map(str, tiling.dkv)),
        "dq": "/".join(map(str, tiling.dq)) if tiling.dq else "fused in dkv",
        "source": source})
    seq = shape.seq
    if shape.window:  # a query sees itself and the window - 1 keys before it
        one = sm.LocalMask((seq, seq), (shape.window - 1, 0), 0)
    else:
        one = (sm.CausalMask if shape.causal else sm.FullMask)((seq, seq))
    sizes = sk.BlockSizes(  # without dq's blocks: the fused backward
        **dict(zip(("block_q", "block_kv", "block_kv_compute", "block_q_dkv",
                    "block_kv_dkv", "block_kv_dkv_compute", "block_q_dq",
                    "block_kv_dq"), tiling.blocks)),
        use_fused_bwd_kernel=tiling.dq is None)
    # the kernel object holds its block tables as arrays: made while a
    # program is traced, they must be values and not that trace's tracers,
    # or the next program to find the object here meets a leaked tracer
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(sm.MultiHeadMask([one] * shape.heads),
                                  block_sizes=sizes, head_shards=1,
                                  q_seq_shards=1)


def _expand_heads(t, heads: int):
    """Grouped heads: ``t`` ``[B, G, S, D]`` with each of its ``G`` heads
    repeated for the ``heads / G`` query heads that share it, in order: a
    copy in HBM, and a sum over the copies on the way back. Dense attention
    takes its keys and values so, and the splash kernels where keys and
    values come in different numbers of heads."""
    return t if t.shape[1] == heads else jnp.repeat(
        t, heads // t.shape[1], axis=1)


@functools.partial(jax.jit, static_argnames=("causal", "window", "tiling"))
def unequal_attention(q, k, v, segment_ids=None, *, causal: bool = False,
                      window: int = 0,
                      tiling: Optional[SplashTiling] = None):
    """Fused attention for heads whose values are not as wide as their
    queries and keys, or that come in groups: q ``[B, H, S, Dqk]``, k ``[B,
    Hk, S, Dqk]``, v ``[B, Hv, S, Dv]`` (S a multiple of 128; ``Hk`` and
    ``Hv`` divide ``H``: a key or value head serves the query heads of its
    group), ``segment_ids`` ``[B, S]`` int32 or None, output ``[B, H, S,
    Dv]``; scores over ``sqrt(Dqk)``.
    Where ``Hk == Hv`` the kernels take keys and values in those heads: a
    block's index maps find its key head from its query head (``h // (H /
    G)``, ``jnp.repeat``'s order), and the dkv kernel sums a group's dK and
    dV in its f32 scratch and writes them once, after the group's last
    query head. Their one demand is as many key heads as value heads, so
    differential attention's 20 and 10 under 40 query heads are both
    repeated to a head a query head, as all were before PR 54, and the call
    is counted in ``attention_kv_repeat_total`` (values repeated twice for
    the 20 key heads made the kernels' scopes 1.5 ms a step shorter and the
    step 0.1 ms longer: PERF.md section 6, PR 54).
    ``window`` > 0: causal, and a query sees the ``window`` keys up to its
    own. The kernels' block sizes come from the shapes
    (:func:`splash_tiling`); ``tiling`` is for a test or a timing that wants
    another. Jitted, so a model's layers share one trace and one lowering of
    its three kernels."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    _, heads, seq, d = q.shape
    kv_heads = k.shape[1] if k.shape[1] == v.shape[1] else heads
    kernel = _splash_kernel(
        _Shape(seq, d, v.shape[3], heads, causal, window), tiling, kv_heads,
        kv_heads // min(k.shape[1], v.shape[1]))
    k, v = _expand_heads(k, kv_heads), _expand_heads(v, kv_heads)
    # the kernel has no scale of its own
    q = (q.astype(jnp.float32) * (1.0 / float(d) ** 0.5)).astype(q.dtype)
    ids = None if segment_ids is None else segment_ids.astype(jnp.int32)
    # the kernel takes one row; rows are few at these lengths, and a vmap
    # over them is more than Pallas's interpreter follows
    return jnp.stack([
        kernel(q[i], k[i], v[i], None if ids is None
               else sk.SegmentIds(q=ids[i], kv=ids[i]))
        for i in range(q.shape[0])])


MIN_FUSED_SEQ = 256  # timed on the v5e at 16,384 tokens of 12 heads x 64
# (PERF.md section 6, PR 29): at 128 dense attention is level with the kernel
# (1.17 against 1.18-1.37 ms a layer), at 256 the kernel is 1.95 times ahead


def fused_attention_applies(seq: int, head_dim: int, mesh=None,
                            platform: Optional[str] = None,
                            value_dim: Optional[int] = None) -> bool:
    """The rule by which a sequence model that was given no attention
    function gets the fused kernel: on a TPU, for a sequence of whole
    128-key blocks from ``MIN_FUSED_SEQ`` up and heads of whole 64-lane
    halves, over one device or a mesh that only has a ``'data'`` axis (a
    ``'model'`` axis has met no chip). The shapes it serves today: BERT's
    64-wide heads up to 1,024 tokens (``short_attention``), OLMoE's 128 at
    4,096 (the library's blocked kernel), and, with ``value_dim`` another
    width than ``head_dim``, ``unequal_attention``: latent attention's 192
    with values of 128, and differential attention's 64 with values of 128
    in 40 query heads over 20 key heads, causal, in a window of 512 or over
    F*'s keys; and ``unequal_attention`` again for heads in groups whose
    values are as wide as their keys: compressed convolutional attention's 8
    query heads over 2 key and value heads of 128, and Qwen3-Next's gated
    attention's 16 query heads over 2 key and value heads of 256, both
    causal, at 8,192 tokens, and SmallThinker's 28 query heads over 4 key and
    value heads of 128 at 16,384 tokens, causal and in a window of 4,096, and
    Laguna's 72 and 48 query heads over 8 key and value heads of 128 at 8,192
    tokens, in a window of 512 and causal.
    Everything else is dense attention, as before: the CPU, a
    ViT's 197 tokens, a tensor-parallel mesh, and several devices with no
    mesh to say how the batch is split."""
    if (platform or jax.default_backend()) != "tpu":
        return False
    if seq < MIN_FUSED_SEQ or seq % _LANES or head_dim % 64 or (
            value_dim or head_dim) % 64:
        return False
    if mesh is None:
        return jax.device_count() == 1
    return tuple(mesh.axis_names) == ("data",)


def make_flash_attention(block_q: int = 512, block_k: int = 512,
                         causal: bool = False, mesh=None,
                         forced: bool = True):
    """Build an ``attention_fn(q, k, v, mask=None, dtype=None)``.

    q/k/v are [B, H, S, D]; mask (optional) is the key-validity mask
    [B, 1, 1, S] produced by :class:`..models.transformer.TransformerEncoder`.
    ``causal=True`` selects the kernels' fused autoregressive masking (the
    decoder/GPT path) — the library kernel then also skips the fully-masked
    upper blocks, the usual ~2x flash speedup for causal attention.

    ``forced`` is ``--flash_attention``: on a TPU the kernel or an error,
    whatever the shapes. Without it (what ``get_task`` binds when no
    attention function was chosen) each call takes the kernel where
    :func:`fused_attention_applies` says so for the shapes it sees, and
    dense attention elsewhere. Either way sequences of up to ``SHORT_SEQ``
    whole blocks run :func:`short_attention`, longer ones the library's
    blocked kernel, and heads whose values are not as wide as their keys, or
    whose keys and values are fewer than the queries,
    :func:`unequal_attention`.

    ``mesh`` is the trainer's device mesh. XLA cannot partition a Mosaic
    kernel ("wrap the call in a shard_map"), so on more than one device the
    kernel runs under ``shard_map``: batch rows split over ``'data'``, heads
    over ``'model'`` where the mesh has that axis, each device running the
    kernel on its own tile — attention mixes nothing across rows or heads,
    so no collective is needed. A batch the data axis does not divide
    (``model.init``'s batch of 1; a train or eval batch always divides) runs
    the same kernel whole on every device.
    """
    platform = jax.default_backend()  # read once: the calls obey this one
    use_pallas = platform == "tpu"
    if use_pallas and forced:
        # No try/except: on a TPU a missing kernel module is an error, not
        # a reason to run dense attention under the flag's name.
        from jax.experimental.pallas.ops.tpu import flash_attention  # noqa: F401

    def kernel(q, k, v, ids, window=0):
        seq = q.shape[2]
        if q.shape[3] != v.shape[3] or k.shape[1] != q.shape[1]:
            return unequal_attention(q, k, v, ids, causal=causal,
                                     window=window)
        if window:
            raise NotImplementedError(
                "a window over heads as many and as wide in values as in "
                "keys has no kernel here (differential attention's are 64 "
                "and 128 in groups: unequal_attention)")
        if seq <= SHORT_SEQ and seq % _LANES == 0:
            return short_attention(q, k, v, ids, causal=causal)
        from jax.experimental.pallas.ops.tpu import flash_attention as fa

        sizes = fa.BlockSizes(
            block_q=min(block_q, seq),
            block_k_major=min(block_k, seq),
            block_k=min(block_k, seq),
            block_b=1,
            block_q_major_dkv=min(block_q, seq),
            block_k_major_dkv=min(block_k, seq),
            block_k_dkv=min(block_k, seq),
            block_q_dkv=min(block_q, seq),
            block_k_major_dq=min(block_k, seq),
            block_k_dq=min(block_k, seq),
            block_q_dq=min(block_q, seq),
        )
        out = fa.flash_attention(
            q, k, v,
            segment_ids=None if ids is None else fa.SegmentIds(q=ids, kv=ids),
            sm_scale=1.0 / float(q.shape[-1]) ** 0.5,
            block_sizes=sizes, causal=causal,
        )
        return out.astype(q.dtype)

    def per_device(batch_axis, window=0):
        qkv = P(batch_axis, "model" if "model" in mesh.axis_names else None,
                None, None)
        # check_vma off: the library kernel's out_shape declares no vma,
        # which pallas_call rejects under the check.
        return shard_map(
            functools.partial(kernel, window=window), mesh=mesh,
            in_specs=(qkv, qkv, qkv, P(batch_axis, None)),
            out_specs=qkv, check_vma=False,
        )

    over_mesh = use_pallas and mesh is not None and mesh.size > 1

    def fused(seq: int, head_dim: int,
              value_dim: Optional[int] = None) -> bool:
        """Does a call with these shapes run the kernel?"""
        return use_pallas and (forced or fused_attention_applies(
            seq, head_dim, mesh, platform, value_dim))

    def attention_fn(q, k, v, mask=None, dtype=None, segment_ids=None,
                     window=0):
        """``window`` > 0 (a causal function only): a query sees the
        ``window`` keys up to its own. k and v may have fewer heads than q."""
        if not fused(q.shape[2], q.shape[3], v.shape[3]):
            from ..models.transformer import dot_product_attention

            if segment_ids is not None:
                # Packed sequences: the block mask supersedes the plain
                # key-validity mask (it encodes validity AND segment
                # boundaries); causal still composes inside.
                mask = segment_attention_mask(segment_ids)
            if window:
                at = jnp.arange(q.shape[2])
                band = (at[:, None] - at[None, :] < window)[None, None]
                mask = band if mask is None else mask & band
            return dot_product_attention(
                q, _expand_heads(k, q.shape[1]), _expand_heads(v, q.shape[1]),
                mask=mask, dtype=q.dtype, causal=causal)
        ids = None
        if segment_ids is not None:
            # The kernel's native packed-sequence form: tokens attend only
            # within equal ids, so the ragged plane's 1-based segments
            # (0 = padding) map straight through — padding forms its own
            # segment whose outputs are dead (the loss masks them).
            ids = segment_ids.astype(jnp.int32)
        elif mask is not None:
            ids = mask.reshape(mask.shape[0], mask.shape[-1]).astype(jnp.int32)
        if not over_mesh:
            return kernel(q, k, v, ids, window)
        tiles = q.shape[0] % mesh.shape["data"] == 0
        return per_device("data" if tiles else None, window)(q, k, v, ids)

    attention_fn.fused = fused  # train() logs it; the call above obeys it
    return attention_fn
