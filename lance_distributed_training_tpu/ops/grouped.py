"""The held experts' grouped products: each group of sorted rows times its
own expert's matrix,

    y[r] = xs[r] @ w[g]      for the group g whose rows hold r; 0 past them

with ``xs`` ``[R, K]`` the built rows in the layer's type (a rank's sorted
list: group after group, then dead rows), ``w`` ``[G, K, N]`` the held
experts' matrices in the same type and ``group_sizes`` ``[G]`` int32, which
add up to ``R`` at most; f32 sums on the matrix unit, the result in the rows'
type, as each cotangent is. Two forms:

* ``jax.lax.ragged_dot``, the plain form: XLA's own kernel on the TPU
  (``ragged-dot-none`` in a trace, with a layout copy of the matrices for the
  form it differentiates into), a loop over groups elsewhere; JAX
  differentiates it. What runs off the TPU, under a mesh of more than one
  device, and at every shape :data:`TILINGS` has no entry for;
* :func:`kernel_product`: the Pallas grouped matmul of the installed JAX
  (``jax.experimental.pallas.ops.tpu.megablox``), with its own
  differentiation rule: ``gmm`` forward, ``gmm(transpose_rhs=True)`` against
  the same matrices for the rows' cotangent (no copy of them in another
  layout) and ``tgmm`` for the matrices', each at a tiling of its own. The
  library's kernels visit the groups' tiles only and leave what the buffer
  held in the rows past the last group's end, so the result and the rows'
  cotangent are zeroed there (one ``where`` that XLA folds into the pass that
  reads them).

:func:`grouped_product` chooses between them from the platform, the devices
and the call's static shapes (:func:`grouped_tiling`), as :mod:`.rows` and
:mod:`.conv` do: no flag, and never from the step's group sizes. The table is
the rule, and this is the rule of admission to it: a shape gets an entry iff
``scripts/grouped_products_sweep.py`` timed the layer's **whole program** on
the v5e (the three products, their gate, forward and ``value_and_grad`` in
one program, the f32 -> bf16 casts and the layout copies inside) under the
kernels, at the entry's tilings and with every kernel under 14 MiB of VMEM,
ahead of the same program under XLA's kernel by :data:`AHEAD` or more **on
even groups and on the cell's skew alike**, and the shape's cell then read
the gain. Single forms find the tilings and admit nothing: inside a layer
XLA's kernel also pays a layout copy of each matrix for each form it
differentiates into, which a form timed alone does not show. The file an
entry rests on is committed (``scripts/grouped_sweep/<shape>.jsonl``) and
``tests/test_grouped.py`` holds the table to it (PERF.md section 6, PRs 50
and 52).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["AHEAD", "Tiling", "TILINGS", "fits", "grouped_product",
           "grouped_tiling", "kernel_product"]

AHEAD = 0.05  # the rule of admission's margin over XLA's whole-layer time
_LANES = 128
_TYPES = (jnp.bfloat16, jnp.float32)  # what the library's kernels multiply


class Tiling(NamedTuple):
    """``(tm, tk, tn)`` of each kernel, in its own problem's terms: rows,
    contracted columns, result columns (``drhs``: the rows are contracted,
    ``tk`` x ``tn`` is a group's result tile)."""

    fwd: tuple  # xs [R, K] x w [G, K, N] -> [R, N]
    dlhs: tuple  # g [R, N] x w^T -> [R, K]: tk over N, tn over K
    drhs: tuple  # xs^T x g -> [G, K, N]


# (rows built, groups held, K, N) -> the tilings timed ahead of XLA's kernel
TILINGS: dict = {
    # Moonlight-16B-A3B's usual list as one rank of eight holds it: gate and
    # up, then down. Rows in tiles of 128 (a group of some 768 rows that
    # starts anywhere covers six or seven of them, and two or three of 512);
    # the matrices' tile is a whole matrix where VMEM has room for it
    (12288, 8, 2048, 1408): Tiling((128, 2048, 1408), (128, 1408, 2048),
                                   (128, 1024, 1408)),
    (12288, 8, 1408, 2048): Tiling((128, 1408, 2048), (128, 2048, 1408),
                                   (128, 1408, 1024)),
    # OLMoE-1B-7B's list of two rows of 4,096 tokens, all 64 experts held
    # and every row live: groups of 1,024 rows on average, rows in tiles of
    # 256, the matrices' tile a whole matrix but ``tgmm``'s, which is half
    (65536, 64, 2048, 1024): Tiling((256, 2048, 1024), (256, 1024, 2048),
                                    (256, 1024, 1024)),
    (65536, 64, 1024, 2048): Tiling((256, 1024, 2048), (256, 2048, 1024),
                                    (256, 1024, 1024)),
    # Qwen3-Next-80B-A3B's usual list as one rank of sixteen holds it: 32
    # experts of 512 columns, whole matrices. ``gmm`` over K = 512 alone
    # loses to XLA's kernel (0.69 : 0.53 ms) and the layer whole still wins
    (20480, 32, 2048, 512): Tiling((256, 2048, 512), (128, 512, 2048),
                                   (256, 2048, 512)),
    (20480, 32, 512, 2048): Tiling((128, 512, 2048), (256, 2048, 512),
                                   (256, 512, 2048)),
    # ZAYA1-8B's usual list as one rank of two holds it: square experts, so
    # gate, up and down are one call; half a matrix a tile
    (8192, 8, 2048, 2048): Tiling((128, 1024, 2048), (256, 2048, 1024),
                                  (256, 1024, 1024)),
}


def fits(rows: int, k: int, n: int, tiling: Tiling) -> bool:
    """What Mosaic takes of a tiling at a shape, and the tests hold every
    entry to: the rows in whole tiles, the columns in tiles of whole lane
    groups no wider than they are (a last tile may be ragged: the library
    masks it)."""
    return all(rows % tm == 0 and tm % 8 == 0
               and tk % _LANES == 0 and tn % _LANES == 0
               and tk <= a and tn <= b
               for (tm, tk, tn), (a, b) in zip(
                   tiling, ((k, n), (n, k), (k, n))))


def grouped_tiling(rows: int, groups: int, k: int, n: int, mesh=None,
                   platform: Optional[str] = None) -> Optional[Tiling]:
    """The rule by which a grouped product runs the kernels, and at which
    tilings: on a TPU, over one device or a mesh of one (XLA cannot partition
    a Mosaic call), at a shape the table has. None is the plain form."""
    if (platform or jax.default_backend()) != "tpu":
        return None
    if mesh is not None and mesh.size > 1:
        return None
    if jax.device_count() != 1:
        return None
    return TILINGS.get((rows, groups, k, n))


def _live(y, group_sizes):
    """``y`` with the rows past the last group's end as zeros."""
    rows = jnp.arange(y.shape[0], dtype=jnp.int32)
    return jnp.where((rows < group_sizes.sum())[:, None], y, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def kernel_product(xs, w, group_sizes, tiling: Tiling):
    """The grouped product by the library's kernels at ``tiling``, whatever
    the table says: ``R`` in whole ``tm``s, bf16 or f32 operands."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return _live(gmm(xs, w, group_sizes, xs.dtype, tuple(tiling.fwd)),
                 group_sizes)


def _product_bwd(tiling, kept, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    xs, w, group_sizes = kept
    d_xs = _live(gmm(g, w, group_sizes, xs.dtype, tuple(tiling.dlhs),
                     transpose_rhs=True), group_sizes)
    # a group's rows alone enter its sum: the dead rows pass nothing
    d_w = tgmm(xs.swapaxes(0, 1), g, group_sizes, w.dtype,
               tuple(tiling.drhs), num_actual_groups=w.shape[0])
    return d_xs, d_w, None


kernel_product.defvjp(
    lambda xs, w, group_sizes, tiling: (
        kernel_product(xs, w, group_sizes, tiling), (xs, w, group_sizes)),
    _product_bwd)


def grouped_product(xs, w, group_sizes):
    """``[R, K] x [G, K, N] -> [R, N]`` over the rows' groups. By the
    kernels where :func:`grouped_tiling` has these shapes, and the gauge
    ``grouped_products_fused`` then reads 1 (``train()`` sets it to 0 for a
    model with expert layers and puts it on every log line); elsewhere
    ``jax.lax.ragged_dot`` to the letter."""
    tiling = grouped_tiling(xs.shape[0], *w.shape)
    if tiling is None or xs.dtype != w.dtype or xs.dtype not in _TYPES:
        return jax.lax.ragged_dot(xs, w, group_sizes)
    from ..obs.registry import default_registry

    default_registry().gauge("grouped_products_fused").set(1.0)
    return kernel_product(xs, w, group_sizes.astype(jnp.int32), tiling)
