"""Ring attention — sequence/context parallelism over the mesh.

Long-context support beyond the reference's scope (it is vision-only,
SURVEY.md §5 "Long-context / sequence parallelism: absent"), built TPU-first
as a framework capability: shard the sequence axis over a ``'seq'`` mesh
axis and rotate key/value blocks around the ring with ``ppermute`` so ICI
traffic overlaps compute, while queries stay resident. Attention statistics
are accumulated flash-style (running max + running normaliser), so the
result is *exact* softmax attention — not an approximation — with per-device
memory O(S/ring · S/ring) instead of O(S²).

Implementation: ``shard_map`` over ``Mesh(..., ('data', 'seq'))``; each ring
step computes one (Q-block × KV-block) partial and folds it into the
running (max, sum, acc) triple; ``lax.fori_loop`` keeps the ring loop
compiler-friendly (one traced body, ICI ``ppermute`` per iteration).

Interface-compatible with :func:`..models.transformer.dot_product_attention`
so a ``TransformerEncoder(attention_fn=make_ring_attention(mesh))`` becomes
sequence-parallel without touching model code.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "make_ring_attention"]


def _block_attn(q, k, v, m_prev, l_prev, acc, mask_block=None):
    """Fold one KV block into the running flash statistics.

    q [B,H,Sq,D]; k,v [B,H,Sk,D]; m_prev,l_prev [B,H,Sq]; acc [B,H,Sq,D].
    """
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    if mask_block is not None:
        scores = jnp.where(mask_block, scores, jnp.finfo(jnp.float32).min)
    m_block = scores.max(axis=-1)
    m_new = jnp.maximum(m_prev, m_block)
    # Rescale previous accumulator to the new max.
    scale = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[..., None])
    if mask_block is not None:
        # Explicit zeroing: when an entire block is masked, m_new equals the
        # mask fill value and exp(scores - m_new) would be 1, not 0.
        p = p * mask_block.astype(p.dtype)
    l_new = l_prev * scale + p.sum(axis=-1)
    acc = acc * scale[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v
    ).astype(jnp.float32)
    return m_new, l_new, acc


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    axis_name: str = "seq",
) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    Call INSIDE ``shard_map``: q/k/v are the local sequence blocks
    [B, H, S_local, D]. ``mask`` (optional) is the local KEY-side validity
    block [B, 1, 1, S_local] — it travels the ring with k/v.
    """
    ring_size = lax.axis_size(axis_name)
    perm = [(i, (i + 1) % ring_size) for i in range(ring_size)]

    # Carries derived from q/k so their varying-axis types match the loop
    # body's outputs under shard_map's manual-axes type checking.
    m0 = jnp.zeros_like(q[..., 0], jnp.float32) - jnp.inf
    l0 = jnp.zeros_like(q[..., 0], jnp.float32)
    acc0 = jnp.zeros_like(q, jnp.float32)
    if mask is None:
        mask_blk = jnp.zeros_like(k[:, :1, :, 0])[:, :, None, :] == 0  # all True
    else:
        mask_blk = mask.astype(bool)

    def body(i, carry):
        k_blk, v_blk, msk, m, l, acc = carry
        m, l, acc = _block_attn(q, k_blk, v_blk, m, l, acc, msk)
        # Rotate KV (and its mask) one hop around the ring; overlapped with
        # the next block's compute by XLA's async collective scheduling.
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        msk = lax.ppermute(msk, axis_name, perm)
        return k_blk, v_blk, msk, m, l, acc

    _, _, _, m, l, acc = lax.fori_loop(
        0, ring_size, body, (k, v, mask_blk, m0, l0, acc0)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def make_ring_attention(mesh: Mesh, *, data_axis: str = "data",
                        seq_axis: str = "seq", model_axis: str = "model"):
    """Drop-in ``attention_fn`` for :class:`..models.transformer.SelfAttention`.

    Takes GLOBAL [B, H, S, D] arrays, runs the ring under ``shard_map``,
    returns the same global layout. Mask must be the key-validity mask
    ``[B, 1, 1, S]``. When the mesh also has a tensor-parallel ``model_axis``
    (a dp×tp×sp run with :data:`~.sharding.TRANSFORMER_RULES`), the head dim
    is kept sharded over it — heads are independent in attention, so each
    (model, seq) device tile rings over its own head shard and no all-gather
    of QKV is ever needed.
    """

    def _build(head_axis):
        qkv_spec = P(data_axis, head_axis, seq_axis, None)
        mask_spec = P(data_axis, None, None, seq_axis)

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec),
            out_specs=qkv_spec,
        )
        def _sharded(q, k, v, mask):
            return ring_attention(q, k, v, mask, axis_name=seq_axis)

        return _sharded

    cache: dict = {}

    def attention_fn(q, k, v, mask=None, dtype=None):
        dp = mesh.shape.get(data_axis, 1)
        sp = mesh.shape.get(seq_axis, 1)
        mp = mesh.shape.get(model_axis, 1)
        if q.shape[0] % dp or q.shape[2] % sp:
            # Shapes that don't tile the mesh (model.init's batch of 1,
            # ragged eval remainders): exact dense fallback.
            from ..models.transformer import dot_product_attention

            return dot_product_attention(q, k, v, mask=mask, dtype=q.dtype)
        head_axis = model_axis if (mp > 1 and q.shape[1] % mp == 0) else None
        if head_axis not in cache:
            cache[head_axis] = _build(head_axis)
        if mask is None:
            mask = jnp.ones((q.shape[0], 1, 1, q.shape[2]), bool)
        return cache[head_axis](q, k, v, mask)

    return attention_fn
