"""Pallas flash attention — fused TPU attention for the transformer tasks.

The reference has no attention at all (vision-only); this framework's text
arm defaults to XLA einsum attention (:func:`..models.transformer.
dot_product_attention`), which materialises the [B, H, S, S] score matrix in
HBM. For long sequences the fused Pallas kernel
(``jax.experimental.pallas.ops.tpu.flash_attention``, forward + backward)
keeps scores in VMEM tiles instead — O(S) HBM traffic, the standard
flash-attention memory profile — and runs on the MXU via Mosaic.

``make_flash_attention()`` returns a drop-in ``attention_fn`` for
:class:`..models.transformer.SelfAttention`:

* on TPU: the Pallas kernel, or an error if it cannot be had; the
  key-validity mask is lowered to segment ids (valid tokens form segment 1,
  padding segment 0, so valid queries never attend padding; padding queries
  attend only padding, and their outputs are dead — the MLM loss masks them),
* off TPU (CPU tests, simulated meshes): exact dense attention, chosen by
  the platform and never by a failed import.

Composition note: the kernel sees one device's tile; over a data- (and
tensor-) parallel mesh it runs under ``shard_map``, since XLA cannot
partition a Mosaic call. For sequence parallelism use
:mod:`..parallel.ring_attention` instead — the two are alternative
``attention_fn`` values, selected by the trainer (``--flash_attention`` vs
``--seq_parallelism``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["make_flash_attention", "segment_attention_mask"]


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


def make_flash_attention(block_q: int = 512, block_k: int = 512,
                         causal: bool = False, mesh=None):
    """Build an ``attention_fn(q, k, v, mask=None, dtype=None)``.

    q/k/v are [B, H, S, D]; mask (optional) is the key-validity mask
    [B, 1, 1, S] produced by :class:`..models.transformer.TransformerEncoder`.
    ``causal=True`` selects the kernel's fused autoregressive masking (the
    decoder/GPT path) — the kernel then also skips the fully-masked upper
    blocks, the usual ~2x flash speedup for causal attention.

    ``mesh`` is the trainer's device mesh. XLA cannot partition a Mosaic
    kernel ("wrap the call in a shard_map"), so on more than one device the
    kernel runs under ``shard_map``: batch rows split over ``'data'``, heads
    over ``'model'`` where the mesh has that axis, each device running the
    kernel on its own tile — attention mixes nothing across rows or heads,
    so no collective is needed. A batch the data axis does not divide
    (``model.init``'s batch of 1; a train or eval batch always divides) runs
    the same kernel whole on every device.
    """
    use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        # No try/except: on a TPU a missing kernel module is an error, not
        # a reason to run dense attention under the flag's name.
        from jax.experimental.pallas.ops.tpu import flash_attention as fa

    def kernel(q, k, v, ids):
        seq = q.shape[2]
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

    def per_device(batch_axis):
        qkv = P(batch_axis, "model" if "model" in mesh.axis_names else None,
                None, None)
        # check_vma off: the library kernel's out_shape declares no vma,
        # which pallas_call rejects under the check.
        return shard_map(
            kernel, mesh=mesh, in_specs=(qkv, qkv, qkv, P(batch_axis, None)),
            out_specs=qkv, check_vma=False,
        )

    over_mesh = use_pallas and mesh is not None and mesh.size > 1
    if over_mesh:
        split, whole = per_device("data"), per_device(None)

    def attention_fn(q, k, v, mask=None, dtype=None, segment_ids=None):
        if not use_pallas:
            from ..models.transformer import dot_product_attention

            if segment_ids is not None:
                # Packed sequences: the block mask supersedes the plain
                # key-validity mask (it encodes validity AND segment
                # boundaries); causal still composes inside.
                mask = segment_attention_mask(segment_ids)
            return dot_product_attention(q, k, v, mask=mask, dtype=q.dtype,
                                         causal=causal)
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
            return kernel(q, k, v, ids)
        tiles = q.shape[0] % mesh.shape["data"] == 0
        return (split if tiles else whole)(q, k, v, ids)

    return attention_fn
