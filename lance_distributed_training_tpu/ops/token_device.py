"""Jitted ragged-token pack/unpack — the device half of the token plane.

The host half (:mod:`..data.token_pack`) ships a variable-length column as
a flat ``values`` page + ``offsets`` + a deterministic FFD pack plan
(``slot``/``start`` per sequence, a ``rows × pack_len`` grid). This module
finishes the job as ONE pure jitted kernel per ragged column:

    scatter each sequence's token run into grid[slot, start:start+len]
    and emit segment_ids (1-based sequence index; 0 = dead padding) and
    position_ids (intra-sequence offset) over the same grid

Design constraints (pinned by LDT101/LDT1301 — this module is listed under
``[tool.ldt-check]`` hot-paths AND content-paths, exactly like
``ops/jpeg_device.py``):

* **pure jit** — no host callbacks, no clocks, no RNG; the identical code
  path runs on CPU today and a real TPU unmodified (the scatter lowers to
  one ``scatter`` HLO with unique indices);
* **bit-deterministic** — indices are disjoint by construction (the
  planner never overlaps runs), so ``.at[].set`` has no collision order to
  vary; the same ragged page always yields the same packed slab;
* **static shapes** — ``rows``/``pack_len`` are static jit arguments read
  from the batch's host-side ``_host_pack_meta`` (never from device
  memory: the transform performs **zero** device syncs), and the values
  page's capacity is already bucketed by the pool, so the jit cache holds
  a short ladder of shapes, not one per batch.

``unpack_token_batch`` is the exact inverse (packed slab + offsets + plan
→ the flat values page) — the round-trip identity the tests pin.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from ..data.token_pack import (
    OFFSETS_SUFFIX,
    PACK_META_KEY,
    PACK_MODE_FFD,
    PACK_SLOT_KEY,
    PACK_START_KEY,
    VALUES_SUFFIX,
    is_host_meta_key,
    is_ragged_batch,
    ragged_bases,
)

__all__ = [
    "pack_token_batch",
    "unpack_token_batch",
    "make_pack_transform",
    "is_packed_input",
]


def is_packed_input(batch) -> bool:
    """Does this batch carry the ragged convention (needs the pack
    transform before the train step)?"""
    return is_ragged_batch(batch)


@partial(jax.jit, static_argnames=("rows", "pack_len"))
@jax.named_scope("pack")  # metadata only: names the kernel in a device trace
def pack_token_batch(
    values: jax.Array,
    offsets: jax.Array,
    slot: jax.Array,
    start: jax.Array,
    *,
    rows: int,
    pack_len: int,
):
    """Ragged runs → ``(grid [rows, L], segment_ids, position_ids)``.

    ``values`` is the flat (bucket-padded) token page, ``offsets`` the
    ``[n+1]`` row boundaries, ``slot``/``start`` the planner's placement.
    Tokens beyond a slot's length cap are dropped (the planner already
    counted them); grid cells no sequence covers stay 0 with segment 0 —
    dead by construction for any segment-aware consumer.
    """
    cap = values.shape[0]
    n = slot.shape[0]
    offsets = offsets.astype(jnp.int32)
    lengths = jnp.minimum(offsets[1:] - offsets[:-1], pack_len)  # [n]
    flat = jnp.arange(cap, dtype=jnp.int32)
    # Sequence owning each flat position (positions past offsets[n] — the
    # capacity bucket's zero tail — clamp into range and are masked below).
    seq = jnp.clip(
        jnp.searchsorted(offsets, flat, side="right") - 1, 0, n - 1
    ).astype(jnp.int32)
    k = flat - offsets[seq]  # intra-sequence offset
    valid = (flat < offsets[n]) & (k < lengths[seq])
    dest = slot[seq].astype(jnp.int32) * pack_len \
        + start[seq].astype(jnp.int32) + k
    # Invalid positions scatter past the grid; mode="drop" discards them.
    dest = jnp.where(valid, dest, rows * pack_len)
    grid = jnp.zeros((rows * pack_len,), values.dtype).at[dest].set(
        values, mode="drop"
    )
    seg = jnp.zeros((rows * pack_len,), jnp.int32).at[dest].set(
        seq + 1, mode="drop"
    )
    pos = jnp.zeros((rows * pack_len,), jnp.int32).at[dest].set(
        k, mode="drop"
    )
    return (
        grid.reshape(rows, pack_len),
        seg.reshape(rows, pack_len),
        pos.reshape(rows, pack_len),
    )


@partial(jax.jit, static_argnames=("capacity",))
def unpack_token_batch(
    grid: jax.Array,
    offsets: jax.Array,
    slot: jax.Array,
    start: jax.Array,
    *,
    capacity: int,
):
    """The inverse scatter: packed slab → the flat values page (zero tail),
    for round-trip tests and consumers that want the ragged view back."""
    rows, pack_len = grid.shape
    n = slot.shape[0]
    offsets = offsets.astype(jnp.int32)
    lengths = jnp.minimum(offsets[1:] - offsets[:-1], pack_len)
    flat = jnp.arange(capacity, dtype=jnp.int32)
    seq = jnp.clip(
        jnp.searchsorted(offsets, flat, side="right") - 1, 0, n - 1
    ).astype(jnp.int32)
    k = flat - offsets[seq]
    valid = (flat < offsets[n]) & (k < lengths[seq])
    src = slot[seq].astype(jnp.int32) * pack_len \
        + start[seq].astype(jnp.int32) + k
    src = jnp.clip(src, 0, rows * pack_len - 1)
    gathered = grid.reshape(-1)[src]
    return jnp.where(valid, gathered, jnp.zeros((), grid.dtype))


def _new_shapes_counter():
    from ..obs.registry import default_registry

    return default_registry().counter("pack_new_shapes_total")


def make_pack_transform(batch_sharding=None):
    """The trainer's device-side pack stage: a transform that replaces a
    ragged batch's values/offsets/plan leaves with the packed
    ``(rows, L)`` slabs plus ``attention_mask`` (and, for FFD mode,
    ``segment_ids``/``position_ids``), passing every other leaf (image,
    label, ``_weight``) through untouched. Non-ragged batches (the
    ``--no_token_pack`` control arm) pass through whole, so one handle
    serves both arms — the ``make_batch_transform`` pattern from
    ``ops/jpeg_device.py``.

    The host-side ``_host_pack_meta`` header (a numpy passthrough leaf —
    the placement plane never device_puts ``_host_*`` keys) provides the
    static grid shape with zero device syncs; each genuinely new
    ``(rows, pack_len, capacity)`` combination costs one jit trace,
    counted on ``pack_new_shapes_total`` so the autotuner can trade
    recompiles against padding waste.

    ``batch_sharding`` (a ``NamedSharding`` over the mesh's data axis):
    the kernel's inputs are replicated (ragged leaves have no row dim to
    split), so its outputs come out replicated too — but the train step's
    ``in_shardings`` demand data-sharded batch leaves. When given, every
    packed output leaf is re-laid out to it (an async device-to-device
    reshard; the planner's ``rows_align`` guarantees divisibility).
    """
    seen_shapes = set()
    counter = _new_shapes_counter()

    def _commit(arr):
        if batch_sharding is None:
            return arr
        # Through the compat funnel (LDT801: H2D/re-layout has one door).
        from ..parallel._compat import device_put

        return device_put(arr, batch_sharding)

    def transform(batch: Dict) -> Dict:
        if not is_ragged_batch(batch):
            return batch
        import numpy as np

        meta = np.asarray(batch[PACK_META_KEY])
        rows, pack_len, _payload, mode = (int(x) for x in meta[:4])
        slot = batch[PACK_SLOT_KEY]
        start = batch[PACK_START_KEY]
        out = {
            k: v for k, v in batch.items()
            if not (
                k.endswith(VALUES_SUFFIX) or k.endswith(OFFSETS_SUFFIX)
                or k in (PACK_SLOT_KEY, PACK_START_KEY)
                or is_host_meta_key(k)
            )
        }
        seg = None
        for base in ragged_bases(batch):
            values = batch[base + VALUES_SUFFIX]
            offsets = batch[base + OFFSETS_SUFFIX]
            shape_key = (rows, pack_len, int(values.shape[0]),
                         int(offsets.shape[0]))
            if shape_key not in seen_shapes:
                seen_shapes.add(shape_key)
                counter.inc()
            grid, seg, pos = pack_token_batch(
                values, offsets, slot, start, rows=rows, pack_len=pack_len
            )
            out[base] = _commit(grid)
        if seg is not None:
            out["attention_mask"] = _commit((seg > 0).astype(jnp.int8))
            if mode == PACK_MODE_FFD:
                # Bucket mode (row-preserving, one sequence per slot) needs
                # neither: positions restart at 0 per row anyway and the
                # validity mask carries the whole story.
                out["segment_ids"] = _commit(seg)
                out["position_ids"] = _commit(pos)
        return out

    return transform


# Compile-witness funnel: same module-bottom wrap discipline as
# ops/jpeg_device.py — pack/unpack record per-def-site trace signatures when
# LDT_COMPILE_SANITIZER=1 so the CI gate can assert zero steady-state
# recompiles on the packing path.
from ..utils import compiletrack  # noqa: E402 — deliberate bottom import

if compiletrack.enabled():
    pack_token_batch = compiletrack.wrap_jit(pack_token_batch)
    unpack_token_batch = compiletrack.wrap_jit(unpack_token_batch)
