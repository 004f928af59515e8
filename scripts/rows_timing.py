"""Rows -> tokens under a share (``ops/rows.py``) timed in its forms on the
chip, at the shapes the three share cells with more than one expert a token
call it (bf16 rows, uniform ids, the usual list of twice an even share):
Qwen3-Next ``[20,480, 2,048] -> [16,384, 2,048]`` with 10 slots a token and
32 of 512 experts held, SmallThinker ``[49,152, 2,560] -> [16,384, 2,560]``
with 6 and 16 of 64, Moonlight ``[12,288, 2,048] -> [8,192, 2,048]`` with 6
and 8 of 64; each weighted into f32 (the sum back) and unweighted into bf16
(the cotangent of tokens -> rows):

    chiprun --chips 1 -- python3 scripts/rows_timing.py

* ``loop``: ``sum_slots``, the plain form, a ``fori_loop`` over the slots;
* ``kernel, TxR``: ``rows_kernel`` at blocks of ``T`` tokens and chunks of
  ``R`` sorted rows (``rows_sum`` in a trace), its sort and gather included;
* ``sort``, ``sort + gather``: the kernel's preparation in XLA alone;
* ``row copies, f32``: the other candidate's data path, one asynchronous copy
  a row from HBM into VMEM, 128 in flight, as a gather of the same rows and
  nothing else. Mosaic takes no one-row slice of a tiled ``[R, H]`` array
  ("must be aligned to tiling (8)") and none of a bf16 ``[R, 1, H]`` one (rows
  pair up), so this reads f32 rows laid out ``[R, 1, H]``, a copy of the
  whole array that XLA makes first and the time includes.

``gb_s`` is the bytes that must move (one read of the built rows, one write of
the tokens) over the time. Times are the host's clock around ``CALLS`` calls
that end in ``block_until_ready`` (one program a call; under half a
millisecond the dispatch shows), so it wants a TPU and fails without one. It
also prints how far each form lies from the loop. Not tier-1; ``PERF.md``
section 6 holds the table it gave.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CALLS = 30
SHAPES = {  # name: (tokens, slots a token, experts, held, width)
    "qwen3_next": (16384, 10, 512, 32, 2048),
    "smallthinker": (16384, 6, 64, 16, 2560),
    "moonlight": (8192, 6, 64, 8, 2048),
}
TILES = ((128, 128), (128, 256), (256, 128), (256, 256))


def inputs(tokens, k, experts, held, width, seed=0):
    """The share path's integers as ``models/moe.py`` makes them, for uniform
    ids: ``(rows [R, H] bf16, way, weights [T, k] f32)``."""
    import jax
    import jax.numpy as jnp

    from lance_distributed_training_tpu.ops.rows import Way

    ks = jax.random.split(jax.random.key(seed), 3)
    top_e = jax.lax.top_k(jax.random.uniform(ks[0], (tokens, experts)), k)[1]
    flat = top_e.reshape(-1)
    flat = jnp.where(flat < held, flat, held)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(tokens * k, dtype=order.dtype))
    live = (flat < held).sum()
    built = min(-(-2 * tokens * k * held // (experts * 128)) * 128, tokens * k)
    assert int(live) <= built, (int(live), built)
    pos = inverse.reshape(tokens, k)
    way = Way(order[:built], jnp.arange(built) < live, pos, pos < live)
    rows = jax.random.normal(ks[1], (built, width)).astype(jnp.bfloat16)
    return rows, way, jax.random.uniform(ks[2], (tokens, k))


def row_copies(rows, way, weights, chunk=128):
    """``rows[by_token]`` in f32 by one copy a row, ``chunk`` in flight."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(idx_ref, rows_ref, out_ref, held_ref, sem):
        first = pl.program_id(0) * chunk

        @pl.loop(0, chunk)
        def _(j):
            pltpu.make_async_copy(rows_ref.at[idx_ref[first + j]],
                                  held_ref.at[j], sem).start()

        @pl.loop(0, chunk)
        def _(j):
            pltpu.make_async_copy(rows_ref.at[0], held_ref.at[j], sem).wait()

        out_ref[...] = held_ref[...].reshape(out_ref.shape)

    r, h = rows.shape
    by_token = jax.lax.sort_key_val(
        jnp.where(way.live, way.head, jnp.iinfo(jnp.int32).max),
        jnp.arange(r, dtype=jnp.int32))[1]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(r // chunk,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((chunk, h), lambda c, idx: (c, 0)),
            scratch_shapes=[pltpu.VMEM((chunk, 1, h), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((r, h), jnp.float32),
        name="row_copies")(by_token, rows.astype(jnp.float32).reshape(r, 1, h))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from lance_distributed_training_tpu.ops import rows as ops

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a device time needs a TPU; found {device}")

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        t0 = time.monotonic()
        for _ in range(CALLS):
            last = fn(*a)
        jax.block_until_ready(last)
        return out, (time.monotonic() - t0) / CALLS * 1e3

    def far(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    def sort(rows, way, weights):
        return jax.lax.sort_key_val(
            jnp.where(way.live, way.head, jnp.iinfo(jnp.int32).max),
            jnp.arange(rows.shape[0], dtype=jnp.int32))[1]

    def sort_gather(rows, way, weights):
        return jnp.take(rows, sort(rows, way, weights), axis=0, mode="clip")

    out_dir = os.path.join("chiprun_out", "rows_timing")
    os.makedirs(out_dir, exist_ok=True)
    table = []
    for shape, sizes in SHAPES.items():
        rows, way, weights = inputs(*sizes)
        moved = rows.size * 2  # one read of the built rows, bf16
        for case, w, dtype in (("weighted f32", weights, jnp.float32),
                               ("plain bf16", None, jnp.bfloat16)):
            wrote = sizes[0] * sizes[4] * jnp.dtype(dtype).itemsize
            forms = {"loop": lambda r, way, w: ops.sum_slots(
                r, way, w).astype(dtype)}
            for block_t, block_r in TILES:
                forms[f"kernel, {block_t}x{block_r}"] = functools.partial(
                    ops.rows_kernel, dtype=dtype, block_t=block_t,
                    block_r=block_r)
            if w is None:
                forms.update({"sort": sort, "sort + gather": sort_gather,
                              "row copies, f32": row_copies})
            want = None
            for name, form in forms.items():
                try:
                    y, ms = timed(jax.jit(form), rows, way, w)
                except Exception as e:  # a refusal is a row of the table
                    table.append({"shape": shape, "case": case, "form": name,
                                  "error": " ".join(str(e).split())[:300]})
                    print(json.dumps(table[-1]), flush=True)
                    continue
                want = y if want is None else want
                table.append({
                    "shape": shape, "case": case, "form": name,
                    "ms": round(ms, 3),
                    "gb_s": round((moved + wrote) / ms / 1e6, 1),
                    **({"rel": far(y, want)} if y.shape == want.shape
                       else {})})
                print(json.dumps(table[-1]), flush=True)
    with open(os.path.join(out_dir, "table.json"), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
