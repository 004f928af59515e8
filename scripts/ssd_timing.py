"""The state-space dual's two forms timed on the chip at the shapes the
Granite cell calls it (one row of 8,192 tokens, 64 heads of 64 over one
group of 128 states, bf16 operands, float32 ``dt``), forward alone and
forward + backward in one program, at several chunks and head groups:

    chiprun --chips 1 -- python3 scripts/ssd_timing.py [--pairs 128x16,k128x8]

A row a ``chunk x heads`` pair: ``128x16`` is ``ops/ssd.py``
``ssd_chunked(chunk=128, group=16)``, the plain form in XLA; ``k128x8`` is
``ssd_kernel(chunk=128, block_h=8)``, the Pallas kernel pair (``ssd_fwd`` /
``ssd_bwd`` in a trace). ms forward, ms forward + backward (the plain form's
backward pass makes a group's forward again, as the step does; the kernels'
keeps one state a chunk), and how far the output and each of the six
gradients lie from the first pair's in float32. Times are the host's
clock around ``CALLS`` calls that end in ``block_until_ready`` (one program
a call; the device's own time is in a trace of the cell), so it wants a TPU
and fails without one. The decay masks' exponentials are ``S x chunk`` a
head, so a smaller chunk is less work for the vector unit, while the states
between chunks (``S / chunk`` of ``[64, 64, 128]`` float32) grow: the table
says where the two meet. Not tier-1; ``PERF.md`` section 6 holds the table it
gave, and ``CHUNK``, ``BLOCK_H`` and ``GROUP_H`` in ``ops/ssd.py`` are its
fastest rows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CALLS = 10
SEQ, HEADS, DIM, STATES = 8192, 64, 64, 128
PAIRS = ("128x16,k128x8,k128x16,k256x8,k256x16,k512x8,k128x4,128x8,128x64,"
         "64x16,256x16,256x8")
NAMES = ("x", "dt", "a", "b", "c", "d")


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--pairs", default=PAIRS)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from lance_distributed_training_tpu.ops import ssd

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a device time needs a TPU; found {device}")

    keys = jax.random.split(jax.random.key(0), 7)
    x = jax.random.normal(keys[0], (1, SEQ, HEADS, DIM), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, SEQ, HEADS)) - 2.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (HEADS,), minval=-4.0,
                                    maxval=2.0))
    b = jax.random.normal(keys[3], (1, SEQ, STATES), jnp.bfloat16)
    c = jax.random.normal(keys[4], (1, SEQ, STATES), jnp.bfloat16)
    d = jax.random.normal(keys[5], (HEADS,))
    ct = jax.random.normal(keys[6], (1, SEQ, HEADS, DIM))
    operands = (x, dt, a, b, c, d)

    def timed(fn):
        out = jax.block_until_ready(fn(*operands))
        t0 = time.monotonic()
        for _ in range(CALLS):
            last = fn(*operands)
        jax.block_until_ready(last)
        return out, (time.monotonic() - t0) / CALLS * 1e3

    def far(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    rows, first = [], None
    for pair in args.pairs.split(","):
        kernel = pair.startswith("k")
        chunk, group = (int(n) for n in pair.lstrip("k").split("x"))

        def form(*operands, kernel=kernel, chunk=chunk, group=group):
            if kernel:
                return ssd.ssd_kernel(*operands, chunk=chunk,
                                      block_h=group)[0]
            return ssd.ssd_chunked(*operands, chunk=chunk, group=group)[0]

        def loss(*operands):
            y = form(*operands)
            return (y.astype(jnp.float32) * ct).sum(), y

        row = {"form": "kernel" if kernel else "plain", "chunk": chunk,
               "heads": group}
        try:
            _, row["fwd_ms"] = timed(jax.jit(form))
            ((_, y), grads), row["fwd_bwd_ms"] = timed(jax.jit(
                jax.value_and_grad(loss, argnums=range(6), has_aux=True)))
        except Exception as e:  # a pair the compiler refuses: say so, go on
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
            continue
        if first is None:
            first = (y, grads)
        row["y_far"] = far(y, first[0])
        row.update({f"d{name}_far": far(g, w)
                    for name, g, w in zip(NAMES, grads, first[1])})
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "ssd_timing")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "table.json"), "w") as f:
        json.dump({"device": device.device_kind, "seq": SEQ, "heads": HEADS,
                   "head_dim": DIM, "states": STATES, "calls": CALLS,
                   "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
