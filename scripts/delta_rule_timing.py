"""The gated delta rule's two forms timed against each other on the chip, at
the shapes the Qwen3-Next cell calls them (one row of 8,192 tokens, 16 key
heads serving 32 value heads of 128, bf16), forward alone and forward +
backward in one program as the layer runs them (both forms make the forward
again in the backward pass: two forwards and one backward):

    chiprun --chips 1 -- python3 scripts/delta_rule_timing.py

* ``chunked``: ``ops/delta.py`` ``gated_delta_rule`` off the kernel path:
  ``delta_chunked`` a group of ``GROUP_H`` heads at a time, the chunks'
  preparation and the ``lax.scan`` over them in XLA;
* ``kernel, block_h=N``: ``delta_kernel``, preparation and recurrence in the
  Pallas kernels (``delta_rule_fwd``; ``delta_rule_fwd_kept`` and
  ``delta_rule_bwd`` in the backward pass) with ``N`` heads' states in VMEM
  a grid step.

Times are the host's clock around ``CALLS`` calls that end in
``block_until_ready`` (one program a call, 5 to 100 ms each: the dispatch
is noise), so it wants a TPU and fails without one. It also prints how far
the two forms' outputs and gradients lie from the plain chunked form computed
in float32. Not tier-1; ``PERF.md`` section 6 holds the table it gave.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CALLS = 10
BLOCKS = (2, 4, 8)  # value heads a grid step
ROWS, SEQ, KEY_HEADS, HEADS, DIM = 1, 8192, 16, 32, 128


def inputs(dtype):
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.key(0), 6)
    q = jax.random.normal(ks[0], (ROWS, SEQ, KEY_HEADS, DIM))
    k = jax.random.normal(ks[1], (ROWS, SEQ, KEY_HEADS, DIM))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(DIM)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (ROWS, SEQ, HEADS, DIM))
    # decays a token from 1e-4 (memory over the whole row) to 20
    g = -jnp.exp(jax.random.uniform(ks[3], (ROWS, SEQ, HEADS),
                                    minval=np.log(1e-4), maxval=np.log(20.0)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (ROWS, SEQ, HEADS)))
    ct = jax.random.normal(ks[5], v.shape)
    return tuple(t.astype(dtype) for t in (q, k, v)) + (g, beta), ct


def main() -> None:
    import jax
    import jax.numpy as jnp

    from lance_distributed_training_tpu.ops import delta

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"a device time needs a TPU; found {device}")
    args, ct = inputs(jnp.bfloat16)
    args32, _ = inputs(jnp.float32)

    def program(form):
        def run(*a):
            o, last = form(*a)
            return (o.astype(jnp.float32) * ct).sum(), (o, last)
        return jax.jit(jax.value_and_grad(run, argnums=range(5),
                                          has_aux=True))

    def chunked(*a):
        """``gated_delta_rule`` where the kernels do not apply."""
        original = delta.delta_fused_applies
        delta.delta_fused_applies = lambda *s, **k: False
        try:
            return delta.gated_delta_rule(*a)
        finally:
            delta.delta_fused_applies = original

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        t0 = time.monotonic()
        for _ in range(CALLS):
            last = fn(*a)
        jax.block_until_ready(last)
        return out, (time.monotonic() - t0) / CALLS * 1e3

    with jax.default_matmul_precision("highest"):
        (_, (o32, s32)), g32 = jax.block_until_ready(
            program(chunked)(*args32))
    forms = {"chunked": chunked}
    for block_h in BLOCKS:
        forms[f"kernel, block_h={block_h}"] = functools.partial(
            delta.delta_kernel, block_h=block_h)
    out_dir = os.path.join("chiprun_out", "delta_timing")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for name, form in forms.items():
        t0 = time.monotonic()
        try:
            _, fwd_ms = timed(jax.jit(form), *args)
            ((_, (o, last)), grads), ms = timed(program(form), *args)
        except Exception as e:  # a refusal is a row of the table
            rows.append({"form": name, "error": " ".join(str(e).split())[:300]})
            print(json.dumps(rows[-1]), flush=True)
            continue
        seconds = time.monotonic() - t0

        def far(a, b):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

        rows.append({
            "form": name, "fwd_ms": round(fwd_ms, 3),
            "fwd_bwd_ms": round(ms, 3), "row_s": round(seconds, 1),
            "o_rel": far(o, o32), "state_rel": far(last, s32),
            **{f"d{n}_rel": far(a, b)
               for n, a, b in zip(("q", "k", "v", "g", "beta"), grads, g32)}})
        print(json.dumps(rows[-1]), flush=True)
    with open(os.path.join(out_dir, "table.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
