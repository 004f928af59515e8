"""The depthwise causal convolution and its SiLU timed in its two forms on
the chip, at the shapes the two cells that run it call it (bf16, four taps,
one row of 8,192 tokens): the first 8,192 columns of Qwen3-Next's fused
projection of 12,288, no bias, and the first 5,120 columns of Phi-4-mini-
flash's of 10,240, with a bias. Forward alone, and forward + backward under
``jax.checkpoint`` in one program as the layers run it (two forwards and one
backward):

    chiprun --chips 1 -- python3 scripts/conv_timing.py

* ``plain``: ``nn.silu(causal_depthwise_conv(x[..., :D], taps, bias))``, what
  ``ops/conv.py`` ``causal_conv_silu`` is off the kernel path, in XLA;
* ``kernel, SxD``: ``conv_kernel`` at sequence tiles of ``S`` tokens and
  channel blocks of ``D`` (``causal_conv_silu_fwd`` / ``_bwd`` in a trace).

``gb_s`` is the bytes that must move (one read of ``x`` and one write of the
output forward; backward a read of ``x`` and of the cotangent and a write of
``dx``: seven passes over ``[S, D]`` bf16 for the three) over the time. Times
are the host's clock around ``CALLS`` calls that end in ``block_until_ready``
(one program a call; under half a millisecond the dispatch shows: the device's
own time is in a trace of the cell), so it wants a TPU and fails without one.
It also prints how far the kernel's output and gradients lie from the plain
form's. Not tier-1; ``PERF.md`` section 6 holds the table it gave.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CALLS = 30
SEQ, TAPS = 8192, 4
SHAPES = {  # name: (the projection's columns, the convolved ones, a bias)
    "qwen3_next": (12288, 8192, False),
    "phi4_mini_flash": (10240, 5120, True),
}
TILES = ((512, 512), (512, 1024), (1024, 1024))  # (block_s, block_d)


def inputs(wide, width, has_bias):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (1, SEQ, wide)).astype(jnp.bfloat16)
    taps = jax.random.normal(ks[1], (TAPS, width)) * 0.5
    bias = jax.random.normal(ks[2], (width,)) if has_bias else None
    ct = jax.random.normal(ks[3], (1, SEQ, width)).astype(jnp.bfloat16)
    return (x, taps, bias), ct


def main() -> None:
    import jax
    import jax.numpy as jnp

    from lance_distributed_training_tpu.ops import conv

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

    out_dir = os.path.join("chiprun_out", "conv_timing")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for shape, (wide, width, has_bias) in SHAPES.items():
        args, ct = inputs(wide, width, has_bias)

        def plain(x, taps, bias):
            return jax.nn.silu(conv.causal_depthwise_conv(
                x[..., :width], taps, bias)).astype(x.dtype)

        forms = {"plain": plain}
        for block_s, block_d in TILES:
            forms[f"kernel, {block_s}x{block_d}"] = functools.partial(
                conv.conv_kernel, block_s=block_s, block_d=block_d)

        def program(form):
            def loss(*a):
                y = jax.checkpoint(form)(*a)
                return (y.astype(jnp.float32) * ct).sum(), y
            return jax.jit(jax.value_and_grad(
                loss, argnums=range(3 if has_bias else 2), has_aux=True))

        moved = SEQ * width * 2  # one pass over [S, D] bf16
        want = None
        for name, form in forms.items():
            try:
                _, fwd_ms = timed(jax.jit(form), *args)
                ((_, y), grads), ms = timed(program(form), *args)
            except Exception as e:  # a refusal is a row of the table
                rows.append({"shape": shape, "form": name,
                             "error": " ".join(str(e).split())[:300]})
                print(json.dumps(rows[-1]), flush=True)
                continue
            want = want or (y, grads)
            rows.append({
                "shape": shape, "form": name, "fwd_ms": round(fwd_ms, 3),
                "fwd_gb_s": round(2 * moved / fwd_ms / 1e6, 1),
                "fwd_fwd_bwd_ms": round(ms, 3),
                "fwd_fwd_bwd_gb_s": round(7 * moved / ms / 1e6, 1),
                "y_rel": far(y, want[0]),
                **{f"d{n}_rel": far(a, b) for n, a, b in zip(
                    ("x", "taps", "bias"), grads, want[1])}})
            print(json.dumps(rows[-1]), flush=True)
    with open(os.path.join(out_dir, "table.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
