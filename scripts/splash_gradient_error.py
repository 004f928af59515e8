"""How far the splash kernels' gradients lie from an f32 reference, by the
form of the backward pass: bf16 inputs at the cells' shapes, on the chip.

The dq kernel sums a query block's dq over all key blocks in f32 scratch and
rounds once. The library's fused backward writes one dq partial a key block
in the queries' dtype and XLA sums them: each partial is rounded to bf16 on
its way through HBM. This reads what that costs: dq, dk, dv and the output
of ``ops/flash.py`` ``unequal_attention`` against dense attention computed in
f32 (``Precision.HIGHEST``) from the same bf16 inputs, for square blocks of
512, the best tiling with two backward kernels, and the fused backward.

    chiprun --chips 1 -- python3 scripts/splash_gradient_error.py

Prints a table a shape and writes ``chiprun_out/splash_gradient_error.json``;
``PERF.md`` section 6 (PR 34) holds what it gave. Not tier-1.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lance_distributed_training_tpu.ops.flash import (  # noqa: E402
    SplashTiling,
    _square,
    unequal_attention,
)

SEQ = 8192
SEEDS = (0, 1)
# (q, k, v) heads and widths as the cells' layers call unequal_attention,
# and the three forms: today's, the fastest with two kernels, the fused
SHAPES = {
    "moonlight": ((16, 192), (16, 192), (16, 128), {
        "square 512": _square(512),
        "two kernels": SplashTiling((1024, 1024, 256), (1024, 1024, 512),
                                    (1024, 1024)),
        "fused": SplashTiling((1024, 1024, 256), (1024, 1024, 1024), None)}),
    "phi4_causal": ((40, 64), (20, 64), (10, 128), {
        "square 512": _square(512),
        "two kernels": SplashTiling((2048, 2048, 256), (1024, 1024, 1024),
                                    (1024, 1024)),
        "fused": SplashTiling((2048, 2048, 256), (1024, 2048, 512), None)}),
}
NAMES = ("out", "dq", "dk", "dv")


def kernel_form(tiling):
    @jax.jit
    def run(q, k, v, w):
        def loss(q, k, v):
            out = unequal_attention(q, k, v, jnp.ones((1, SEQ), jnp.int32),
                                    causal=True, tiling=tiling)
            return (out.astype(jnp.float32) * w).sum(), out

        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
        return (out, *grads)

    return run


@jax.jit
def reference_group(q, k, v, w):
    """Dense causal attention of one value head's query heads, in f32: q
    ``[Hq, S, D]``, k ``[Hk, S, D]``, v ``[1, S, Dv]``; the output and the
    gradients of ``sum(out * w)``, k's and v's summed over their groups."""
    hi = jax.lax.Precision.HIGHEST

    def loss(q, k, v):
        k = jnp.repeat(k, q.shape[0] // k.shape[0], axis=0)
        s = jnp.einsum("hqd,hkd->hqk", q, k, precision=hi) / q.shape[-1] ** .5
        at = jnp.arange(SEQ)
        s = jnp.where(at[:, None] >= at[None, :], s, -jnp.inf)
        out = jnp.einsum("hqk,kd->hqd", jax.nn.softmax(s, axis=-1), v[0],
                         precision=hi)
        return (out * w).sum(), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out, *grads)


def reference(q, k, v, w):
    """The same four arrays as a kernel form gives, in f32 on the host, a
    value head's group at a time (a group's scores are 1 GiB at most)."""
    q, k, v = (t[0].astype(jnp.float32) for t in (q, k, v))
    groups = v.shape[0]
    per_q, per_k = q.shape[0] // groups, k.shape[0] // groups
    parts = [reference_group(q[g * per_q:(g + 1) * per_q],
                             k[g * per_k:(g + 1) * per_k], v[g:g + 1],
                             w[0, g * per_q:(g + 1) * per_q])
             for g in range(groups)]
    return [np.concatenate([np.asarray(p[i], np.float64) for p in parts])
            for i in range(4)]


def errors(got, want) -> dict:
    """Each array's distance from the reference over the reference's norm,
    and its largest element's over the reference's root mean square."""
    out = {}
    for name, g, w in zip(NAMES, got, want):
        diff = np.asarray(g[0], np.float64) - w
        out[name] = {"rel": float(np.linalg.norm(diff) / np.linalg.norm(w)),
                     "max_over_rms": float(np.abs(diff).max()
                                           / np.sqrt(np.mean(w * w)))}
    return out


def main() -> None:
    device = jax.devices()[0]
    print(f"device: {device.device_kind}")
    results = []
    for shape, (*qkv, forms) in SHAPES.items():
        runs = {form: kernel_form(tiling) for form, tiling in forms.items()}
        for seed in SEEDS:
            keys = jax.random.split(jax.random.key(seed), 4)
            q, k, v = (jax.random.normal(key, (1, heads, SEQ, width),
                                         jnp.bfloat16)
                       for key, (heads, width) in zip(keys, qkv))
            w = jax.random.normal(keys[3], (1, qkv[0][0], SEQ, qkv[2][1]),
                                  jnp.bfloat16).astype(jnp.float32)
            want = reference(q, k, v, w)
            for form, run in runs.items():
                row = {"shape": shape, "seed": seed, "form": form,
                       **errors(run(q, k, v, w), want)}
                results.append(row)
                print(shape, seed, form, " ".join(
                    f"{n} {row[n]['rel']:.5%} (max {row[n]['max_over_rms']:.4f})"
                    for n in NAMES), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "splash_gradient_error.json"),
              "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
