"""The Gated DeltaNet's two norms timed where they ran and where they run,
on the chip, at the shapes the Qwen3-Next cell calls them (one row of 8,192
tokens, 16 key heads serving 32 value heads of 128, bf16, the convolved
projection ``[q; k; v]`` 8,192 columns wide and the fused one 12,288),
forward alone and forward + backward in one program:

    chiprun --chips 1 -- python3 scripts/gdn_norms_timing.py

* ``rule, norm in XLA``: the layer's lines before PR 48: the unit norms of
  ``q`` and ``k`` in ``jax.numpy``, the slice of ``v``, then ``ops/delta.py``
  ``delta_kernel`` on the three arrays;
* ``rule, norm in the kernels``: ``delta_kernel_packed(.., qk_norm=True)``,
  the kernels reading ``q``, ``k``, ``v`` where they lie and norming a key
  head's tile as they load it;
* ``norm, plain``: ``ops/norm.py`` ``gated_rms_norm_plain``, the layer's
  ``jax.numpy`` lines, ``z`` sliced out of the fused projection;
* ``norm, kernel SxD/R``: ``norm_kernel`` at sequence tiles of ``S`` tokens,
  channel blocks of ``D`` and loop steps of ``R`` tokens
  (``gated_rms_norm_fwd`` / ``_bwd`` in a trace).

``gb_s`` is the bytes that must move over the time: the norm reads ``o`` and
``z`` and writes the output forward (three passes over ``[S, 4,096]`` bf16),
and reads ``o``, ``z`` and the cotangent and writes ``do`` and ``dz``
backward (five more). Times are the host's clock around ``CALLS`` calls that
end in ``block_until_ready`` (one program a call; under half a millisecond
the dispatch shows: the device's own time is in a trace of the cell), so it
wants a TPU and fails without one. It also prints how far each form's output
and gradients lie from the plain form's in float32. Not tier-1; ``PERF.md``
section 6 holds the table it gave.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CALLS = 20
SEQ, KEY_HEADS, HEADS, DIM = 8192, 16, 32, 128
KEYS, VALUES = KEY_HEADS * DIM, HEADS * DIM
TILES = ((1024, 1024, 64), (1024, 1024, 32), (1024, 1024, 128),
         (512, 1024, 64), (2048, 1024, 64), (1024, 512, 64))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lance_distributed_training_tpu.ops import delta, norm

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

    def table(forms, args, args32, ct, names, moved=None):
        """A row a form: forward, forward + backward, and the distance of
        the output and of each gradient from the first form's in f32."""
        def program(form):
            def loss(*a):
                y = form(*a)
                return (y.astype(jnp.float32) * ct).sum(), y
            return jax.jit(jax.value_and_grad(
                loss, argnums=range(len(args)), has_aux=True))

        with jax.default_matmul_precision("highest"):
            (_, y32), g32 = jax.block_until_ready(
                program(next(iter(forms.values())))(*args32))
        for name, form in forms.items():
            try:
                _, fwd_ms = timed(jax.jit(form), *args)
                ((_, y), grads), ms = timed(program(form), *args)
            except Exception as e:  # a refusal is a row of the table
                rows.append({"form": name,
                             "error": " ".join(str(e).split())[:300]})
                print(json.dumps(rows[-1]), flush=True)
                continue
            rows.append({
                "form": name, "fwd_ms": round(fwd_ms, 3),
                "fwd_bwd_ms": round(ms, 3),
                **({"fwd_gb_s": round(3 * moved / fwd_ms / 1e6, 1),
                    "fwd_bwd_gb_s": round(8 * moved / ms / 1e6, 1)}
                   if moved else {}),
                "y_rel": far(y, y32),
                **{f"d{n}_rel": far(a, b)
                   for n, a, b in zip(names, grads, g32)}})
            print(json.dumps(rows[-1]), flush=True)

    rows = []
    ks = jax.random.split(jax.random.key(0), 8)
    # the rule: the convolved projection (after a SiLU: above -0.28), decays
    # a token from 1e-4 (memory over the whole row) to 20
    mixed = jax.nn.silu(jax.random.normal(ks[0], (1, SEQ, 2 * KEYS + VALUES)))
    g = -jnp.exp(jax.random.uniform(ks[1], (1, SEQ, HEADS),
                                    minval=np.log(1e-4), maxval=np.log(20.0)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (1, SEQ, HEADS)))
    ct = jax.random.normal(ks[3], (1, SEQ, HEADS, DIM))

    def in_xla(mixed, g, beta):
        q, k, v = delta._columns(mixed, KEY_HEADS, DIM, HEADS, delta._normed)
        return delta.delta_kernel(q, k, v, g, beta)[0]

    def in_kernels(mixed, g, beta):
        return delta.delta_kernel_packed(
            mixed, g, beta, key_heads=KEY_HEADS, key_dim=DIM,
            qk_norm=True)[0]

    def chunked(mixed, g, beta):
        """``gated_delta_rule_packed`` where the kernels do not apply: the
        plain norm and ``delta_chunked``, what the others are held to."""
        original = delta.delta_fused_applies
        delta.delta_fused_applies = lambda *s, **k: False
        try:
            return delta.gated_delta_rule_packed(
                mixed, g, beta, key_heads=KEY_HEADS, key_dim=DIM,
                qk_norm=True)[0]
        finally:
            delta.delta_fused_applies = original

    table({"rule, plain norm and chunked form": chunked,
           "rule, norm in XLA": in_xla,
           "rule, norm in the kernels": in_kernels},
          (mixed.astype(jnp.bfloat16), g, beta), (mixed, g, beta), ct,
          ("mixed", "g", "beta"))

    # the gated norm: the rule's output and the fused projection
    o = jax.random.normal(ks[4], (1, SEQ, HEADS, DIM)) * 0.3
    qkvz = jax.random.normal(ks[5], (1, SEQ, 2 * KEYS + 2 * VALUES))
    scale = 1.0 + 0.1 * jax.random.normal(ks[6], (DIM,))
    ct = jax.random.normal(ks[7], (1, SEQ, VALUES))
    forms = {"norm, plain": norm.gated_rms_norm_plain}
    for block_s, block_d, step in TILES:
        forms[f"norm, kernel {block_s}x{block_d}/{step}"] = functools.partial(
            norm.norm_kernel, block_s=block_s, block_d=block_d,
            step_rows=step)
    table(forms, (o.astype(jnp.bfloat16), qkvz.astype(jnp.bfloat16), scale),
          (o, qkvz, scale), ct, ("o", "z", "scale"), SEQ * VALUES * 2)
    out_dir = os.path.join("chiprun_out", "gdn_norms_timing")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "table.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
