"""Device time of one step in the differential attention sub-layers (window,
full and cross), forward and backward: operations of ``jit_step`` under the
model's ``attention`` scope (``models/transformer.py`` ``DecoderBlock``;
inside it ``attn.window``, ``attn.full`` and ``attn.cross`` around the
kernels, ``diff.combine`` for lambda, the subtraction and the 128-wide norm,
and the projections)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "attention")
