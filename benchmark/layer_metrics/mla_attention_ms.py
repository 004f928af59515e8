"""Device time of one step in the latent-attention sub-layer, forward and
backward, all layers: operations of ``jit_step`` under the model's
``attention`` scope (``models/transformer.py`` ``DecoderBlock``; inside it
``mla.project``: the four projections, the latent's norm and the rotary
turns, and ``mla.kernel``: scores, softmax and context, on the chip the
fused kernel for 192-wide keys and 128-wide values)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "attention")
