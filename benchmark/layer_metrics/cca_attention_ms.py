"""Device time of one step in the compressed-convolutional-attention
sub-layer, forward and backward, all layers: operations of ``jit_step`` under
the model's ``attention`` scope (``models/transformer.py`` ``DecoderBlock``;
inside it ``cca.project``: the three projections into the latent,
``cca.mix``: the two convolutions, the q-k mean, the norms, the rotary turn
and the value shift, ``cca.kernel``: scores, softmax and context, on the chip
the fused kernel over grouped heads, and ``cca.out``: the latent back to the
stream)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "attention")
