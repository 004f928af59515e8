"""Device time of one step in the Gated DeltaNet mixers, forward and backward
(and the backward pass's second forward of the rule), all linear-attention
layers: operations of ``jit_step`` under the model's ``linear_attention``
scope (``models/transformer.py`` ``DecoderBlock``; inside it ``gdn.project``:
the fused projection, the 64-wide one and the output projection,
``gdn.conv``: the 4-tap depthwise convolution and its SiLU, ``gdn.gates``:
beta, g and the L2 norms, ``gdn.kernel``: the gated delta rule and nothing
else, ``gdn.norm``: the norm gated by SiLU)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "linear_attention")
