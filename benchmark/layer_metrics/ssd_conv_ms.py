"""Device time of one step in the Mamba-2 layers' depthwise causal
convolution, its bias and its SiLU, forward and backward, all nine layers:
operations of ``jit_step`` under the model's ``ssd.conv`` scope
(``models/transformer.py`` ``Mamba2Mixer``: ``ops/conv.py``
``causal_conv_silu`` over the first 4,352 columns of the fused projection; on
the chip the Pallas kernel pair, which reads them where they lie)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "ssd.conv")
