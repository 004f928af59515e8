"""Device time of one step in the Gated DeltaNets' depthwise causal
convolution and its SiLU, forward and backward, all linear-attention layers:
operations under the model's ``gdn.conv`` scope (``models/transformer.py``
``GatedDeltaNet`` over ``causal_depthwise_conv``: 4 taps over the 8,192
channels of queries, keys and values, sums in float32). Elementwise over ``[S,
8,192]``: bound by memory, so the number says how much of it XLA fuses."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "gdn.conv")
