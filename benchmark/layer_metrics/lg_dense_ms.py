"""Device time of one step in Laguna's leading dense layer's feed-forward,
forward and backward: operations of ``jit_step`` under the model's
``mlp.dense`` scope (``models/transformer.py`` ``DecoderBlock``: published
layer 0's SwiGLU of 12,288, three products of [8,192, 3,072] by
[3,072, 12,288]; 19% of the step's counted multiply-adds)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "mlp.dense")
