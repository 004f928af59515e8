"""Device time of one step in the MLP router, forward and backward, all
layers: operations under the model's ``moe.router`` scope, which for this
model holds the down-projection to 256, the mix with the previous layer's
state, the norm, the three-layer MLP (all float32 at the highest precision:
six bf16 passes a product), the softmax over 16, the selection bias and the
choice of the one expert (``models/moe.py`` ``StateRouter`` and the head of
``DroplessMoE``)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.router")
