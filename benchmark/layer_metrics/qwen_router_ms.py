"""Device time of one step in the expert layers' router, forward and
backward, all layers: operations under the model's ``moe.router`` scope
(``models/moe.py`` ``DroplessMoE``), which for this model holds the 512-wide
float32 product at the highest precision (six bf16 passes), the softmax over
512, ``lax.top_k`` of 10 in 512 and the renormalisation over the chosen."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.router")
