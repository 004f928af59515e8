"""Device time of one step in the expert layers' router, forward and
backward, all layers: operations under the model's ``moe.router`` scope,
which for this model opens twice a layer: in ``DecoderBlock`` ahead of
``ln_attn`` and attention, around the 64-wide float32 product of the layer's
input at the highest precision (six bf16 passes), and in ``DroplessMoE``
around the softmax over 64, ``lax.top_k`` of 6 in 64 and the renormalisation
over the chosen."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.router")
