"""Device time of one step in the gated softmax-attention sub-layer, forward
and backward, all full-attention layers: operations of ``jit_step`` under the
model's ``attention`` scope (``models/transformer.py`` ``DecoderBlock``;
inside it ``attn.project``: the query-and-gate, key, value and output
projections, the per-head norms and the rotary turn of a quarter of a head,
``attn.kernel``: scores, softmax and context, on the chip the fused kernel
over 16 query heads on 2 key/value heads of 256, and ``attn.gate``: the
output times ``sigmoid(gate)``)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "attention")
