"""Device time of one step in granite-4.0-h-micro's attention sub-layer,
forward and backward, the one attention layer of the ten held: operations of
``jit_step`` under the model's ``attention`` scope (``models/transformer.py``
``DecoderBlock`` around ``GroupedAttention``; inside it ``attn.project``: the
query, key and value projections and the score scale folded into the
queries; ``attn.full``: scores, softmax and context, on the chip the fused
kernel over 32 query heads on 8 key/value heads of 64 at an 8,192-token row;
``attn.out``: the output projection)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "attention")
