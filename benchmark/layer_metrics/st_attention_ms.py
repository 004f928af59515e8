"""Device time of one step in SmallThinker's grouped-attention sub-layers,
forward and backward, all four layers: operations of ``jit_step`` under the
model's ``attention`` scope (``models/transformer.py`` ``DecoderBlock``
around ``GroupedAttention``; inside it ``attn.project``: the query, key and
value projections and, in the three window layers, the rotary turn over the
whole head; ``attn.window`` or ``attn.full``: scores, softmax and context,
on the chip the fused kernel over 28 query heads on 4 key/value heads of 128
at a 16,384-token row; ``attn.out``: the output projection). The router's
product, which this model makes ahead of attention, is under ``moe.router``
and not here."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "attention")
