"""Device time of one step in Laguna's gated grouped-attention sub-layers,
forward and backward (and ``--remat``'s second forward), all five layers:
operations of ``jit_step`` under the model's ``attention`` scope
(``models/transformer.py`` ``DecoderBlock`` around ``GroupedAttention``;
inside it ``attn.project``: the query, key and value projections and the
rotary turn, over the whole head in the three window layers of 72 query
heads and under YaRN's table over half of it in the two full layers of 48;
``attn.window`` or ``attn.full``: scores, softmax and context, on the chip
the fused kernel over 8 key/value heads of 128 at an 8,192-token row;
``attn.gate``: the gate a head; ``attn.out``: the output projection)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "attention")
