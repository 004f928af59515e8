"""Device time of one step in the gated delta rule, forward and backward, all
linear-attention layers: operations under the model's ``gdn.kernel`` scope
(``models/transformer.py`` ``GatedDeltaNet``: ``ops/delta.py``
``gated_delta_rule`` and nothing else, whichever form runs: the chunks'
preparation in XLA (the decay masks, the triangular inverse, ``W``, ``U`` and
the two score matrices), the sequential part over a row's chunks, and the
backward pass's second forward of both, a group of heads at a time)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "gdn.kernel")
