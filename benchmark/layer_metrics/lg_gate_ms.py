"""Device time of one step in Laguna's gate a head, forward and backward,
all five layers: operations of ``jit_step`` under the model's ``attn.gate``
scope (``models/transformer.py`` ``GroupedAttention`` under ``head_gate``:
the [3,072, heads] product of the mixer's normed input, the sigmoid in
float32 and the broadcast product with the attention output between the
kernel and the output projection: elementwise over [8,192, heads, 128])."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "attn.gate")
