"""Device time of one step's backward pass: ``XLA Ops`` of ``jit_step``
whose scope has ``transpose(`` (``transpose(jvp(forward))``). XLA fuses most
of an SGD or AdamW update into the weight-gradient fusions, which keep the
backward's name: see ``step_opt_ms``."""

from reduce import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "backward")
