"""Device time of one step in the vocabulary projection and the loss over
it, forward and backward: operations of ``jit_step`` under the model's
``lm_head`` scope and the step's ``loss`` scope. With one layer the head is
over half of the step's matrix operations (PERF.md section 4)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "lm_head", "loss")
