"""Device time of one step in a Mamba mixer outside its scan, forward and
backward, all Mamba layers: operations of ``jit_step`` under the model's
scopes ``ssm.project`` (the four products: in, x, dt, out, with the
softplus), ``ssm.conv`` (the depthwise causal convolution and its SiLU) and
``ssm.gate`` (the skip and the gate)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "ssm.project", "ssm.conv",
                                    "ssm.gate")
