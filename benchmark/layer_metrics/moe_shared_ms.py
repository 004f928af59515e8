"""Device time of one step in the shared experts, forward and backward, all
expert layers: operations of ``jit_step`` under the scope ``moe.shared``
(``models/moe.py`` ``DroplessMoE``: one SwiGLU of ``n_shared_experts`` x
``moe_intermediate_size`` that every token passes, beside the routed
experts)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "moe.shared")
