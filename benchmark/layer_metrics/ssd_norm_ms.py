"""Device time of one step in the Mamba-2 layers' gated norm, forward and
backward, all nine layers: operations of ``jit_step`` under the model's
``ssd.norm`` scope (``models/transformer.py`` ``Mamba2Mixer``:
``ops/norm.py`` ``gate_then_rms_norm``, the SiLU gate and then one RMSNorm
over all 4,096 columns, the plain ``jax.numpy`` lines in XLA)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "ssd.norm")
