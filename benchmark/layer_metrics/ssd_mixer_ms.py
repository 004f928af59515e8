"""Device time of one step in the Mamba-2 mixers, forward and backward (and
the recomputed forwards: the block's under ``--remat`` and the dual's
inside its own backward pass), all nine state-space layers: operations of
``jit_step`` under the model's ``state_space`` scope (``models/transformer.py``
``DecoderBlock`` around ``Mamba2Mixer``; inside it ``ssd.project``: the fused
input projection, the 64-wide one of ``dt`` and the output projection,
``ssd.conv``: the 4-tap depthwise convolution, its bias and its SiLU,
``ssd.kernel``: the state-space dual and nothing else, ``ssd.norm``: the
gate and the one RMSNorm over all 4,096 columns)."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "state_space")
