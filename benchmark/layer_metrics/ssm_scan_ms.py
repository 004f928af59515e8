"""Device time of one step in the selective scans, forward and backward, all
Mamba layers: operations of ``jit_step`` under the model's ``ssm.scan`` scope
(``models/transformer.py`` ``MambaMixer``: the scan and nothing else; on the
chip ``ops/scan.py``'s two kernels and the layout of ``b`` and ``c`` for
them). Under ``--remat`` the forward kernel runs a second time inside the
backward pass and is in this time too."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "ssm.scan")
