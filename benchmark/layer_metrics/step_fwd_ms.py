"""Device time of one step's forward pass, the loss with it: ``XLA Ops`` of
``jit_step`` whose scope (the ``op_name`` that ``jax.named_scope`` in
``trainer.make_train_step`` writes) has ``forward`` or ``loss`` and no
``transpose(``; per run of the program, weighted over compiled shapes as
``step_device_ms`` is. Read from the trace file by ``reduce/scopes.py``."""

from reduce import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "forward")
