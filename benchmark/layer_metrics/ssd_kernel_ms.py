"""Device time of one step in the state-space dual, forward and backward, all
Mamba-2 layers: operations of ``jit_step`` under the model's ``ssd.kernel``
scope (``models/transformer.py`` ``Mamba2Mixer``: ``ops/ssd.py``
``ssd_packed`` and nothing else; on the chip the Pallas kernel pair
``ssd_fwd`` / ``ssd_bwd``, the running sums and the two layouts of the
per-token scalars for them, and the sums of ``db`` and ``dc`` over the head
blocks; off it the plain chunked form, a group of heads at a time). Under
``--remat`` the forward kernel runs a second time inside the backward pass,
and a third time inside the rule's own differentiation (the run that keeps
one state a chunk); all of it is in this time."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "ssd.kernel")
