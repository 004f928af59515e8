"""Device time of one run of the pack transform (XLA module
``jit_pack_token_batch``) in the trace: the median of each compiled shape,
weighted by how often it ran."""

from reduce import xplane


def read(ctx):
    return xplane.module_ms(ctx["trace"], "jit_pack_token_batch")
