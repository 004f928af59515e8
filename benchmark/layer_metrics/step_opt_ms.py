"""Device time of one step under the ``optimizer`` scope
(``apply_gradients`` and the ``batch_stats`` replace). A fusion carries the
``op_name`` of one of its instructions, so an update that XLA fused into a
weight-gradient fusion is counted under ``step_bwd_ms``; on four chips the
gradient all-reduce has no scope of its own and is counted where XLA's
metadata puts it (PERF.md section 5 says where)."""

from reduce import scopes


def read(ctx):
    return scopes.per_step_ms(ctx, "optimizer")
