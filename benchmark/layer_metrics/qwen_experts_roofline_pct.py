"""Share of the chip's roofline that the held experts' grouped products reach
with 10 experts a token and a sixteenth of them held: 32 groups of 512-wide
experts of about 160 rows a row of 8,192 tokens. Read as
``moe_local_experts_roofline_pct`` reads Moonlight's (that file says how):
the least time the chip could take for them, from ``expert_flops`` and
``expert_bytes`` of ``benchmark/flops/<config>.py`` at the rows the program
counted in groups (the window's ``moe_local_assignments_total`` over its
steps), over the device time of ``moe.experts`` and the kernel XLA makes of
``ragged_dot``. Here the weights' bytes bound it, not the operations (nine
passes over 4 x 32 matrices of 2,048 x 512); the list's dead rows and the
backward pass's recomputation of the three forward products count against
the share."""

from layer_metrics.moe_local_experts_roofline_pct import read  # noqa: F401
