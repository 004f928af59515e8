"""Operations of one ResNet-50 training step, from shapes.

Counts the multiply-adds of every convolution and of the classifier, two
operations each, walking the same stages as the plain reference; batch norm,
ReLU, pooling and the loss are left out (under 1% of the total). The backward
pass costs twice the forward (one product for the input's gradient, one for
the weight's), so a step is three forwards; nothing is recomputed.
"""

from __future__ import annotations

STAGES = (3, 4, 6, 3)


def forward_flops(model: dict, batch: int) -> float:
    size, classes = int(model["image_size"]), int(model["num_classes"])
    width = int(model["num_filters"])
    macs = 0

    def conv(hw_out, k, cin, cout):
        return hw_out * hw_out * k * k * cin * cout

    hw = size // 2
    macs += conv(hw, 7, 3, width)
    hw //= 2  # max pool
    cin = width
    for stage, count in enumerate(STAGES):
        mid = width * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            out_hw = hw // stride
            macs += conv(hw, 1, cin, mid)
            macs += conv(out_hw, 3, mid, mid)
            macs += conv(out_hw, 1, mid, mid * 4)
            if j == 0:
                macs += conv(out_hw, 1, cin, mid * 4)
            cin, hw = mid * 4, out_hw
    macs += cin * classes
    return 2.0 * macs * batch


def step_flops(model: dict, leaf_shapes: dict) -> float:
    """``leaf_shapes``: the shapes of one step's batch leaves."""
    return 3.0 * forward_flops(model, int(leaf_shapes["image"][0]))


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    size = int(config["task"]["image_size"])
    return {"image": np.zeros((rows, size, size, 3), np.uint8),
            "label": np.zeros((rows,), np.int32)}
