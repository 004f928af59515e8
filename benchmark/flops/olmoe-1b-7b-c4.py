"""Operations of one OLMoE training step, from shapes, and the operations and
bytes of its grouped expert products.

Per token, forward, in multiply-adds: each layer's four attention projections
(4 h^2), attention's scores and context (2 S h where every key is computed
for every query, the dense path; S h where a causal kernel skips the upper
triangle: ``model["attention"]`` says which path the cell runs), the router
(E h), the three products of each of the token's k experts (k x 3 h f); once,
the untied head (h V). Two operations a multiply-add; norms, rotary turns,
softmax, SiLU, the sort and the loss are left out. Backward is twice forward;
nothing is recomputed.
"""

from __future__ import annotations


def _sizes(model: dict):
    return (int(model["hidden_size"]), int(model["intermediate_size"]),
            int(model["num_experts"]), int(model["num_experts_per_tok"]))


def forward_flops(model: dict, rows: int, seq: int) -> float:
    h, f, e, k = _sizes(model)
    attended = seq if model.get("attention", "dense") == "dense" else seq / 2
    layer = 4 * h * h + 2 * attended * h + e * h + k * 3 * h * f
    per_token = int(model["num_hidden_layers"]) * layer \
        + h * int(model["vocab_size"])
    return 2.0 * per_token * rows * seq


def step_flops(model: dict, leaf_shapes: dict) -> float:
    rows, seq = leaf_shapes["input_ids"][:2]
    return 3.0 * forward_flops(model, int(rows), int(seq))


def expert_flops(model: dict, tokens: int) -> float:
    """The grouped products of one step, forward and backward, all layers:
    three forward and six backward products of 2 N h f operations each over
    the N = tokens x k sorted rows."""
    h, f, _, k = _sizes(model)
    return int(model["num_hidden_layers"]) * 9 * 2.0 * tokens * k * h * f


def expert_bytes(model: dict, tokens: int) -> float:
    """What those nine products have to read and write at least, in bf16:
    each takes two of rows-by-h, rows-by-f and the experts' E-by-h-by-f
    matrix and writes the third."""
    h, f, e, k = _sizes(model)
    n = tokens * k
    return int(model["num_hidden_layers"]) * 9 * 2.0 * (
        n * h + n * f + e * h * f)


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    seq = int(config["task"]["seq_len"])
    return {"input_ids": np.zeros((rows, seq), np.int32),
            "attention_mask": np.ones((rows, seq), np.int8)}
