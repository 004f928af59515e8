"""Operations of one BERT-base masked-LM training step, from shapes.

Per token and layer: the four attention projections (4 h^2), the two
feed-forward products (2 h f), and attention's scores and context over the
row's full length (2 S h; the dense path computes every key for every query,
masked or not). The head projects every position onto the vocabulary (h V),
because the program computes logits for all positions, not only the masked
15%. Two operations per multiply-add; layer norms, softmax, GELU and the
loss are left out (about 1%). Backward is twice forward; nothing is
recomputed. Padding tokens of a packed grid are computed like any other, so
the count is per grid token; ``step_mfu_pct`` therefore says how busy the
matrix unit is, and ``pad_waste_pct`` says how much of that was padding.
"""

from __future__ import annotations


def forward_flops(model: dict, rows: int, seq: int) -> float:
    h, f = int(model["hidden_size"]), int(model["intermediate_size"])
    layers, vocab = int(model["num_hidden_layers"]), int(model["vocab_size"])
    per_token = layers * (4 * h * h + 2 * h * f + 2 * seq * h) + h * vocab
    return 2.0 * per_token * rows * seq


def step_flops(model: dict, leaf_shapes: dict) -> float:
    rows, seq = leaf_shapes["input_ids"][:2]
    return 3.0 * forward_flops(model, int(rows), int(seq))


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    seq = int(config["task"]["seq_len"])
    return {"input_ids": np.zeros((rows, seq), np.int32),
            "attention_mask": np.ones((rows, seq), np.int8)}
