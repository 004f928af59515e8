"""Operations of one ZAYA1 training step on one rank of an expert-parallel
pair, from shapes, and the operations and bytes of its two kernels: the held
experts' grouped products and the attention kernel.

Per token, forward, in multiply-adds. Every layer: compressed convolutional
attention's four projections (h x 8 x 128, twice h x 2 x 128, 8 x 128 x h:
5.24 M), the convolutions within a head on queries and keys (2 taps x (8 + 2)
heads x 128 x 128: 0.33 M), its scores and context over the keys a causal
kernel computes, half of them (8 x (128 + 128) x S / 2); the router (h x 256,
twice 256 x 256, 256 x 16: 0.66 M); and the one routed assignment where it
lands on an expert held here, in expectation at even routing: held / 16 = 0.5
a token a layer, 3 h x 2,048 each. Once, the tied head over the vocabulary
slice (h V). Two operations a multiply-add; norms, the depthwise convolutions,
the q-k mean, the rotary turns, softmax, GELU, SiLU, the residual scales, the
sort and the loss are left out. Backward is twice forward; nothing recomputed
is counted (the expert layer recomputes its grouped products in the backward
pass).
"""

from __future__ import annotations


def _attention(model: dict):
    return (int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), int(model["head_dim"]))


def forward_flops(model: dict, rows: int, seq: int) -> float:
    h = int(model["hidden_size"])
    heads, groups, d = _attention(model)
    attention = (2 * h * heads * d + 2 * h * groups * d
                 + 2 * (heads + groups) * d * d
                 + heads * 2 * d * seq / 2)
    f = int(model["moe_intermediate_size"])
    r = int(model["router_hidden_size"])
    experts = int(model["router_experts"])
    sparse = (h * r + 2 * r * r + r * experts
              + int(model["num_experts_per_tok"])
              * int(model["num_experts"]) / experts * 3 * h * f)
    per_token = (int(model["num_hidden_layers"]) * (attention + sparse)
                 + h * int(model["vocab_size"]))
    return 2.0 * per_token * rows * seq


def step_flops(model: dict, leaf_shapes: dict) -> float:
    rows, seq = leaf_shapes["input_ids"][:2]
    return 3.0 * forward_flops(model, int(rows), int(seq))


def expert_flops(model: dict, assignments: float) -> float:
    """The held experts' grouped products of one step, forward and backward:
    three forward and six backward products of 2 h f operations a sorted row
    in a group; ``assignments`` is the step's rows in groups, all layers
    together (the program's ``moe_local_assignments_total``)."""
    return 9 * 2.0 * assignments * int(model["hidden_size"]) * int(
        model["moe_intermediate_size"])


def expert_bytes(model: dict, assignments: float) -> float:
    """What those nine products have to read and write at least, in bf16:
    each takes two of rows-by-h, rows-by-f and a layer's held-by-h-by-f
    matrix and writes the third."""
    h, f = int(model["hidden_size"]), int(model["moe_intermediate_size"])
    return 9 * 2.0 * (assignments * (h + f) + int(model["num_hidden_layers"])
                      * int(model["num_experts"]) * h * f)


def attention_flops(model: dict, rows: int, seq: int) -> float:
    """The attention kernels of one step, forward and backward, all layers,
    over the causal half of the pairs: scores and context forward, and
    backward the products that give dV, dP, dQ and dK (the backward
    kernels' recomputation of the scores is not counted)."""
    heads, _, d = _attention(model)
    return (int(model["num_hidden_layers"]) * rows * heads * seq * seq / 2
            * 3 * 2 * d * 2.0)


def attention_bytes(model: dict, rows: int, seq: int) -> float:
    """What the kernels read and write at least, in bf16: q, k, v in and o
    out forward; q, k, v, o, dO in and dQ, dK, dV out backward; keys and
    values in their own two heads (the call repeats them for the query heads
    of their group, which counts against the share)."""
    heads, groups, d = _attention(model)
    return (int(model["num_hidden_layers"]) * rows * seq * d * 2.0
            * (2 * heads + 2 * groups + 3 * heads + 2 * groups
               + heads + 2 * groups))


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    seq = int(config["task"]["seq_len"])
    return {"input_ids": np.zeros((rows, seq), np.int32),
            "attention_mask": np.ones((rows, seq), np.int8)}
