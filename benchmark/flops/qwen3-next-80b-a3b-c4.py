"""Operations of one Qwen3-Next training step on one rank of an
expert-parallel sixteen, from shapes, and the operations and bytes of its
three kernels: the gated delta rule, the attention kernel and the held
experts' grouped products.

Per token, forward, in multiply-adds. A linear-attention layer: its three
projections (h x 12,288, h x 64, 4,096 x h: 33.7 M) and the rule at what no
chunking avoids, a value head reading its state by the key, reading it by the
query and updating it (3 x 128 x 128 x 32 heads: 1.6 M). The attention layer:
its four projections (h x 8,192 for queries and gate, twice h x 512, 4,096 x
h: 27.3 M) and its scores and context over the keys a causal kernel computes,
half of them (16 x (256 + 256) x S / 2). Every layer's expert sub-layer: the
router (h x 512), the shared expert (3 h x 512) and its gate (h), and the
routed assignments where they land on an expert held here, in expectation at
even routing: 10 x 32 / 512 = 0.625 a token, 3 h x 512 each. Once, the head
over the vocabulary slice (h V). Two operations a multiply-add; norms, the
depthwise convolution, the gates, the rotary turns, softmax, SiLU, the sort
and the loss are left out, and so are a chunk's own products (the triangular
inverse, the scores inside a chunk): a form of the rule that needs fewer
would count the same. Backward is twice forward; nothing recomputed is
counted (the rule's forward and the expert layer's grouped products are made
again in the backward pass).
"""

from __future__ import annotations


def _layers(model: dict) -> tuple:
    kinds = model["layer_kinds_held"]
    linear = sum(1 for k in kinds if k == "linear_attention")
    return linear, len(kinds) - linear


def _delta(model: dict) -> tuple:
    return (int(model["linear_num_key_heads"]),
            int(model["linear_num_value_heads"]),
            int(model["linear_key_head_dim"]),
            int(model["linear_value_head_dim"]))


def _attention(model: dict) -> tuple:
    return (int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), int(model["head_dim"]))


def forward_flops(model: dict, rows: int, seq: int) -> float:
    h = int(model["hidden_size"])
    linear_layers, full_layers = _layers(model)
    hk, hv, dk, dv = _delta(model)
    linear = (h * (2 * hk * dk + 2 * hv * dv) + h * 2 * hv + hv * dv * h
              + 3 * hv * dk * dv)
    heads, groups, d = _attention(model)
    full = (h * heads * 2 * d + 2 * h * groups * d + heads * d * h
            + heads * 2 * d * seq / 2)
    f = int(model["moe_intermediate_size"])
    experts = int(model["router_experts"])
    sparse = (h * experts
              + 3 * h * int(model["shared_expert_intermediate_size"]) + h
              + int(model["num_experts_per_tok"])
              * int(model["num_experts"]) / experts * 3 * h * f)
    per_token = (linear_layers * linear + full_layers * full
                 + (linear_layers + full_layers) * sparse
                 + h * int(model["vocab_size"]))
    return 2.0 * per_token * rows * seq


def step_flops(model: dict, leaf_shapes: dict) -> float:
    rows, seq = leaf_shapes["input_ids"][:2]
    return 3.0 * forward_flops(model, int(rows), int(seq))


def delta_flops(model: dict, rows: int, seq: int) -> float:
    """The gated delta rule of one step, forward and backward, all
    linear-attention layers, at what no chunking avoids: a token and value
    head reads the state by the key, reads it by the query and makes the
    rank-one update, 3 x d_k x d_v multiply-adds forward and twice that
    backward; nothing for a chunk's own products or for recomputation."""
    _, hv, dk, dv = _delta(model)
    return _layers(model)[0] * rows * seq * hv * 3 * dk * dv * 3 * 2.0


def delta_bytes(model: dict, rows: int, seq: int) -> float:
    """What the rule has to read and write at least: forward q, k (bf16, in
    their own key heads), v (bf16), g, beta (f32) in and o (bf16) out;
    backward those and o's cotangent in and the five gradients out."""
    hk, hv, dk, dv = _delta(model)
    inputs = 2 * (2 * hk * dk + hv * dv) + 2 * 4 * hv
    return _layers(model)[0] * rows * seq * (
        inputs + 2 * hv * dv + inputs + 2 * hv * dv + inputs)


def attention_flops(model: dict, rows: int, seq: int) -> float:
    """The attention kernels of one step, forward and backward, all full
    attention layers, over the causal half of the pairs: scores and context
    forward, and backward the products that give dV, dP, dQ and dK (the
    backward kernels' recomputation of the scores is not counted)."""
    heads, _, d = _attention(model)
    return (_layers(model)[1] * rows * heads * seq * seq / 2
            * 3 * 2 * d * 2.0)


def attention_bytes(model: dict, rows: int, seq: int) -> float:
    """What the kernels read and write at least, in bf16: q, k, v in and o
    out forward; q, k, v, o, dO in and dQ, dK, dV out backward; keys and
    values in their own two heads (the call repeats them for the query heads
    of their group, which counts against the share)."""
    heads, groups, d = _attention(model)
    return (_layers(model)[1] * rows * seq * d * 2.0
            * (2 * heads + 2 * groups + 3 * heads + 2 * groups
               + heads + 2 * groups))


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
    matrix and writes the third. With 32 groups of about 160 rows against
    3 x 2,048 x 512 matrices the weights' bytes bound it, not the
    operations."""
    h, f = int(model["hidden_size"]), int(model["moe_intermediate_size"])
    return 9 * 2.0 * (assignments * (h + f) + sum(_layers(model))
                      * int(model["num_experts"]) * h * f)


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    seq = int(config["task"]["seq_len"])
    return {"input_ids": np.zeros((rows, seq), np.int32),
            "attention_mask": np.ones((rows, seq), np.int8)}
