"""Operations of one Moonlight training step on one rank of an
expert-parallel job, from shapes, and the operations and bytes of its two
kernels: the held experts' grouped products and the attention kernel.

Per token, forward, in multiply-adds. Every layer: latent attention's four
projections (h x 16 x 192, h x 576, 512 x 16 x 256, 16 x 128 x h: 13.76 M)
and its scores and context over the keys a causal kernel computes, half of
them (16 x (192 + 128) x S / 2). The leading dense layers: a SwiGLU of
``intermediate_size`` (3 h f). Every other layer: the router (64 h), the
shared expert (3 h x 2 x 1,408) and the routed assignments that land on the
experts held here, in expectation at even routing: k x held / 64 = 0.75 a
token a layer, 3 h x 1,408 each. Once, the untied head over the vocabulary
slice (h V). Two operations a multiply-add; norms, rotary turns, softmax,
SiLU, sigmoid, the sort and the loss are left out. Backward is twice
forward; nothing recomputed is counted (the expert layer recomputes its
grouped products in the backward pass, and ``--remat`` a whole block).
"""

from __future__ import annotations


def _attention(model: dict):
    heads = int(model["num_attention_heads"])
    qk = int(model["qk_nope_head_dim"]) + int(model["qk_rope_head_dim"])
    return heads, qk, int(model["v_head_dim"])


def forward_flops(model: dict, rows: int, seq: int) -> float:
    h = int(model["hidden_size"])
    heads, qk, v = _attention(model)
    rank, rope = int(model["kv_lora_rank"]), int(model["qk_rope_head_dim"])
    attention = (h * heads * qk + h * (rank + rope)
                 + rank * heads * (qk - rope + v) + heads * v * h
                 + heads * (qk + v) * seq / 2)
    f = int(model["moe_intermediate_size"])
    held_share = int(model["n_routed_experts"]) / int(model["router_experts"])
    sparse = (h * int(model["router_experts"])
              + 3 * h * f * int(model["n_shared_experts"])
              + int(model["num_experts_per_tok"]) * held_share * 3 * h * f)
    layers = int(model["num_hidden_layers"])
    dense_layers = min(int(model["first_k_dense_replace"]), layers)
    per_token = (layers * attention
                 + dense_layers * 3 * h * int(model["intermediate_size"])
                 + (layers - dense_layers) * sparse
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
    layers = int(model["num_hidden_layers"]) - int(
        model["first_k_dense_replace"])
    return 9 * 2.0 * (assignments * (h + f)
                      + layers * int(model["n_routed_experts"]) * h * f)


def attention_flops(model: dict, rows: int, seq: int) -> float:
    """The attention kernels of one step, forward and backward, all layers,
    over the causal half of the pairs: scores and context forward, and
    backward the products that give dV, dP, dQ and dK (the backward
    kernels' recomputation of the scores is not counted)."""
    heads, qk, v = _attention(model)
    return (int(model["num_hidden_layers"]) * rows * heads * seq * seq / 2
            * 3 * (qk + v) * 2.0)


def attention_bytes(model: dict, rows: int, seq: int) -> float:
    """What the kernels read and write at least, in bf16: q, k, v in and o
    out forward; q, k, v, o, dO in and dQ, dK, dV out backward."""
    heads, qk, v = _attention(model)
    return (int(model["num_hidden_layers"]) * rows * heads * seq * 2.0
            * (2 * qk + 2 * v + 2 * qk + 3 * v + 2 * qk + v))


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    seq = int(config["task"]["seq_len"])
    return {"input_ids": np.zeros((rows, seq), np.int32),
            "attention_mask": np.ones((rows, seq), np.int8)}
