"""Operations of one Laguna-S-2.1 training step on rank 0 of an
expert-parallel 32 and the first pipeline stage, from shapes, and the
operations and bytes of its kernels: attention under each of its two masks
and the held experts' grouped products.

Per token, forward, in multiply-adds. Every layer's mixer by its own number
of query heads N (48 in a full layer, 72 in a window one, ``heads_held``):
the query and output projections (2 h N 128), keys and values (2 h 8 128) and
the gate a head (h N): 44.2 M full, 63.1 M window. Layer 0's dense SwiGLU
(3 h 12,288 = 113.2 M). Every sparse layer's router (h 256), shared SwiGLU
(3 h 1,024) and routed assignments where they land on an expert held here,
in expectation at even routing: 10 x 8 / 256 = 0.3125 a token, 3 h 1,024
each. Once, the head over the vocabulary slice (h V). Per row, the scores and
the context of a layer's N heads (128 + 128) over the query-key pairs its
mask lets through: the causal half for a full layer (the triangle:
33,558,528 of an 8,192-token row), the band for a window layer (a query sees
itself and the 511 keys before it: 4,063,488). Two operations a
multiply-add; norms, the rotary turns, softmax, the sigmoids, the sort and
the loss are left out. Backward is twice forward; nothing recomputed is
counted (``--remat``'s second forward, the backward kernels' scores, the
expert layer's grouped products made again in the backward pass).
"""

from __future__ import annotations


def _pairs(seq: int, window: int) -> float:
    """Query-key pairs a causal mask lets through in one row, a head."""
    if not window or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + (seq - window) * window


_MASKS = {"full": "F", "window": "W"}


def _held(model: dict, mask: str) -> list:
    """``(mask, query heads)`` of the held layers that ``mask`` names
    (``"F"`` full, ``"W"`` window, or both), one a layer."""
    return [(_MASKS[kind], int(heads)) for kind, heads in zip(
        model["layer_kinds_held"], model["heads_held"])
        if _MASKS[kind] in mask.split()]


def _head_pairs(model: dict, seq: int, mask: str = "F W") -> float:
    """Pairs a row, summed over the heads of the held layers of ``mask``."""
    window = int(model["sliding_window"])
    return sum(heads * _pairs(seq, window if m == "W" else 0)
               for m, heads in _held(model, mask))


def forward_flops(model: dict, rows: int, seq: int) -> float:
    h, d = int(model["hidden_size"]), int(model["head_dim"])
    groups = int(model["num_key_value_heads"])
    experts = int(model["router_experts"])
    mixers = sum(2 * h * heads * d + 2 * h * groups * d + h * heads
                 for _, heads in _held(model, "F W"))
    sparse = model["mlp_kinds_held"].count("sparse")
    dense = model["mlp_kinds_held"].count("dense")
    per_token = (
        mixers + dense * 3 * h * int(model["intermediate_size"])
        + sparse * (h * experts
                    + 3 * h * int(model["shared_expert_intermediate_size"])
                    + int(model["num_experts_per_tok"])
                    * int(model["num_experts"]) / experts
                    * 3 * h * int(model["moe_intermediate_size"]))
        + h * int(model["vocab_size"]))
    return 2.0 * rows * (per_token * seq + 2 * d * _head_pairs(model, seq))


def step_flops(model: dict, leaf_shapes: dict) -> float:
    rows, seq = leaf_shapes["input_ids"][:2]
    return 3.0 * forward_flops(model, int(rows), int(seq))


def attention_flops(model: dict, rows: int, seq: int, mask: str) -> float:
    """The attention kernels of one step in the layers of ``mask`` (``"F"``
    or ``"W"``), forward and backward, over the pairs the mask lets through
    (the band counted as a band, the triangle as a triangle): scores and
    context forward, and backward the products that give dV, dP, dQ and dK
    (six products of d multiply-adds a pair; the backward kernels'
    recomputation of the scores and ``--remat``'s second forward are not
    counted)."""
    d = int(model["head_dim"])
    return rows * _head_pairs(model, seq, mask) * 3 * 2 * d * 2.0


def attention_bytes(model: dict, rows: int, seq: int, mask: str) -> float:
    """What those kernels read and write at least, in bf16: q, k, v in and o
    out forward; q, k, v, o, dO in and dQ, dK, dV out backward; keys and
    values in their own eight heads (the call repeats them nine times for a
    window layer's query heads and six times for a full one's, which counts
    against the share)."""
    d, groups = int(model["head_dim"]), int(model["num_key_value_heads"])
    return sum(rows * seq * d * 2.0
               * (2 * heads + 2 * groups + 3 * heads + 2 * groups
                  + heads + 2 * groups)
               for _, heads in _held(model, mask))


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
    matrix and writes the third. With 8 groups of about 320 rows against
    3 x 3,072 x 1,024 matrices the bytes bound it, not the operations."""
    h, f = int(model["hidden_size"]), int(model["moe_intermediate_size"])
    return 9 * 2.0 * (assignments * (h + f)
                      + model["mlp_kinds_held"].count("sparse")
                      * int(model["num_experts"]) * h * f)


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    seq = int(config["task"]["seq_len"])
    return {"input_ids": np.zeros((rows, seq), np.int32),
            "attention_mask": np.ones((rows, seq), np.int8)}
