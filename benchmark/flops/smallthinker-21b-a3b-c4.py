"""Operations of one SmallThinker training step on one rank of an
expert-parallel four, from shapes, and the operations and bytes of its
kernels: attention under each of its two masks and the held experts' grouped
products.

Per token, forward, in multiply-adds. Every layer: the four projections of
grouped attention (h x 28 x 128 for the queries, twice h x 4 x 128 for keys
and values, 28 x 128 x h back: 21.0 M) and the router (h x 64). Every layer's
routed assignments where they land on an expert held here, in expectation at
even routing: 6 x 16 / 64 = 1.5 a token, 3 h x 768 each. Once, the head over
the vocabulary slice (h V). Per row, the scores and the context of 28 heads
(128 + 128) over the query-key pairs a layer's mask lets through: the causal
half for a full layer (N: 134,225,920 of a 16,384-token row), the band for a
window layer (W: a query sees itself and the 4,095 keys before it, 58,722,304).
Two operations a multiply-add; norms, the rotary turns, softmax, ReLU, the
sort and the loss are left out. Backward is twice forward; nothing recomputed
is counted (the backward kernels' scores, the expert layer's grouped
products made again in the backward pass).
"""

from __future__ import annotations


def _sizes(model: dict) -> tuple:
    return (int(model["hidden_size"]), int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), int(model["head_dim"]))


def _pairs(seq: int, window: int) -> float:
    """Query-key pairs a causal mask lets through in one row, a head."""
    if not window or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + (seq - window) * window


_MASKS = {"full_no_position": "N", "window_rotary": "W"}


def _held(model: dict, mask: str) -> list:
    """The held layers' masks that ``mask`` names (``"N"`` full, ``"W"``
    window, or both), one a layer."""
    return [_MASKS[k] for k in model["layer_kinds_held"]
            if _MASKS[k] in mask.split()]


def _layer_pairs(model: dict, seq: int, mask: str = "N W") -> float:
    """Pairs a row and head, summed over the held layers of ``mask``."""
    window = int(model["sliding_window_size"])
    return sum(_pairs(seq, window if m == "W" else 0)
               for m in _held(model, mask))


def forward_flops(model: dict, rows: int, seq: int) -> float:
    h, heads, groups, d = _sizes(model)
    layers = len(model["layer_kinds_held"])
    experts = int(model["router_experts"])
    per_token = (layers * (2 * h * heads * d + 2 * h * groups * d
                           + h * experts
                           + int(model["moe_num_active_primary_experts"])
                           * int(model["moe_num_primary_experts"]) / experts
                           * 3 * h * int(model["moe_ffn_hidden_size"]))
                 + h * int(model["vocab_size"]))
    return 2.0 * rows * (per_token * seq
                         + heads * 2 * d * _layer_pairs(model, seq))


def step_flops(model: dict, leaf_shapes: dict) -> float:
    rows, seq = leaf_shapes["input_ids"][:2]
    return 3.0 * forward_flops(model, int(rows), int(seq))


def attention_flops(model: dict, rows: int, seq: int, mask: str) -> float:
    """The attention kernels of one step in the layers of ``mask`` (``"N"``
    or ``"W"``), forward and backward, over the pairs the mask lets through:
    scores and context forward, and backward the products that give dV, dP,
    dQ and dK (six products of d multiply-adds a pair; the backward kernels'
    recomputation of the scores is not counted)."""
    _, heads, _, d = _sizes(model)
    return rows * heads * _layer_pairs(model, seq, mask) * 3 * 2 * d * 2.0


def attention_bytes(model: dict, rows: int, seq: int, mask: str) -> float:
    """What those kernels read and write at least, in bf16: q, k, v in and o
    out forward; q, k, v, o, dO in and dQ, dK, dV out backward; keys and
    values in their own four heads (the call repeats them seven times for
    the query heads of their group, which counts against the share)."""
    _, heads, groups, d = _sizes(model)
    return (len(_held(model, mask)) * rows * seq * d * 2.0
            * (2 * heads + 2 * groups + 3 * heads + 2 * groups
               + heads + 2 * groups))


def expert_flops(model: dict, assignments: float) -> float:
    """The held experts' grouped products of one step, forward and backward:
    three forward and six backward products of 2 h f operations a sorted row
    in a group; ``assignments`` is the step's rows in groups, all layers
    together (the program's ``moe_local_assignments_total``)."""
    return 9 * 2.0 * assignments * int(model["hidden_size"]) * int(
        model["moe_ffn_hidden_size"])


def expert_bytes(model: dict, assignments: float) -> float:
    """What those nine products have to read and write at least, in bf16:
    each takes two of rows-by-h, rows-by-f and a layer's held-by-h-by-f
    matrix and writes the third. With 16 groups of about 1,536 rows against
    3 x 2,560 x 768 matrices the operations bound it, not the bytes."""
    h, f = int(model["hidden_size"]), int(model["moe_ffn_hidden_size"])
    return 9 * 2.0 * (assignments * (h + f) + len(model["layer_kinds_held"])
                      * int(model["moe_num_primary_experts"]) * h * f)


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    seq = int(config["task"]["seq_len"])
    return {"input_ids": np.zeros((rows, seq), np.int32),
            "attention_mask": np.ones((rows, seq), np.int8)}
