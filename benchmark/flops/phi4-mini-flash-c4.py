"""Operations of one training step of the held Phi-4-mini-flash (SambaY)
layers, from shapes, and the operations and bytes of the three kernels: the
selective scan, window attention, and full and cross attention.

Per token, forward, in multiply-adds. Every layer: a SwiGLU of
``intermediate_size`` (3 h f). By kind (``layer_kinds``): M and M* the four
products of a Mamba mixer (h x 2 d_i, d_i x (dt_rank + 2 N), dt_rank x d_i,
d_i x h) and the scan's three multiply-adds a state (d_i N each: the decay, the
input, the read-out; the exponential is left out); G the unit's two products
(2 h d_i); S and F* the projections (2 h h for queries and output, 2 h x kv
heads x 64 for keys and values) and the scores and context of 40 heads (64 +
128) over the keys the mask lets a query see: the band for S (window keys, the
row's first window fewer), the causal half for F* and X; X queries and output
only. Once, the tied head over the vocabulary slice (h V). Two operations a
multiply-add; norms, the convolution, softmax, SiLU, softplus, lambda and the
loss are left out. Backward is twice forward. Under ``--remat``, which the
cell passes, every block's forward runs a second time inside the backward
pass: that recomputation is NOT counted as useful work, so ``step_mfu_pct``
and the kernels' roofline shares read what the step does for the model, not
what the chip executes.
"""

from __future__ import annotations


def _sizes(model: dict):
    h, heads = int(model["hidden_size"]), int(model["num_attention_heads"])
    return (h, heads, int(model["num_key_value_heads"]), h // heads,
            int(model["ssm_inner_size"]), int(model["ssm_states"]),
            int(model["ssm_dt_rank"]))


def _pairs(seq: int, window: int) -> float:
    """Query-key pairs a causal mask lets through in one row, per head."""
    if not window or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + (seq - window) * window


def _count(model: dict, kind: str) -> int:
    return sum(1 for k in model["layer_kinds"] if k in kind.split())


def forward_flops(model: dict, rows: int, seq: int) -> float:
    h, heads, kv, d, inner, states, rank = _sizes(model)
    kinds = model["layer_kinds"]
    mamba = (h * 2 * inner + inner * (rank + 2 * states) + rank * inner
             + inner * h + 3 * inner * states)
    per_token = (len(kinds) * 3 * h * int(model["intermediate_size"])
                 + _count(model, "M M*") * mamba
                 + _count(model, "G") * 2 * h * inner
                 + _count(model, "S F*") * (2 * h * h + 2 * h * kv * d)
                 + _count(model, "X") * 2 * h * h
                 + h * int(model["vocab_size"]))
    pairs = (_count(model, "S") * _pairs(seq, int(model["sliding_window"]))
             + _count(model, "F* X") * _pairs(seq, 0))
    return 2.0 * rows * (per_token * seq + heads * 3 * d * pairs)


def step_flops(model: dict, leaf_shapes: dict) -> float:
    rows, seq = leaf_shapes["input_ids"][:2]
    return 3.0 * forward_flops(model, int(rows), int(seq))


def scan_flops(model: dict, rows: int, seq: int) -> float:
    """The selective scans of one step, forward and backward, all Mamba
    layers: three multiply-adds a state and token forward, twice that
    backward (the backward kernel's recomputation of the states is not
    counted, nor the exponentials)."""
    _, _, _, _, inner, states, _ = _sizes(model)
    return _count(model, "M M*") * rows * seq * inner * states * 3 * 3 * 2.0


def scan_bytes(model: dict, rows: int, seq: int) -> float:
    """What the scans read and write at least: forward x (bf16), dt (f32),
    B and C (f32) in and the sums (bf16) out; backward x, dt, B, C and the
    sums' gradient in, the gradients of x, dt, B and C out."""
    _, _, _, _, inner, states, _ = _sizes(model)
    return _count(model, "M M*") * rows * seq * (
        inner * (2 + 4 + 2) + 2 * states * 4
        + inner * (2 + 4 + 2 + 2 + 4) + 4 * states * 4)


def attention_flops(model: dict, rows: int, seq: int, kinds: str) -> float:
    """The attention kernels of one step in the layers of ``kinds``, forward
    and backward, over the pairs their mask lets through: scores and context
    forward, and backward the products that give dV, dP, dQ and dK (the
    backward kernels' recomputation of the scores is not counted)."""
    _, heads, _, d, _, _, _ = _sizes(model)
    window = int(model["sliding_window"])
    pairs = sum(_pairs(seq, window if k == "S" else 0)
                for k in model["layer_kinds"] if k in kinds.split())
    return rows * heads * pairs * 3 * (d + 2 * d) * 2.0


def attention_bytes(model: dict, rows: int, seq: int, kinds: str) -> float:
    """What those kernels read and write at least, in bf16, with keys and
    values in the 40 heads the kernel is handed: q, k, v in and o out
    forward; q, k, v, o, dO in and dQ, dK, dV out backward."""
    _, heads, _, d, _, _, _ = _sizes(model)
    qk, v = d, 2 * d
    return (_count(model, kinds) * rows * heads * seq * 2.0
            * (2 * qk + 2 * v + 2 * qk + 3 * v + 2 * qk + v))


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    seq = int(config["task"]["seq_len"])
    return {"input_ids": np.zeros((rows, seq), np.int32),
            "attention_mask": np.ones((rows, seq), np.int8)}
