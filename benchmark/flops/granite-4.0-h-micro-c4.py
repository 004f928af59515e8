"""Operations of one training step of the held granite-4.0-h-micro layers,
from shapes, and the operations and bytes of two kernels: the state-space
dual of the Mamba-2 layers and the attention layer's scores and context.

Per token, forward, in multiply-adds. Every layer: a SwiGLU of
``shared_intermediate_size`` (3 h f: 50.3 M). A Mamba-2 layer
(``layer_kinds_held`` ``"mamba"``): the input projection (h x (2 d_i + 2 N +
heads): 17.4 M) and the output projection (d_i x h: 8.4 M), and the dual form
itself at the published chunk (``mamba_chunk_size`` L = 256), whatever chunk
the program runs: ``c b'`` over the causal half of a chunk (N L / 2, once
for all heads), the masked scores applied to ``x`` (d_i L / 2), a chunk's
state (d_i N) and its read-out (d_i N): 1.59 M. The attention layer: the
four projections (2 h x heads x d + 2 h x kv heads x d: 10.5 M) and, per row,
the scores and the context of 32 heads (64 + 64) over the causal half of the
pairs. Once, the tied head over the vocabulary slice (h V). Two operations a
multiply-add; norms, the convolution, softmax, SiLU, softplus, the
exponentials, the multipliers and the loss are left out. Backward is twice
forward. Under ``--remat``, which the cell passes, every block's forward
runs a second time inside the backward pass, and the dual form's a third
time inside its own: that recomputation is NOT counted as useful work, so
``step_mfu_pct`` and the kernels' roofline shares read what the step does
for the model, not what the chip executes.
"""

from __future__ import annotations


def _sizes(model: dict) -> tuple:
    h = int(model["hidden_size"])
    return (h, int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]),
            h // int(model["num_attention_heads"]),
            int(model["mamba_n_heads"]) * int(model["mamba_d_head"]),
            int(model["mamba_d_state"]), int(model["mamba_n_heads"]))


def _count(model: dict, kind: str) -> int:
    return sum(1 for k in model["layer_kinds_held"] if k == kind)


def _ssd_per_token(model: dict) -> float:
    """The dual form's multiply-adds a token and layer, forward."""
    _, _, _, _, inner, states, _ = _sizes(model)
    half = int(model["mamba_chunk_size"]) / 2
    return states * half + inner * half + 2 * inner * states


def _pairs(seq: int) -> float:
    """Query-key pairs the causal mask lets through in one row, a head."""
    return seq * (seq + 1) / 2


def forward_flops(model: dict, rows: int, seq: int) -> float:
    h, heads, kv, d, inner, states, ssm_heads = _sizes(model)
    layers = len(model["layer_kinds_held"])
    mamba = (h * (2 * inner + 2 * states + ssm_heads) + inner * h
             + _ssd_per_token(model))
    per_token = (layers * 3 * h * int(model["shared_intermediate_size"])
                 + _count(model, "mamba") * mamba
                 + _count(model, "attention") * (2 * h * heads * d
                                                 + 2 * h * kv * d)
                 + h * int(model["vocab_size"]))
    pairs = _count(model, "attention") * _pairs(seq)
    return 2.0 * rows * (per_token * seq + heads * 2 * d * pairs)


def step_flops(model: dict, leaf_shapes: dict) -> float:
    rows, seq = leaf_shapes["input_ids"][:2]
    return 3.0 * forward_flops(model, int(rows), int(seq))


def ssd_flops(model: dict, rows: int, seq: int) -> float:
    """The dual form of one step, forward and backward, all Mamba-2 layers:
    the count above, twice that backward (nothing recomputed is counted)."""
    return _count(model, "mamba") * rows * seq * _ssd_per_token(model) \
        * 3 * 2.0


def ssd_bytes(model: dict, rows: int, seq: int) -> float:
    """What the dual form reads and writes at least: forward x, B and C
    (bf16) and dt (f32) in and y (bf16) out; backward the same four and y's
    gradient in, the gradients of x, B, C and dt out."""
    _, _, _, _, inner, states, ssm_heads = _sizes(model)
    operands = inner * 2 + 2 * states * 2 + ssm_heads * 4
    return _count(model, "mamba") * rows * seq * (
        operands + inner * 2 + operands + inner * 2 + operands)


def attn_flops(model: dict, rows: int, seq: int) -> float:
    """The attention kernels of one step, forward and backward, over the
    causal half of the pairs: scores and context forward, and backward the
    products that give dV, dP, dQ and dK (six products of d multiply-adds a
    pair; the backward kernels' recomputation of the scores is not
    counted)."""
    _, heads, _, d, _, _, _ = _sizes(model)
    return (_count(model, "attention") * rows * heads * _pairs(seq)
            * 3 * 2 * d * 2.0)


def attn_bytes(model: dict, rows: int, seq: int) -> float:
    """What those kernels read and write at least, in bf16, with keys and
    values in the 32 heads the kernel is handed: q, k, v in and o out
    forward; q, k, v, o, dO in and dQ, dK, dV out backward."""
    _, heads, _, d, _, _, _ = _sizes(model)
    return _count(model, "attention") * rows * heads * seq * d * 2.0 * (
        4 + 5 + 3)


def example_batch(config: dict, rows: int) -> dict:
    """A batch of zeros in the shapes the task takes (for ``rehearse.py``)."""
    import numpy as np

    seq = int(config["task"]["seq_len"])
    return {"input_ids": np.zeros((rows, seq), np.int32),
            "attention_mask": np.ones((rows, seq), np.int8)}
