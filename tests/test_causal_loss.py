"""The causal-LM task's loss and metric (``models/tasks.py``
``_causal_lm_task``) against the shift by one token written out on the logits
themselves (``logits[:, :-1]``), which the task never does: it shifts the
targets and their weights and reads the ``[B, S, V]`` grid whole.
``tests/test_attention_choice.py`` holds what that buys in the compiled
program; ``tests/test_models.py`` ``TestCausalLM`` (the slow tier) the model
under the loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lance_distributed_training_tpu.models import get_task

SEQ, VOCAB = 16, 128
CASES = {
    # documents' lengths row by row, and what the last position's logits are
    # set to, if anything (the sliced form cuts them off)
    "padded tail": ([[11], [16]], None),  # unpacked: no segment ids
    "packed": ([[5, 7, 2], [9, 7]], None),  # junctions and a tail
    "last inf": ([[16], [6, 10]], np.inf),
    "last nan": ([[16], [6, 10]], np.nan),
    "all padding": ([[], []], None),  # the denominator's floor
}


def _batch(lengths, packed):
    gen = np.random.default_rng(36)
    mask = np.zeros((len(lengths), SEQ), np.int8)
    seg = np.zeros((len(lengths), SEQ), np.int32)
    for row, docs in enumerate(lengths):
        at = 0
        for doc, n in enumerate(docs, start=1):
            mask[row, at:at + n], seg[row, at:at + n] = 1, doc
            at += n
    ids = gen.integers(2, VOCAB, mask.shape).astype(np.int32)
    return {"input_ids": ids, "attention_mask": mask,
            **({"segment_ids": seg} if packed else {})}


def _sliced(logits, batch):
    """Loss, accuracy by row and weights, the logits sliced."""
    ids, mask = jnp.asarray(batch["input_ids"]), batch["attention_mask"]
    w = jnp.asarray(mask[:, 1:], jnp.float32)
    if "segment_ids" in batch:
        seg = batch["segment_ids"]
        w = w * (seg[:, 1:] == seg[:, :-1])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    raw = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    hit = (jnp.argmax(logits[:, :-1], -1) == ids[:, 1:]) * w
    return ((raw * w).sum() / jnp.maximum(w.sum(), 1.0),
            hit.sum(-1) / jnp.maximum(w.sum(-1), 1.0), w)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("what", ["loss", "metric", "gradient"])
def test_the_shift_on_the_targets_equals_the_shift_on_the_logits(case, what):
    lengths, last = CASES[case]
    batch = _batch(lengths, packed=case != "padded tail")
    task = get_task("causal_lm", model_name="gpt_small", seq_len=SEQ,
                    vocab_size=VOCAB)
    finite = jax.random.normal(jax.random.key(36), (2, SEQ, VOCAB)) * 3
    live = np.pad(np.asarray(_sliced(finite, batch)[2]) > 0, ((0, 0), (0, 1)))
    logits = finite if last is None else finite.at[:, -1].set(last)
    aux = jnp.float32(0.25)

    def ours(x):
        return task.loss((x, aux), batch)

    def plain(x):
        return _sliced(x, batch)[0] + aux

    if what == "loss":
        got = float(ours(logits))
        np.testing.assert_allclose(got, float(plain(finite)), rtol=1e-6)
        if not live.any():
            assert got == 0.25
    elif what == "metric":
        np.testing.assert_allclose(
            np.asarray(task.metric((logits, aux), batch)),
            np.asarray(_sliced(finite, batch)[1]), rtol=1e-6)
    else:
        got = np.asarray(jax.grad(ours)(logits))
        want = np.asarray(jax.grad(plain)(finite))
        # exactly nothing where there is no weight, finite logits or not
        assert (got[~live] == 0).all() and (want[~live] == 0).all()
        np.testing.assert_allclose(got[live], want[live], rtol=1e-5,
                                   atol=1e-8)
