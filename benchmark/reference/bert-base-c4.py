"""Plain reference of the program's BERT-base encoder with its masked-LM
head: float32 ``jax.numpy`` only, eval mode, fed the program's own parameter
tree. Widths are those of ``google-bert/bert-base-uncased`` (hidden 768, 12
layers, 12 heads of 64, feed-forward 3072, vocabulary 30,522, 512 positions).
Departures from the published model, all the program's own block and all
listed in ``benchmark/configs/bert-base-c4.json``: layer norm before each
sub-layer and once more at the end (pre-LN) with epsilon 1e-6, no token-type
embedding, no embedding layer norm, no dropout, tanh-approximated GELU, no
pooler or next-sentence head, and the masked-LM head is the tied embedding
matrix with neither the transform layer nor a bias. With ``segment_ids``
attention stays inside a packed document and positions restart with it.
"""

from __future__ import annotations

import numpy as np

MASK_ID = 1
MASK_STRIDE = 7  # round(1 / 0.15): the program's eval corrupts every 7th slot

# bf16 activations and bf16 attention weights through 12 layers, and a
# vocabulary projection in one bf16 pass, against float32 at highest
# precision: worst difference near 3% of the logits' spread (measured on the
# v5e, PR 23, in PERF.md). One missing block, residual or layer norm moves
# them by more than a third of their spread.
TOLERANCE = 0.12


def eval_batch(rows, config: dict) -> dict:
    """First rows as the padded arm feeds them: ids padded with 0 to the
    sequence length, mask over the real tokens."""
    seq = int(config["task"]["seq_len"])
    col = rows.column("input_ids").combine_chunks()
    ids = np.zeros((len(col), seq), np.int32)
    mask = np.zeros((len(col), seq), np.int8)
    for i, doc in enumerate(col.to_pylist()):
        ids[i, :len(doc)] = doc[:seq]
        mask[i, :len(doc)] = 1
    return {"input_ids": ids, "attention_mask": mask}


def packed_batch(rows, config: dict) -> dict:
    """The same rows laid two to a row where they fit, with segment and
    position ids: what the ragged plane hands the model, built greedily here
    so that the reference's segment masking is checked against the
    program's."""
    seq = int(config["task"]["seq_len"])
    docs = [d[:seq] for d in
            rows.column("input_ids").combine_chunks().to_pylist()]
    n = len(docs)
    out = {k: np.zeros((n, seq), np.int32)
           for k in ("input_ids", "segment_ids", "position_ids")}
    fill = [0] * n
    for i, doc in enumerate(docs):
        r = next(r for r in range(n) if fill[r] + len(doc) <= seq)
        a = fill[r]
        out["input_ids"][r, a:a + len(doc)] = doc
        out["segment_ids"][r, a:a + len(doc)] = i + 1
        out["position_ids"][r, a:a + len(doc)] = np.arange(len(doc))
        fill[r] += len(doc)
    out["attention_mask"] = (out["segment_ids"] > 0).astype(np.int8)
    return out


def perturb(variables, rng):
    return variables  # nothing degenerate at initialisation


def live(batch, want):
    """Which logits mean something: those of real tokens (a padding slot's
    are whatever the block makes of the padding id)."""
    return batch["attention_mask"] > 0


def forward(variables, batch):
    import jax
    import jax.numpy as jnp

    p = variables["params"]
    ids = jnp.asarray(batch["input_ids"], jnp.int32)
    live = jnp.asarray(batch["attention_mask"]) > 0
    seq = ids.shape[1]
    corrupt = ((jnp.arange(seq) % MASK_STRIDE) == 0)[None, :] & live
    ids = jnp.where(corrupt, MASK_ID, ids)

    def norm(x, q):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-6) * q["scale"] + q["bias"]

    with jax.default_matmul_precision("highest"):
        table = p["tok_embed"]["embedding"]
        x = table[ids]
        if "position_ids" in batch:
            x = x + p["pos_embed"][jnp.asarray(batch["position_ids"])]
            seg = jnp.asarray(batch["segment_ids"])
            allow = (seg[:, :, None] == seg[:, None, :]) & live[:, None, :]
        else:
            x = x + p["pos_embed"][:seq]
            allow = jnp.broadcast_to(live[:, None, :], (len(ids), seq, seq))
        layers = sum(1 for k in p if k.startswith("layer_"))
        for i in range(layers):
            q = p[f"layer_{i}"]
            a = q["attn"]
            y = norm(x, q["ln_attn"])
            qh, kh, vh = (
                jnp.einsum("bsh,hnd->bnsd", y, a[n]["kernel"])
                + a[n]["bias"][None, :, None, :]
                for n in ("query", "key", "value"))
            scores = jnp.einsum("bnqd,bnkd->bnqk", qh, kh) / np.sqrt(
                qh.shape[-1])
            scores = jnp.where(allow[:, None], scores,
                               jnp.finfo(jnp.float32).min)
            ctx = jnp.einsum("bnqk,bnkd->bqnd", jax.nn.softmax(scores, -1), vh)
            x = x + ctx.reshape(x.shape) @ a["out"]["kernel"] + a["out"]["bias"]
            y = norm(x, q["ln_mlp"])
            y = jax.nn.gelu(y @ q["mlp_in"]["kernel"] + q["mlp_in"]["bias"],
                            approximate=True)
            x = x + y @ q["mlp_out"]["kernel"] + q["mlp_out"]["bias"]
        return norm(x, p["ln_final"]) @ table.T
