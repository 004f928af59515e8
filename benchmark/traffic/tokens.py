"""General generator of tokenised text corpora, read from a traffic file.

Documents are token ids uniform over the vocabulary (ids 0 and 1 are the
program's padding and mask ids and are never drawn) with lengths from a
lognormal clipped to ``[min_len, max_len]``, as a BERT pipeline that
truncates at its sequence length sees them. Lengths and ids both come from
the run's seed, so every seed is another corpus: another mix of packed grid
shapes, and now and then a shape that the run meets for the first time inside
its window (PERF.md section 6, PR 23). ``layout`` says how they are
stored: ``ragged`` keeps one variable-length ``input_ids`` list per document;
``prepacked`` concatenates the same documents and cuts the stream every
``max_len`` tokens into fixed-size-list rows with an all-ones mask, the
layout offline packers write (the tail that fills no row is dropped). Built
with numpy from offsets and values, never from Python lists.
"""

from __future__ import annotations

import numpy as np

VERSION = 1  # part of the data set's key: bump when the bytes change


def documents(dataset: dict, seed: int):
    """``(values int32 [total], offsets int64 [docs+1])`` from the seed."""
    docs = int(dataset["documents"])
    lengths = np.clip(
        np.random.default_rng([seed, 7]).lognormal(
            np.log(float(dataset["median_len"])), float(dataset["sigma"]),
            docs).astype(np.int64),
        int(dataset["min_len"]), int(dataset["max_len"]))
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    values = np.random.default_rng([seed, 8]).integers(
        2, int(dataset["vocab_size"]), int(offsets[-1]), dtype=np.int32)
    return values, offsets


def generate(dataset: dict, seed: int, out_dir: str, workers: int) -> dict:
    import pyarrow as pa

    from lance_distributed_training_tpu.data import write_dataset

    values, offsets = documents(dataset, seed)
    seq = int(dataset["max_len"])
    if dataset["layout"] == "ragged":
        schema = pa.schema([("input_ids", pa.list_(pa.int32()))])
        column = pa.ListArray.from_arrays(
            pa.array(offsets.astype(np.int32)), pa.array(values))
        table = pa.Table.from_arrays([column], schema=schema)
        rows = len(offsets) - 1
    elif dataset["layout"] == "prepacked":
        rows = len(values) // seq
        ids = pa.FixedSizeListArray.from_arrays(
            pa.array(values[:rows * seq]), seq)
        mask = pa.FixedSizeListArray.from_arrays(
            pa.array(np.ones(rows * seq, np.int8)), seq)
        schema = pa.schema([("input_ids", pa.list_(pa.int32(), seq)),
                            ("attention_mask", pa.list_(pa.int8(), seq))])
        table = pa.Table.from_arrays([ids, mask], schema=schema)
    else:
        raise ValueError(f"unknown layout {dataset['layout']!r}")
    write_dataset(table, out_dir, schema=schema, mode="overwrite",
                  max_rows_per_file=int(dataset["fragment_rows"]))
    return {"rows": rows, "documents": len(offsets) - 1,
            "tokens": int(offsets[-1])}


class Plan:
    """What the sharded-batch plan schedules, step by step, from the traffic
    file and the seed alone (the corpus is regenerated, not read back)."""

    def __init__(self, dataset: dict, seed: int, batch: int):
        values, offsets = documents(dataset, seed)
        self.batch = batch
        seq = int(dataset["max_len"])
        if dataset["layout"] == "ragged":
            rows = len(offsets) - 1
            per_row = np.diff(offsets)
        else:
            rows = len(values) // seq
            per_row = np.full(rows, seq, np.int64)
        self.steps_per_epoch = rows // batch
        per_step = per_row[:self.steps_per_epoch * batch].reshape(
            self.steps_per_epoch, batch).sum(1)
        self._cum = np.concatenate([[0], np.cumsum(per_step)])

    def _upto(self, step: int) -> int:
        epochs, rest = divmod(step, self.steps_per_epoch)
        return int(epochs * self._cum[-1] + self._cum[rest])

    def samples(self, first_step: int, last_step: int) -> int:
        """Real tokens trained on in steps ``first_step+1 .. last_step``."""
        return self._upto(last_step) - self._upto(first_step)

    def scheduled(self, first_step: int, last_step: int) -> int:
        """Rows (documents, or pre-packed rows) scheduled in those steps."""
        return (last_step - first_step) * self.batch
