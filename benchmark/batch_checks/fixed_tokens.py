"""Pre-packed token batches against the plain reader: fixed-size rows are
taken as stored, so every leaf is equal bit for bit."""

from __future__ import annotations

import numpy as np

from reference.reader import read_rows


def check(batches: list, dataset_dir: str, batch: int, traffic: dict,
          config: dict) -> list:
    problems = []
    for k, got in enumerate(batches):
        table = read_rows(dataset_dir, k * batch, (k + 1) * batch)
        for name in ("input_ids", "attention_mask"):
            col = table.column(name).combine_chunks()
            want = np.asarray(col.flatten()).reshape(len(col), -1)
            if not np.array_equal(np.asarray(got[name]), want):
                problems.append(f"step {k + 1}: leaf {name} differs from "
                                "the stored rows")
    return problems
