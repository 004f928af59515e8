"""Ragged batches after the token plane against the plain reader, on meaning
and not on the packer's order: every document of the batch appears exactly
once, whole and contiguous, under one segment id with positions 0 ..
length-1, and nothing else is marked as real."""

from __future__ import annotations

import numpy as np

from reference.reader import read_rows


def check(batches: list, dataset_dir: str, batch: int, traffic: dict,
          config: dict) -> list:
    problems = []
    for k, got in enumerate(batches):
        col = read_rows(dataset_dir, k * batch, (k + 1) * batch).column(
            "input_ids").combine_chunks()
        offsets = np.asarray(col.offsets)
        values = np.asarray(col.flatten())
        want = sorted(values[a:b].tobytes()
                      for a, b in zip(offsets[:-1], offsets[1:]))
        ids = np.asarray(got["input_ids"])
        seg = np.asarray(got["segment_ids"])
        pos = np.asarray(got["position_ids"])
        mask = np.asarray(got["attention_mask"])
        found, bad = [], []
        for r in range(ids.shape[0]):
            edges = np.flatnonzero(np.diff(seg[r], prepend=-1, append=-1))
            for a, b in zip(edges[:-1], edges[1:]):
                if seg[r, a] == 0:
                    continue
                found.append(ids[r, a:b].astype(np.int32).tobytes())
                if not np.array_equal(pos[r, a:b], np.arange(b - a)):
                    bad.append((r, int(a)))
        if sorted(found) != want:
            problems.append(f"step {k + 1}: the packed grid holds "
                            f"{len(found)} runs that are not the batch's "
                            f"{len(want)} documents, each once and whole")
        if bad:
            problems.append(f"step {k + 1}: positions do not restart at "
                            f"{bad[:3]}")
        if not np.array_equal(mask.astype(bool), seg > 0):
            problems.append(f"step {k + 1}: attention_mask != (segment > 0)")
    return problems
