"""The plain reader: what a straightforward program makes of the same rows.

Rows are read with pyarrow straight from the fragment files the manifest
lists, in stored order; images are decoded at full size with PIL and resized
in one step; labels and fixed-size token rows are taken as stored. Nothing
here calls the program's readers, decoders or planners. The comparisons that
decide part of ``correct`` are one module each under
``benchmark/batch_checks/``, found by the name in the traffic file.
"""

from __future__ import annotations

import io
import json
import os

import numpy as np


def read_rows(dataset_dir: str, start: int, stop: int):
    """Rows ``[start, stop)`` of the data set as one pyarrow table."""
    import pyarrow as pa
    from pyarrow import ipc

    with open(os.path.join(dataset_dir, "manifest.json")) as f:
        fragments = json.load(f)["fragments"]
    pieces, base = [], 0
    for frag in fragments:
        lo, hi = max(start, base), min(stop, base + frag["num_rows"])
        if lo < hi:
            with pa.memory_map(os.path.join(dataset_dir, frag["path"])) as src:
                table = ipc.open_file(src).read_all()
                pieces.append(table.slice(lo - base, hi - lo).combine_chunks())
        base += frag["num_rows"]
    return pa.concat_tables(pieces)


def decode_images(table, size: int) -> np.ndarray:
    """JPEG column -> ``[n, size, size, 3]`` uint8: full-size PIL decode, then
    one bilinear resize to the square the model takes (aspect not kept, as
    the reference job's ``Resize((224, 224))``)."""
    from PIL import Image

    out = np.empty((table.num_rows, size, size, 3), np.uint8)
    for i, blob in enumerate(table.column("image").to_pylist()):
        img = Image.open(io.BytesIO(blob)).convert("RGB")
        out[i] = np.asarray(img.resize((size, size), Image.BILINEAR))
    return out
