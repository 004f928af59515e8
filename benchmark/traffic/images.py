"""General generator of JPEG classification corpora, read from a traffic file.

Reads only parameters (``dataset`` of ``benchmark/traffic/<name>.json``) and a
seed; writes through the program's public writer. The pictures are smooth
seeded fields in three octaves plus mild noise: the statistics of a photograph
as far as a JPEG coder cares (energy falling with frequency, file sizes in the
range of the source corpus), not white noise, which is the worst case for the
entropy decoder and stands for no photograph. Imports numpy, PIL and pyarrow
only: nothing here may touch JAX (the generator's threads run beside the
process that holds the chip, and its worker processes must never claim it).
"""

from __future__ import annotations

import io
import multiprocessing
from concurrent.futures import ProcessPoolExecutor

import numpy as np

VERSION = 1  # part of the data set's key: bump when the bytes change


def _field(rng, h: int, w: int, cell: int) -> np.ndarray:
    from PIL import Image

    lo = rng.integers(0, 256, (h // cell + 2, w // cell + 2, 3), dtype=np.uint8)
    return np.asarray(
        Image.fromarray(lo).resize((w, h), Image.BICUBIC), dtype=np.int16
    )


def _one_jpeg(seed: int, index: int, sizes, noise: int, quality: int) -> bytes:
    from PIL import Image

    rng = np.random.default_rng([seed, index])
    pick = rng.random()
    acc = 0.0
    w, h = sizes[-1][0], sizes[-1][1]
    for sw, sh, share in sizes:
        acc += share
        if pick < acc:
            w, h = sw, sh
            break
    img = (_field(rng, h, w, 128) * 5 + _field(rng, h, w, 32) * 2
           + _field(rng, h, w, 8)) // 8
    img += rng.integers(-noise, noise + 1, img.shape, dtype=np.int16)
    buf = io.BytesIO()
    Image.fromarray(np.clip(img, 0, 255).astype(np.uint8)).save(
        buf, format="JPEG", quality=quality)
    return buf.getvalue()


def _some_jpegs(seed: int, indices, sizes, noise: int, quality: int) -> list:
    return [_one_jpeg(seed, i, sizes, noise, quality) for i in indices]


def generate(dataset: dict, seed: int, out_dir: str, workers: int) -> dict:
    """Author the corpus under ``out_dir``; returns counts for the log. The
    pictures are made in spawned worker processes, which import this module
    and nothing of JAX (threads spent most of their time waiting for the
    interpreter lock: 16 s on the 13-core chip host, PR 23)."""
    import pyarrow as pa

    from lance_distributed_training_tpu.data import write_dataset

    schema = pa.schema([("image", pa.binary()), ("label", pa.int64())])
    rows, unique = int(dataset["rows"]), int(dataset["unique_images"])
    sizes = [tuple(s) for s in dataset["sizes_w_h_share"]]
    count = min(unique, rows)
    chunks = [range(lo, min(lo + 32, count)) for lo in range(0, count, 32)]
    with ProcessPoolExecutor(
            max(min(workers, len(chunks)), 1),
            mp_context=multiprocessing.get_context("spawn")) as pool:
        futures = [pool.submit(_some_jpegs, seed, chunk, sizes,
                               int(dataset["noise"]),
                               int(dataset["jpeg_quality"]))
                   for chunk in chunks]
        blobs = [blob for future in futures for blob in future.result()]
    bank = pa.array(blobs, pa.binary())
    labels = np.random.default_rng([seed, 1 << 30]).integers(
        0, int(dataset["num_classes"]), rows)

    def batches():
        for lo in range(0, rows, 4096):
            idx = np.arange(lo, min(lo + 4096, rows)) % len(blobs)
            yield pa.record_batch(
                [bank.take(pa.array(idx)),
                 pa.array(labels[lo:lo + 4096], pa.int64())],
                schema=schema)

    write_dataset(batches(), out_dir, schema=schema, mode="overwrite",
                  max_rows_per_file=int(dataset["fragment_rows"]))
    return {"rows": rows, "unique": len(blobs),
            "mean_jpeg_bytes": float(np.mean([len(b) for b in blobs]))}


class Plan:
    """What the sharded-batch plan schedules, step by step, worked out from the
    traffic file alone: step ``k`` of an epoch trains rows
    ``[k*batch, (k+1)*batch)``, the tail that fills no batch is dropped."""

    def __init__(self, dataset: dict, seed: int, batch: int):
        self.batch = batch
        self.steps_per_epoch = int(dataset["rows"]) // batch

    def samples(self, first_step: int, last_step: int) -> int:
        """Images trained on in steps ``first_step+1 .. last_step``."""
        return (last_step - first_step) * self.batch

    scheduled = samples  # one sample is one image
