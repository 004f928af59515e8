"""Image batches against the plain reader: the same plan rows read with
pyarrow, decoded at full size with PIL and resized in one step; labels as
stored."""

from __future__ import annotations

import numpy as np

from reference.reader import decode_images, read_rows

# The program decodes with libjpeg's DCT scaling (a 512 px source comes out of
# the decoder at 256 px) and then samples bilinearly; PIL decodes at 512 px and
# resizes with a filter whose support grows with the reduction. On smooth
# fields with +-6 levels of noise the two agree to about 1 level on average
# (measured here on the CPU, PR 23: mean 0.9, 99.9th percentile 9); a wrong
# row, a shifted image or swapped channels give a mean above 20.
IMAGE_MEAN_ABS_MAX = 2.5
IMAGE_P999_ABS_MAX = 24


def check(batches: list, dataset_dir: str, batch: int, traffic: dict,
          config: dict) -> list:
    size = int(config["task"]["image_size"])
    problems = []
    for k, got in enumerate(batches):
        table = read_rows(dataset_dir, k * batch, (k + 1) * batch)
        want = decode_images(table, size)
        labels = np.asarray(table.column("label").to_numpy(), np.int64)
        if got["image"].shape != want.shape or got["image"].dtype != np.uint8:
            problems.append(f"step {k + 1}: image leaf {got['image'].shape} "
                            f"{got['image'].dtype}, want {want.shape} uint8")
            continue
        diff = np.abs(got["image"].astype(np.int16) - want.astype(np.int16))
        mean, p999 = float(diff.mean()), float(np.percentile(diff, 99.9))
        if mean > IMAGE_MEAN_ABS_MAX or p999 > IMAGE_P999_ABS_MAX:
            problems.append(f"step {k + 1}: images differ from the plain "
                            f"reader's by mean {mean:.2f}, p99.9 {p999:.0f}")
        if not np.array_equal(np.asarray(got["label"], np.int64), labels):
            problems.append(f"step {k + 1}: labels differ")
    return problems
