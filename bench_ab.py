"""Columnar-vs-folder A/B benchmark — the reference's core comparison.

The reference repo exists to compare Lance columnar loading against vanilla
torchvision file loading on the same task (``/root/reference/README.md:
286-290``; the whole ``torch_version/`` tree — ``iter_style.py`` and
``map_style.py`` are "deliberately near-isomorphic" to the Lance drivers so
the comparison isolates the data layer). This script runs that comparison on
THIS host: all four quadrants {columnar, folder} x {map, iterable} over the
SAME image corpus — the columnar dataset is built from the folder tree by
``create_dataset_from_image_folder`` (byte-identical JPEG pass-through), so
the two arms read literally the same bytes through different storage.

Fairness caveat ("same bytes" is about VALUES, not inodes): the synthetic
folder tree is hardlink-deduplicated to a 64-image unique pool
(``create_synthetic_image_folder`` — every row links to one of 64 inodes),
while the columnar import materialises every row into its fragments. The
folder arm therefore enjoys a page-cache working set ~rows/64 smaller than
the columnar arm's, an edge real datasets don't have. Default runs accept
it (both arms fit this host's page cache after the warm pass, so the skew
is second-order); pass ``--no_hardlink`` for fidelity runs — it rewrites
every hardlinked file as a distinct copy (same bytes, distinct inodes)
before measuring, making the two arms' cache footprints honest.

Two tiers per quadrant, both through product code paths:

1. **loader-only** — construct the exact pipeline ``train()`` builds
   (``FolderDataPipeline`` / ``MapStylePipeline`` / ``make_train_pipeline``
   with the trainer's decoder) and measure pure data-layer throughput:
   open/read/decode to device-ready arrays, no model. On this 1-core host
   the end-to-end number is compute-bound, so THIS is the number that
   actually separates the storage layers.
2. **end-to-end** — the real ``train()`` (resnet18, device_cache off so
   every epoch streams), reporting epoch-1 images/sec and loader_stall_pct.

Every quadrant line carries ``vs_baseline`` = its loader-only rate over the
**folder-map** arm's (the torchvision ``DistributedSampler`` twin = the
control arm = 1.0), so no number floats free; a final ``ab_summary`` line
names the winner.

Usage::

    python bench_ab.py                 # all four quadrants + summary
    python bench_ab.py --no_hardlink   # fidelity: one inode per folder row
    BENCH_SMALL=1 python bench_ab.py   # tiny smoke
    BENCH_AB_LOADER_ROWS=4096 BENCH_AB_STEPS=12 python bench_ab.py

Each quadrant runs in a subprocess (CPU-pinned before any backend query —
this benchmark never touches the chip) sharing one corpus built by
the parent; a warm pass equalises page-cache state between arms.
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import tempfile
import time

SMALL = bool(os.environ.get("BENCH_SMALL"))
LOADER_ROWS = int(os.environ.get("BENCH_AB_LOADER_ROWS") or 0) or (
    256 if SMALL else 2048)
TRAIN_STEPS = int(os.environ.get("BENCH_AB_STEPS") or 0) or (2 if SMALL else 6)
LOADER_PASSES = 1 if SMALL else 3
BATCH = 16 if SMALL else 64
IMAGE_SIZE = 64 if SMALL else 224
NUM_CLASSES = 10 if SMALL else 101

QUADRANTS = [
    ("folder", "map"),       # torchvision map_style twin — the control arm
    ("folder", "iterable"),  # torchvision iter_style twin
    ("columnar", "map"),     # lance_map_style twin
    ("columnar", "iterable"),  # lance_iterable twin (the headline loader)
]


def _force_cpu() -> None:
    from _bench_init import force_cpu

    force_cpu(1)


def _materialize_tree(tree: str) -> int:
    """Break hardlink dedup: rewrite every multi-link file as a distinct
    copy (same bytes, its own inode), so the folder arm's page-cache
    footprint matches the columnar arm's every-row materialisation. Returns
    the number of files rewritten."""
    import shutil

    rewritten = 0
    for dirpath, _dirnames, filenames in os.walk(tree):
        for fn in sorted(filenames):
            path = os.path.join(dirpath, fn)
            if os.stat(path).st_nlink <= 1:
                continue
            tmp = path + ".mat"
            shutil.copyfile(path, tmp)  # reads via one link, writes new inode
            os.replace(tmp, path)
            rewritten += 1
    return rewritten


def _build_corpus(root: str, rows: int, tag: str,
                  no_hardlink: bool = False) -> tuple[str, str]:
    """Folder tree of ``rows`` JPEGs (64-image unique pool, FOOD101-shaped
    class layout) + a byte-identical columnar import of that tree. With
    ``no_hardlink`` the tree is re-materialised to one inode per row (see
    the module docstring's fairness caveat)."""
    from lance_distributed_training_tpu.data.authoring import (
        create_dataset_from_image_folder,
        create_synthetic_image_folder,
    )

    tree = create_synthetic_image_folder(
        os.path.join(root, f"{tag}-folder"), rows,
        num_classes=NUM_CLASSES, image_size=IMAGE_SIZE,
    )
    if no_hardlink:
        n = _materialize_tree(tree)
        print(f"[ab] --no_hardlink: materialized {n} files in {tag}-folder",
              file=sys.stderr, flush=True)
    uri = os.path.join(root, f"{tag}-columnar")
    create_dataset_from_image_folder(
        tree, uri, fragment_size=max(rows // 4, 1), batch_size=512,
    )
    return tree, uri


def _make_loader(config, epoch: int):
    """The trainer's own loader for this config — product path, but with
    device_put disabled so tier 1 measures storage+decode, not jax.Array
    construction (identical for both arms anyway on one CPU device)."""
    from unittest import mock

    from lance_distributed_training_tpu.data.format import Dataset
    from lance_distributed_training_tpu.trainer import _build_loader

    dataset = (
        Dataset(config.dataset_path)
        if config.data_format == "columnar" else None
    )
    with mock.patch(
        "lance_distributed_training_tpu.trainer.make_global_batch",
        new=lambda batch, mesh=None, seq_axis=None: batch,
    ):
        return _build_loader(config, dataset, mesh=None, epoch=epoch)


def _loader_only(config) -> dict:
    """Warm pass (page cache + thread spin-up), then LOADER_PASSES timed
    full passes; rate = decoded images / wall seconds."""
    consumed = 0
    for b in _make_loader(config, epoch=0):
        consumed += 1
    t0 = time.perf_counter()
    n_img = 0
    for ep in range(1, LOADER_PASSES + 1):
        for batch in _make_loader(config, epoch=ep):
            n_img += int(next(iter(batch.values())).shape[0])
    dt = time.perf_counter() - t0
    return {
        "loader_images_per_sec": round(n_img / dt, 2),
        "loader_batches": consumed,
        "loader_measured_images": n_img,
        "loader_measured_secs": round(dt, 3),
    }


def run_quadrant(arm: str, style: str, corpus_root: str) -> dict:
    _force_cpu()
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    loader_path = os.path.join(
        corpus_root, f"loader-{'columnar' if arm == 'columnar' else 'folder'}")
    train_path = os.path.join(
        corpus_root, f"train-{'columnar' if arm == 'columnar' else 'folder'}")

    base = dict(
        data_format=arm, loader_style=style, num_classes=NUM_CLASSES,
        image_size=IMAGE_SIZE, batch_size=BATCH, no_wandb=True, no_ddp=True,
        eval_at_end=False, device_cache=False, prefetch=3,
    )
    # Tier 1: pure data layer over the big corpus.
    tier1 = _loader_only(TrainConfig(dataset_path=loader_path, **base))
    # Tier 2: real train() over the small corpus; epoch 1 (post-compile,
    # still streaming — device_cache off) is the measurement.
    result = train(TrainConfig(
        dataset_path=train_path, model_name="resnet18", epochs=2, **base))
    return {
        "metric": f"ab-{arm}-{style}",
        "value": tier1["loader_images_per_sec"],
        "unit": "loader_images/sec",
        "vs_baseline": None,  # parent fills: / folder-map loader rate
        **tier1,
        "train_images_per_sec": round(
            float(result["images_per_sec_per_chip"]), 2),
        "train_loader_stall_pct": round(
            float(result["loader_stall_pct"]), 2),
        "train_loss": round(float(result["loss"]), 4),
        "basis": "streaming_epoch1_cpu_1core",
    }


def main() -> None:
    if "--run" in sys.argv:
        i = sys.argv.index("--run")
        arm, style, corpus_root = sys.argv[i + 1 : i + 4]
        try:
            print(json.dumps(run_quadrant(arm, style, corpus_root)),
                  flush=True)
        except Exception as e:  # noqa: BLE001 — always leave a parseable line
            import traceback

            traceback.print_exc(file=sys.stderr)
            print(json.dumps({"metric": f"ab-{arm}-{style}", "value": None,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
        return

    no_hardlink = "--no_hardlink" in sys.argv
    root = tempfile.mkdtemp(prefix="ldt-ab-")
    print(f"[ab] building shared corpus under {root} "
          f"(loader={LOADER_ROWS} rows, train={BATCH * TRAIN_STEPS} rows, "
          f"{IMAGE_SIZE}px, no_hardlink={no_hardlink})",
          file=sys.stderr, flush=True)
    _force_cpu()
    # Stdout is the JSON-lines artifact; authoring progress prints
    # ("wrote N rows in M fragments") must not contaminate it.
    with contextlib.redirect_stdout(sys.stderr):
        _build_corpus(root, LOADER_ROWS, "loader", no_hardlink=no_hardlink)
        _build_corpus(root, BATCH * TRAIN_STEPS, "train",
                      no_hardlink=no_hardlink)

    # The control arm (folder-map) runs FIRST, so every record can be
    # printed the moment its quadrant finishes with vs_baseline already
    # filled — a kill mid-benchmark keeps all completed measurements
    # (the same checkpoint-every-record contract as the campaign stages).
    records = []
    ctl_rate = None
    for arm, style in QUADRANTS:
        print(f"[ab] running {arm}-{style} ...", file=sys.stderr, flush=True)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--run", arm,
                 style, root],
                capture_output=True, text=True,
                timeout=int(os.environ.get("BENCH_AB_QUADRANT_TIMEOUT")
                            or 1800),
            )
            lines = [l for l in proc.stdout.splitlines()
                     if l.startswith("{")]
            err = (proc.stderr or "no output").strip()[-400:]
        except subprocess.TimeoutExpired:
            lines, err = [], "quadrant timeout — wedged loader or train()"
        if lines:
            r = json.loads(lines[-1])
        else:
            r = {"metric": f"ab-{arm}-{style}", "value": None, "error": err}
        # Self-describing artifact: which folder-corpus fidelity produced
        # this line (see the module docstring's hardlink caveat).
        r["folder_corpus"] = (
            "materialized_per_row" if no_hardlink
            else "hardlink_dedup_64_inodes"
        )
        if (arm, style) == ("folder", "map"):
            ctl_rate = r.get("value") or None
        if r.get("value") is not None and ctl_rate:
            r["vs_baseline"] = round(r["value"] / ctl_rate, 3)
        records.append(r)
        print(json.dumps(r), flush=True)

    by_name = {r["metric"]: r for r in records}

    col = by_name.get("ab-columnar-iterable", {})
    fol = by_name.get("ab-folder-iterable", {})
    if col.get("value") and fol.get("value"):
        speedup = col["value"] / fol["value"]
        winner = "columnar" if speedup > 1.0 else "folder"
        print(json.dumps({
            "metric": "ab_summary",
            "value": round(speedup, 3),
            "unit": "columnar_iter_over_folder_iter_loader_rate",
            "vs_baseline": round(speedup, 3),
            "winner": winner,
            "note": (
                "loader-only tier isolates the data layer (1-core host: "
                "end-to-end is compute-bound); train_* fields give the "
                "product-path numbers"
            ),
        }), flush=True)


if __name__ == "__main__":
    main()
