"""Zero-copy batch-plane A/B — the r6 acceptance benchmark (BENCH_ZC_r06).

Two arm PAIRS over one shared synthetic columnar corpus, each pair measured
in its own subprocess (fresh process registry + buffer pool, CPU-pinned
before any backend query — this benchmark never touches the chip):

* ``workers-pickle`` vs ``workers-shm`` — ``num_workers=2``, legacy pickle
  IPC vs shared-memory ring slots (acceptance: shm **>= +15%** loader
  img/s over pickle on this box);
* ``thread-nopool`` vs ``thread-pool`` — ``num_workers=0``, fresh
  allocation per batch (~ the pre-r6 HEAD thread path) vs pooled decode
  pages (acceptance: no worse than nopool).

The two arms of a pair run INTERLEAVED, pass by pass, inside one process
and each arm's rate is computed over its summed pass times — this box's
run-to-run throughput drift (a shared 2-core container; >2x swings between
subprocesses were observed) cancels out of the within-pair ratio, which is
the number the acceptance criteria are about.

Loaders are the trainer's own (``_build_loader`` + ``_make_worker_pool``),
device_put disabled so the measurement is storage+decode+IPC, exactly like
``bench_ab.py`` tier 1. Pooled-arm records carry the pool/shm counters
scraped from a live ``/metrics`` exporter in the measuring subprocess — the
artifact shows whether the plane actually recycled, not just how fast it
went. ``vs_baseline`` is normalized to the pair's control arm.

Usage::

    python bench_zero_copy.py                  # full run (writes stdout JSONL)
    BENCH_SMALL=1 python bench_zero_copy.py    # tiny smoke
    BENCH_ZC_ROWS=4096 BENCH_ZC_PASSES=5 python bench_zero_copy.py
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import subprocess
import sys
import tempfile
import time
import urllib.request

SMALL = bool(os.environ.get("BENCH_SMALL"))
ROWS = int(os.environ.get("BENCH_ZC_ROWS") or 0) or (256 if SMALL else 2048)
NUM_WORKERS = int(os.environ.get("BENCH_ZC_WORKERS") or 0) or 2
PASSES = int(os.environ.get("BENCH_ZC_PASSES") or 0) or (1 if SMALL else 3)
BATCH = 16 if SMALL else 64
IMAGE_SIZE = 64 if SMALL else 224
NUM_CLASSES = 10 if SMALL else 101

# Pair = (pair_name, [(arm_name, num_workers, shm_workers, buffer_pool),
#                     ...]) — first arm is the pair's control (vs_baseline 1).
PAIRS = [
    ("workers", [
        ("workers-pickle", NUM_WORKERS, False, False),  # the r5 IPC path
        ("workers-shm", NUM_WORKERS, True, True),       # the r6 plane
    ]),
    ("thread", [
        ("thread-nopool", 0, False, False),  # ~ pre-r6 HEAD thread path
        ("thread-pool", 0, False, True),     # r6 default thread path
    ]),
]


def _force_cpu() -> None:
    from _bench_init import force_cpu

    force_cpu(1)


def _scrape_metrics() -> dict:
    """Serve the process registry once and scrape the buffer-plane series —
    the artifact records pool behavior from the same surface operators
    scrape (/metrics), not from internal counters."""
    from lance_distributed_training_tpu.obs.http import MetricsHTTPServer
    from lance_distributed_training_tpu.obs.registry import default_registry

    exporter = MetricsHTTPServer(default_registry(), port=0).start()
    try:
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/metrics", timeout=10
        ).read().decode()
    finally:
        exporter.stop()

    def series(name: str) -> float:
        m = re.search(rf"^{name} (\S+)$", text, re.M)
        return float(m.group(1)) if m else 0.0

    hits, misses = series("bufpool_hit_total"), series("bufpool_miss_total")
    return {
        "bufpool_hit_total": hits,
        "bufpool_miss_total": misses,
        "bufpool_hit_rate": round(hits / (hits + misses), 4)
        if hits + misses else None,
        "shm_batches_total": series("shm_batches_total"),
        "shm_fallback_total": series("shm_fallback_total"),
    }


def run_pair(pair_name: str, uri: str) -> list:
    _force_cpu()
    from unittest import mock

    from lance_distributed_training_tpu.data.format import Dataset
    from lance_distributed_training_tpu.trainer import (
        TrainConfig,
        _build_loader,
        _make_worker_pool,
    )

    arms = dict(PAIRS)[pair_name]
    dataset = Dataset(uri)
    state = {}
    for name, num_workers, shm, pool in arms:
        config = TrainConfig(
            dataset_path=uri, num_classes=NUM_CLASSES,
            image_size=IMAGE_SIZE, batch_size=BATCH, no_wandb=True,
            no_ddp=True, prefetch=3, num_workers=num_workers,
            shm_workers=shm, buffer_pool=pool,
        )
        state[name] = {
            "config": config,
            "workers": _make_worker_pool(config, dataset),
            "images": 0,
            "secs": 0.0,
        }

    def one_pass(name: str, epoch: int) -> None:
        st = state[name]
        with mock.patch(
            "lance_distributed_training_tpu.trainer.make_global_batch",
            new=lambda batch, mesh=None, seq_axis=None: batch,
        ):
            loader = _build_loader(st["config"], dataset, mesh=None,
                                   epoch=epoch, workers=st["workers"])
        t0 = time.perf_counter()
        n = 0
        for batch in loader:
            n += int(next(iter(batch.values())).shape[0])
            del batch
        st["secs"] += time.perf_counter() - t0
        st["images"] += n

    try:
        for name, *_ in arms:  # warm: page cache, worker spin-up, pool fill
            st = state[name]
            with mock.patch(
                "lance_distributed_training_tpu.trainer.make_global_batch",
                new=lambda batch, mesh=None, seq_axis=None: batch,
            ):
                for batch in _build_loader(st["config"], dataset, mesh=None,
                                           epoch=0, workers=st["workers"]):
                    del batch
        # Interleave: arm A pass 1, arm B pass 1, arm A pass 2, ... so slow
        # host-level drift lands on both arms of the ratio equally.
        for ep in range(1, PASSES + 1):
            for name, *_ in arms:
                one_pass(name, ep)
    finally:
        for st in state.values():
            if st["workers"] is not None:
                st["workers"].shutdown()

    metrics = _scrape_metrics()
    leftover = [f for f in os.listdir("/dev/shm") if f.startswith("ldtshm")]
    records = []
    for name, num_workers, shm, pool in arms:
        st = state[name]
        records.append({
            "metric": f"zc-{name}",
            "value": round(st["images"] / st["secs"], 2),
            "unit": "loader_images/sec",
            "vs_baseline": None,  # parent fills: / pair-control rate
            "loader_measured_images": st["images"],
            "loader_measured_secs": round(st["secs"], 3),
            "num_workers": num_workers,
            "transport": ("shm" if shm else "pickle") if num_workers else None,
            "buffer_pool": pool,
            # Process-wide series: attributed to the pair's pooled arm (one
            # pooled arm per subprocess by construction).
            **(metrics if pool else {}),
            "shm_leftover_segments": leftover,
            "basis": (
                f"loader_only_interleaved_passes_cpu_{os.cpu_count()}core_"
                f"{IMAGE_SIZE}px"
            ),
        })
    return records


def main() -> None:
    if "--run" in sys.argv:
        i = sys.argv.index("--run")
        pair_name, uri = sys.argv[i + 1 : i + 3]
        try:
            for r in run_pair(pair_name, uri):
                print(json.dumps(r), flush=True)
        except Exception as e:  # noqa: BLE001 — always leave a parseable line
            import traceback

            traceback.print_exc(file=sys.stderr)
            print(json.dumps({"metric": f"zc-{pair_name}", "value": None,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
        return

    root = tempfile.mkdtemp(prefix="ldt-zc-")
    uri = os.path.join(root, "ds")
    print(f"[zc] building corpus: {ROWS} rows @ {IMAGE_SIZE}px under {root}",
          file=sys.stderr, flush=True)
    _force_cpu()
    from lance_distributed_training_tpu.data.authoring import (
        create_synthetic_classification_dataset,
    )

    with contextlib.redirect_stdout(sys.stderr):
        create_synthetic_classification_dataset(
            uri, ROWS, num_classes=NUM_CLASSES, image_size=IMAGE_SIZE,
            fragment_size=max(ROWS // 4, 1),
        )

    records = {}
    for pair_name, arms in PAIRS:
        print(f"[zc] running pair {pair_name} "
              f"({' vs '.join(a[0] for a in arms)}) ...",
              file=sys.stderr, flush=True)
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--run",
                 pair_name, uri],
                capture_output=True, text=True,
                timeout=int(os.environ.get("BENCH_ZC_PAIR_TIMEOUT") or 2400),
            )
            lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
            err = (proc.stderr or "no output").strip()[-400:]
        except subprocess.TimeoutExpired:
            lines, err = [], "pair timeout — wedged loader"
        if not lines:
            r = {"metric": f"zc-{pair_name}", "value": None, "error": err}
            records[r["metric"]] = r
            print(json.dumps(r), flush=True)
            continue
        control_rate = None
        for line in lines:
            r = json.loads(line)
            if control_rate is None:  # first record of the pair = control
                control_rate = r.get("value") or None
            if r.get("value") and control_rate:
                r["vs_baseline"] = round(r["value"] / control_rate, 3)
            records[r["metric"]] = r
            print(json.dumps(r), flush=True)

    shm = records.get("zc-workers-shm", {})
    pk = records.get("zc-workers-pickle", {})
    tp = records.get("zc-thread-pool", {})
    tn = records.get("zc-thread-nopool", {})
    if shm.get("value") and pk.get("value"):
        speedup = shm["value"] / pk["value"]
        print(json.dumps({
            "metric": "zc_summary",
            "value": round(speedup, 3),
            "unit": "workers_shm_over_workers_pickle_loader_rate",
            "vs_baseline": round(speedup, 3),
            "accept_worker_path": bool(speedup >= 1.15),
            "thread_pool_vs_nopool": round(tp["value"] / tn["value"], 3)
            if tp.get("value") and tn.get("value") else None,
            "bufpool_hit_rate_shm_arm": shm.get("bufpool_hit_rate"),
            "note": (
                "acceptance: workers-shm >= 1.15x workers-pickle AND "
                "thread-pool ~>= 1.0x thread-nopool; arms of a pair run "
                "interleaved in one process so host drift cancels from the "
                "ratio; hit rate scraped from /metrics in the measuring "
                "subprocess"
            ),
        }), flush=True)


if __name__ == "__main__":
    main()
