"""CI device-decode smoke: entropy-split parity + live decode_* telemetry.

Forced-CPU devices (the same jit kernel runs unmodified on real TPU — no
host callbacks, pinned by LDT101/LDT1301); asserts:

1. host-vs-device parity within the pinned envelope
   (``ops.jpeg_device.HOST_PARITY_MAX_ABS_DIFF``) AND bit-identical
   device-arm repeats, at the loader level;
2. a short ``--device_decode`` train run serves ``decode_entropy_ms``,
   ``decode_device_ms`` (sampled in the trainer's transform stage) and the
   ``decode_coeff_bytes_total`` / ``decode_pixel_bytes_total`` counters on
   a LIVE /metrics scrape (the exporter is polled while the trainer runs);
3. zero BufferPool-page leaks under the leak sanitizer
   (``utils/leaktrack.py`` — every lease the run took was released or
   swept) and zero leaked ``/dev/shm`` segments.

Equivalent by hand::

    ldt train --dataset_path <ds> --device_decode --metrics_port 9464 ... &
    curl -s localhost:9464/metrics | grep -E 'decode_(entropy|device)_ms'
"""

import gc
import os
import pathlib
import tempfile
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("LDT_LEAK_SANITIZER", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from lance_distributed_training_tpu.data.authoring import (  # noqa: E402
    create_synthetic_classification_dataset,
)
from lance_distributed_training_tpu.data.decode import (  # noqa: E402
    ImageClassificationDecoder,
)
from lance_distributed_training_tpu.data.device_decode import (  # noqa: E402
    CoeffImageDecoder,
)
from lance_distributed_training_tpu.data.pipeline import (  # noqa: E402
    make_train_pipeline,
)
from lance_distributed_training_tpu.obs.http import (  # noqa: E402
    MetricsHTTPServer,
)
from lance_distributed_training_tpu.obs.registry import (  # noqa: E402
    default_registry,
)
from lance_distributed_training_tpu.ops.jpeg_device import (  # noqa: E402
    HOST_PARITY_MAX_ABS_DIFF,
    decode_coeff_batch,
)
from lance_distributed_training_tpu.utils import leaktrack  # noqa: E402

SIZE = 32


def _kernel(batch) -> np.ndarray:
    return np.asarray(decode_coeff_batch(
        batch["jpeg_coef_y"], batch["jpeg_coef_cb"], batch["jpeg_coef_cr"],
        batch["jpeg_quant"], batch["jpeg_geom"], out_size=SIZE,
    ))


def _shm_segments() -> list:
    root = pathlib.Path("/dev/shm")
    if not root.exists():
        return []
    return [p.name for p in root.glob("ldt*")]


def main() -> None:
    leaktrack.enable()
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="ldt-ci-dd-"))
    ds = create_synthetic_classification_dataset(
        str(tmp / "ds"), rows=96, num_classes=10, image_size=48,
        fragment_size=48, unique_images=24, seed=7,
    )

    # -- 1: loader-level parity + bit-identical repeats -------------------
    from lance_distributed_training_tpu.data.buffers import (
        default_buffer_pool,
    )

    pool = default_buffer_pool()
    coeff_batches = []
    pipe = make_train_pipeline(
        ds, "batch", 16, 0, 1,
        CoeffImageDecoder(image_size=SIZE, buffer_pool=pool),
    )
    for b in pipe:
        coeff_batches.append({k: np.array(v) for k, v in b.items()})
    pixel_batches = list(make_train_pipeline(
        ds, "batch", 16, 0, 1, ImageClassificationDecoder(image_size=SIZE),
    ))
    assert len(coeff_batches) == len(pixel_batches) == 6
    worst = 0
    for cb, pb in zip(coeff_batches, pixel_batches):
        dev = _kernel(cb)
        dev2 = _kernel(cb)
        assert np.array_equal(dev, dev2), "device arm not bit-identical"
        diff = int(np.abs(
            dev.astype(np.int32) - pb["image"].astype(np.int32)
        ).max())
        worst = max(worst, diff)
    assert worst <= HOST_PARITY_MAX_ABS_DIFF, (
        f"parity envelope broken: {worst} > {HOST_PARITY_MAX_ABS_DIFF}"
    )
    print(f"parity ok: max abs diff {worst} <= {HOST_PARITY_MAX_ABS_DIFF}, "
          "repeats bit-identical")

    # -- 2: live /metrics during a --device_decode train run --------------
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    exporter = MetricsHTTPServer(default_registry(), port=0).start()
    results: dict = {}

    def run() -> None:
        results["train"] = train(TrainConfig(
            dataset_path=ds.uri, task_type="classification", num_classes=10,
            image_size=SIZE, batch_size=16, epochs=2, no_wandb=True,
            eval_at_end=False, autotune=False, log_every=0,
            model_name="resnet18", device_decode=True, lr=0.01,
        ))

    t = threading.Thread(target=run, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{exporter.port}"
    wanted = ("decode_entropy_ms_count", "decode_device_ms_count",
              "decode_coeff_bytes_total")
    deadline = time.monotonic() + 240
    live = ""
    while time.monotonic() < deadline:
        live = urllib.request.urlopen(
            f"{base}/metrics", timeout=10
        ).read().decode()
        if all(s in live for s in wanted) and t.is_alive():
            break
        if not t.is_alive():
            break
        time.sleep(0.5)
    t.join(timeout=240)
    assert not t.is_alive(), "trainer did not finish"
    assert "train" in results, "trainer thread died"
    for series in wanted + ("decode_pixel_bytes_total",):
        assert series in live or series in urllib.request.urlopen(
            f"{base}/metrics", timeout=10
        ).read().decode(), f"missing {series} on /metrics"
    exporter.stop()
    print(f"live /metrics ok: {', '.join(wanted)} present; "
          f"final loss {results['train']['loss']:.3f}")

    # -- 3: leak-clean under the sanitizer --------------------------------
    del coeff_batches, pixel_batches, pipe
    for _ in range(50):
        gc.collect()
        pool.sweep()
        if leaktrack.outstanding() == 0:
            break
    assert leaktrack.outstanding() == 0, (
        f"leaked pool leases: {leaktrack.outstanding()} outstanding "
        f"({ {k: v for k, v in leaktrack.sites().items() if v.get('leaked') or v['acquired'] > v['released']} })"
    )
    segs = _shm_segments()
    assert not segs, f"leaked /dev/shm segments: {segs}"
    print("leak sanitizer ok: 0 outstanding leases, /dev/shm clean")
    print("device-decode smoke ok")


if __name__ == "__main__":
    main()
