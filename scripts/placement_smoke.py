"""CI placement smoke: mesh-native global batches + H2D telemetry + a
2-simulated-process shard parity check, on 8 XLA-forced CPU devices.

What it asserts (the r7 acceptance surface, in one short run):

1. the trainer's default loader path yields **global** ``jax.Array``
   batches — full global shape, ``P('data')`` sharding, per-device shards
   of ``batch/8`` rows — through the async placement plane;
2. the placed stream is **bit-identical** to the reference function
   ``make_global_batch`` applied to the same host batches;
3. two *simulated* training processes (process_index 0 and 1 of 2 — real
   multi-process needs a jax.distributed rendezvous CI doesn't have)
   produce disjoint host shards whose concatenation equals the
   single-process global batch bit-for-bit, and the fleet's
   stripe→process mapping is disjoint and covering;
4. ``trainer_h2d_ms`` and ``placement_buffer_depth`` are served on
   ``/metrics``, so H2D wait is separable from decode wait in stall
   accounting.

Equivalent by hand::

    ldt train --dataset_path <ds> --backend cpu --num_cpu_devices 8 \
        --metrics_port 9464 &
    curl -s localhost:9464/metrics | grep trainer_h2d_ms_bucket
"""

import os
import pathlib
import shutil
import tempfile
import urllib.request

# Before the first ``import jax``, as tests/conftest.py does.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from lance_distributed_training_tpu.data import (  # noqa: E402
    ImageClassificationDecoder,
    PlacementPlane,
    make_train_pipeline,
)
from lance_distributed_training_tpu.data.authoring import (  # noqa: E402
    create_synthetic_classification_dataset,
)
from lance_distributed_training_tpu.data.format import Dataset  # noqa: E402
from lance_distributed_training_tpu.fleet.balancer import (  # noqa: E402
    members_for_process,
)
from lance_distributed_training_tpu.obs.http import (  # noqa: E402
    MetricsHTTPServer,
)
from lance_distributed_training_tpu.obs.registry import (  # noqa: E402
    default_registry,
)
from lance_distributed_training_tpu.parallel import (  # noqa: E402
    get_mesh,
    make_global_batch,
)

BATCH = 16


def main() -> None:
    assert len(jax.devices()) == 8, jax.devices()
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="ldt-ci-placement-"))
    uri = str(tmp / "ds")
    create_synthetic_classification_dataset(
        uri, 64, num_classes=5, image_size=32, fragment_size=32
    )
    dataset = Dataset(uri)
    mesh = get_mesh()
    decode = ImageClassificationDecoder(image_size=32)
    try:
        # 1+2: placed global batches, bit-identical to the reference
        # function over the same host batches.
        plane = PlacementPlane(mesh, depth=2)
        placed = list(plane.wrap(
            make_train_pipeline(dataset, "batch", BATCH, 0, 1, decode)
        ))
        host_full = list(make_train_pipeline(
            dataset, "batch", BATCH, 0, 1, decode
        ))
        want_full = [make_global_batch(b, mesh) for b in host_full]
        assert placed and len(placed) == len(want_full)
        for got, want in zip(placed, want_full):
            assert got["image"].shape == (BATCH, 32, 32, 3)
            assert got["image"].sharding.spec == P("data"), (
                got["image"].sharding
            )
            shard = got["image"].addressable_shards[0]
            assert shard.data.shape[0] == BATCH // 8, shard.data.shape
            for key in want:
                assert got[key].sharding == want[key].sharding
                np.testing.assert_array_equal(
                    np.asarray(got[key]), np.asarray(want[key])
                )

        # 3: two simulated processes — disjoint shards that reassemble the
        # single-process stream, and a disjoint covering stripe mapping.
        shards = [
            list(make_train_pipeline(dataset, "batch", BATCH // 2, p, 2,
                                     decode))
            for p in range(2)
        ]
        assert len(shards[0]) == len(shards[1]) == len(host_full)
        for full, s0, s1 in zip(host_full, *shards):
            np.testing.assert_array_equal(
                full["image"],
                np.concatenate([s0["image"], s1["image"]], axis=0),
            )
        members = [{"server_id": f"s{i}", "addr": f"h{i}:1"}
                   for i in range(5)]
        assigned = [members_for_process(members, p, 2) for p in range(2)]
        ids = [m["server_id"] for s in assigned for m in s]
        assert sorted(ids) == sorted(m["server_id"] for m in members)
        assert len(set(ids)) == len(ids)

        # 4: the H2D telemetry the plane feeds is on /metrics.
        exporter = MetricsHTTPServer(default_registry(), port=0).start()
        try:
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/metrics", timeout=10
            ).read().decode()
        finally:
            exporter.stop()
        for series in ("trainer_h2d_ms_bucket", "trainer_h2d_ms_count",
                       "placement_buffer_depth",
                       "placement_batches_placed"):
            assert series in text, f"missing {series} in /metrics"
        print(
            f"placement smoke ok: {len(placed)} global batches "
            f"({BATCH}x32x32x3 over 8 devices, P('data')), 2-process "
            "shards reassemble bit-identically, trainer_h2d_ms on /metrics"
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
