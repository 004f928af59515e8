"""REAL two-process distributed training on CPU meshes.

Everything else in the suite simulates multi-process topologies through the
sampler-plan math on one process. This test launches TWO actual OS
processes that rendezvous through ``jax.distributed.initialize`` (the
``init_process_group`` equivalent, /root/reference/lance_iterable.py:79-80,
driven here by explicit coordinator args as torchrun injects
MASTER_ADDR/RANK/WORLD_SIZE, :154-156), assemble one global batch from
per-process shards, and run the full ``train()`` loop with XLA-compiled
cross-process collectives — the multi-node-without-a-cluster check
SURVEY.md §4 calls for.
"""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # heavy integration tier (see conftest); gate commits with -m fast

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Child: 4 virtual CPU devices per process, 2 processes → 8 global devices.
_CHILD = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from lance_distributed_training_tpu.trainer import TrainConfig, train

uri, coord, pid = sys.argv[1], sys.argv[2], int(sys.argv[3])
cfg = TrainConfig(
    dataset_path=uri, num_classes=10, model_name="resnet18", image_size=32,
    batch_size=16, epochs=1, no_wandb=True, augment=False, eval_at_end=False,
    log_every=0, coordinator_address=coord, num_processes=2, process_id=pid,
)
results = train(cfg)
assert jax.process_count() == 2, jax.process_count()
import math

assert math.isfinite(results["loss"])
print(f"proc{pid} OK loss={results['loss']:.4f}", flush=True)
'''


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_train(image_dataset):
    coord = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    env["LDT_METRICS_PATH"] = os.devnull
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD, image_dataset.uri, coord, str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = ["", ""]
    try:
        for i, p in enumerate(procs):
            outs[i], _ = p.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for i, p in enumerate(procs):
            try:
                outs[i], _ = p.communicate(timeout=10)
            except Exception:
                pass
        pytest.fail(
            "two-process train timed out (collective hang?): "
            + (outs[0] or "")[-1500:] + (outs[1] or "")[-1500:]
        )
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"proc{i} failed:\n{outs[i][-3000:]}"
    assert "proc0 OK" in outs[0]
    assert "proc1 OK" in outs[1]
