"""Process set-up around ``train()``: nothing on the chip path may hide
which device it ran on, fall back without saying so, or claim a backend
from a process that is not the chip's holder. CPU-only twins of what
``chip_smoke.py`` checks on the TPU."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def root_scripts(monkeypatch):
    """The repo root on sys.path, so the root scripts import by name."""
    monkeypatch.syspath_prepend(REPO)


def _python(code: str, *args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code, *args], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )


# -- flash attention: chosen by platform, never by exception -----------------


def _no_pallas(monkeypatch):
    # A None entry makes `from jax.experimental.pallas.ops.tpu import ...`
    # raise ImportError, as on an install without the kernel module.
    monkeypatch.setitem(sys.modules, "jax.experimental.pallas.ops.tpu", None)


def test_flash_on_tpu_without_pallas_raises(monkeypatch):
    import jax

    from lance_distributed_training_tpu.ops.flash import make_flash_attention

    _no_pallas(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ImportError):
        make_flash_attention()


def test_flash_off_tpu_is_dense_and_never_imports_pallas(monkeypatch):
    import jax

    from lance_distributed_training_tpu.models.transformer import (
        dot_product_attention,
    )
    from lance_distributed_training_tpu.ops.flash import make_flash_attention

    _no_pallas(monkeypatch)
    assert jax.default_backend() == "cpu"
    attention = make_flash_attention()
    q, k, v = (
        jax.random.normal(jax.random.key(i), (1, 2, 8, 4)) for i in range(3)
    )
    np.testing.assert_array_equal(
        np.asarray(attention(q, k, v)),
        np.asarray(dot_product_attention(q, k, v, dtype=q.dtype)),
    )


def test_platforms_other_than_tpu_are_not_tpu(monkeypatch):
    """The old alias list is gone: only jax's own "tpu" selects Pallas."""
    import jax

    from lance_distributed_training_tpu.ops import flash

    assert not hasattr(flash, "_TPU_PLATFORMS")
    _no_pallas(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    flash.make_flash_attention()  # dense arm: no import attempted


def test_flash_kernel_runs_per_device_tile_under_shard_map(monkeypatch):
    """XLA cannot partition a Mosaic kernel (PR 21's four-chip run died on
    "wrap the call in a shard_map", then on model.init's batch of 1, then
    on check_vma), so over a mesh the kernel must see one device's tile.
    The real Pallas kernel, in TPU interpret mode on the CPU mesh: forward
    and gradient equal global dense attention, a batch the data axis does
    not divide still runs the kernel, and init traces."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lance_distributed_training_tpu.models import get_task
    from lance_distributed_training_tpu.models.transformer import (
        dot_product_attention,
    )
    from lance_distributed_training_tpu.ops.flash import make_flash_attention
    from lance_distributed_training_tpu.parallel import get_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # Two devices: the interpreter's cross-device machinery stalls on the
    # full 8-device test mesh, and two already make XLA refuse the bare call.
    mesh = get_mesh(jax.devices()[:2])
    attention = make_flash_attention(mesh=mesh)
    batch, heads, seq, dim = 2 * mesh.shape["data"], 2, 128, 64
    q, k, v = (
        jax.random.normal(jax.random.key(i), (batch, heads, seq, dim),
                          jnp.float32)
        for i in range(3)
    )
    valid = np.ones((batch, 1, 1, seq), bool)
    valid[:, :, :, seq // 2:] = False  # the back half of every row is padding
    live = slice(0, seq // 2)  # padding queries' outputs are dead

    def dense(q):
        return dot_product_attention(q, k, v, mask=valid, dtype=q.dtype)

    sharded = NamedSharding(mesh, P("data"))
    with pltpu.force_tpu_interpret_mode():
        out = jax.jit(attention, in_shardings=(sharded,) * 4)(q, k, v, valid)
        grad = jax.jit(jax.grad(
            lambda q: attention(q, k, v, valid)[:, :, live].sum()))(q)
        one = jax.jit(attention)(q[:1], k[:1], v[:1], valid[:1])
    np.testing.assert_allclose(np.asarray(out)[:, :, live],
                               np.asarray(dense(q))[:, :, live], atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grad),
        np.asarray(jax.grad(lambda q: dense(q)[:, :, live].sum())(q)),
        atol=1e-5)
    np.testing.assert_allclose(np.asarray(one)[:, :, live],
                               np.asarray(dense(q))[:1, :, live], atol=1e-5)
    # The trainer's init pass (a batch of 1) traces through the wrapper.
    task = get_task("masked_lm", model_name="bert_small", vocab_size=200,
                    seq_len=seq, attention_fn=attention)
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
    assert "params" in shapes


# -- --backend tpu -----------------------------------------------------------


def test_backend_tpu_on_cpu_process_exits_naming_platform():
    import lance_distributed_training_tpu.cli as cli

    with pytest.raises(SystemExit) as err:
        cli.main(["--dataset_path", "/nonexistent", "--backend", "tpu",
                  "--no_wandb"])
    assert "platform='cpu'" in str(err.value)


def test_backend_tpu_rejects_any_other_accelerator(monkeypatch):
    import jax

    import lance_distributed_training_tpu.cli as cli

    class _Dev:
        platform = "gpu"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    with pytest.raises(SystemExit, match="platform='gpu'"):
        cli.main(["--dataset_path", "/nonexistent", "--backend", "tpu",
                  "--no_wandb"])


# -- one process for each chip ----------------------------------------------


def test_importing_the_package_initialises_no_backend():
    """Decode workers and ``serve-data`` import the package (and with it
    jax) beside the process that holds the chip: the import itself must
    never claim a device."""
    proc = _python(
        "import lance_distributed_training_tpu\n"
        "import lance_distributed_training_tpu.cli\n"
        "import lance_distributed_training_tpu.data.workers\n"
        "import lance_distributed_training_tpu.service.server\n"
        "import chip_smoke\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_serve_data_serves_without_a_backend(image_dataset):
    """The data service decodes and streams a batch and still has no JAX
    backend: on the trainer's host it stays off the chip by construction,
    not by the caller remembering JAX_PLATFORMS=cpu."""
    proc = _python(
        "import sys\n"
        "from lance_distributed_training_tpu.service.client import "
        "RemoteLoader\n"
        "from lance_distributed_training_tpu.service.server import "
        "DataService, ServeConfig\n"
        "svc = DataService(ServeConfig(dataset_path=sys.argv[1], "
        "host='127.0.0.1', port=0, image_size=32, num_workers=1)).start()\n"
        "try:\n"
        "    batch = next(iter(RemoteLoader(f'127.0.0.1:{svc.port}', 16, 0, "
        "1)))\n"
        "finally:\n"
        "    svc.stop()\n"
        "assert batch['image'].shape == (16, 32, 32, 3)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n",
        str(image_dataset.uri),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- the benchmark's recorded fixtures ----------------------------------------


@pytest.mark.parametrize("script,closing", [
    ("check_reduce.py", "reduction ok"),
    ("check_scopes.py", "scopes ok"),
])
def test_benchmark_fixture_check_passes(script, closing):
    """The reducers the ledger's per-layer numbers come from still read
    their recorded traces as they did when the fixtures were taken."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", script)], cwd=REPO,
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == closing


# -- chip_smoke.py -----------------------------------------------------------


def test_chip_smoke_fails_at_once_without_an_accelerator():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "platform='cpu'" in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


def test_chip_smoke_main_path_at_toy_size(tmp_path, capsys, root_scripts):
    """The script's own checks, run on the CPU mesh at a toy width: the
    digest comparison, the shard and replication checks, and that
    ``train()`` names its device first in its log and in its result."""
    import jax

    import chip_smoke

    report = chip_smoke.main_path(
        str(tmp_path), backend="cpu", model_name="resnet18", image_size=32,
        per_device_batch=2, steps=2,
    )
    n = jax.device_count()
    assert report["devices"] == n and report["global_batch"] == 2 * n
    assert len(report["losses"]) == 2 and len(report["sync"]) == 2
    out = capsys.readouterr().out
    first_metrics = next(
        line for line in out.splitlines() if line.startswith("[metrics]"))
    assert (f"platform=cpu, device_kind=cpu, device_count={n}"
            in first_metrics)
    assert "device batch == host batch for all 2 steps" in out
    assert f"one 2-row shard on each of {n} devices" in out


def test_chip_smoke_check_fails_loudly(root_scripts):
    import chip_smoke

    with pytest.raises(chip_smoke.CheckFailed, match="digests differ"):
        chip_smoke.check(False, "digests differ")
