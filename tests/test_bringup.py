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


# -- the Moonlight cell's readers and its rehearsal ---------------------------

_STEP = "jit(step)/jvp(forward)/TransformerDecoder/layer_1/"
_BACK = "jit(step)/transpose(jvp(forward))/TransformerDecoder/layer_1/"
# op_name -> ps in one run of the step: a hand-made plane with the scopes
# the Moonlight layers name (models/transformer.py, models/moe.py)
_MOONLIGHT_OPS = {
    _STEP + "attention/attn/mla.project/query/dot_general": 2_000_000_000,
    _STEP + "attention/attn/mla.kernel/splash_mha_fwd": 20_000_000_000,
    _BACK + "attention/attn/mla.kernel/splash_mha_dkv": 40_000_000_000,
    _STEP + "moe/moe.shared/shared/gate/dot_general": 3_000_000_000,
    _STEP + "moe/moe.router/router/dot_general": 100_000_000,
    _STEP + "moe/moe.dispatch/sort": 400_000_000,
    _BACK + "moe/checkpoint/moe.experts/mul": 4_000_000_000,
    "ragged-dot-none": 12_000_000_000,
    _BACK + "moe/moe.combine/mul": 250_000_000,
    "jit(step)/jvp(forward)/TransformerDecoder/layer_0/mlp.dense/mlp/up/"
    "dot_general": 5_000_000_000,
    "jit(step)/optimizer/add": 1_000_000_000,
}
_MOONLIGHT_WANT = {  # ms a step, or the share the reader makes of them
    "mla_attention_ms": 62.0,
    "mla_kernel_roofline_pct": None,  # computed below from the flops file
    "moe_shared_ms": 3.0,
    "moe_local_routed_ms": 16.75,
    "moe_local_experts_roofline_pct": None,
    "moe_local_load_max_over_mean": 1.5,
}


def _moonlight_ctx(ops: dict) -> dict:
    """What ``benchmark/run.py`` hands a reader, around a plane with two
    runs of ``jit_step(7)`` whose operations are ``ops``."""
    import json

    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import run

    config = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "moonlight-16b-a3b-c4.json")))
    metadata = {1: {"name": "jit_step(7)", "tf_op": "", "program_id": None,
                    "category": ""}}
    events = []
    for i, (name, ps) in enumerate(ops.items(), start=2):
        metadata[i] = {"name": f"%op.{i}", "tf_op": name, "program_id": 7,
                       "category": ""}
        events.append((i, ps))
    run_ps = sum(ops.values()) + 1000
    plane_ops, at = [], 0
    for start in (0, run_ps):
        at = start
        for i, ps in events:
            plane_ops.append([i, at, ps])
            at += ps
    points = [{"t": t, "counters": {"moe_local_load_max": 1152.0,
                                    "moe_local_load_mean": 768.0}}
              for t in (10, 20, 30)]
    return {
        "_scope_plane": {"metadata": metadata, "ops": plane_ops,
                         "modules": [[1, 0, run_ps], [1, run_ps, run_ps]]},
        "cell": {"name": "c4-moonlight-ep8-prepacked-8k", "config": config},
        "chips": 1, "steps": 100, "window_ns": (10, 30),
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "flops": run.load_module("flops", "moonlight-16b-a3b-c4"),
        "counters": {"moe_local_assignments_total": 100 * 5 * 6144.0},
        "step_shapes": [{"input_ids": (1, 8192)}], "all_step_shapes": [],
        "log_points": points,
    }


@pytest.mark.parametrize("metric", sorted(_MOONLIGHT_WANT))
def test_moonlight_reader_reads_the_scopes_the_layers_name(metric):
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import run

    ctx = _moonlight_ctx(_MOONLIGHT_OPS)
    value = run.load_module("layer_metrics", metric).read(ctx)
    want = _MOONLIGHT_WANT[metric]
    model, flops = ctx["cell"]["config"]["model"], ctx["flops"]
    if metric == "mla_kernel_roofline_pct":
        want = 100 * flops.attention_flops(model, 1, 8192) / 197e12 / 0.060
    if metric == "moe_local_experts_roofline_pct":
        want = 100 * flops.expert_flops(model, 5 * 6144.0) / 197e12 / 0.016
    assert value == pytest.approx(want, rel=1e-6)
    assert 0 < value < 100 or metric.endswith("_ms") or "load" in metric
    # on a program without these scopes and counters (the parent): nothing,
    # and no error
    bare = _moonlight_ctx({"jit(step)/jvp(forward)/TransformerDecoder/"
                           "layer_0/attn/dot_general": 1_000_000})
    bare["counters"], bare["log_points"] = {}, [
        {"t": 20, "counters": {}}]
    assert run.load_module("layer_metrics", metric).read(bare) is None


def test_moonlight_cell_rehearses_end_to_end_on_the_cpu():
    """``benchmark/run.py``'s whole path for the cell at the tiny preset,
    untraced and traced: the generator, the model check against the
    reference under a share, ``train`` with ``--expert_share``, the log-point
    clock, the stop, the readers."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "rehearse.py"),
         "--cells", "c4-moonlight-ep8-prepacked-8k", "--checks", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "rehearsal ok"
    assert "moe_local_load_max_over_mean" in proc.stdout


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
