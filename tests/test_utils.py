"""Checkpoint + metrics utility tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lance_distributed_training_tpu.models import get_task
from lance_distributed_training_tpu.trainer import TrainConfig, create_train_state
from lance_distributed_training_tpu.utils import MetricLogger, StepTimer
from lance_distributed_training_tpu.utils.checkpoint import CheckpointManager


def test_checkpoint_save_restore_roundtrip(tmp_path):
    task = get_task("classification", num_classes=3, model_name="resnet18",
                    image_size=32)
    cfg = TrainConfig(dataset_path="", num_classes=3)
    state = create_train_state(jax.random.key(0), task, cfg)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2)
    assert mgr.latest_step() is None
    mgr.save(5, state, wait=True)
    assert mgr.latest_step() == 5

    fresh = create_train_state(jax.random.key(1), task, cfg)  # different init
    restored = mgr.restore(fresh)
    a = jax.tree_util.tree_leaves(state.params)[0]
    b = jax.tree_util.tree_leaves(restored.params)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    mgr.close()


def test_checkpoint_max_to_keep(tmp_path):
    task = get_task("classification", num_classes=2, model_name="resnet18",
                    image_size=32)
    cfg = TrainConfig(dataset_path="", num_classes=2)
    state = create_train_state(jax.random.key(0), task, cfg)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2)
    for step in (1, 2, 3):
        mgr.save(step, state, wait=True)
    assert mgr.latest_step() == 3
    assert set(mgr.manager.all_steps()) == {2, 3}
    mgr.close()


def test_step_timer_stall_pct():
    t = StepTimer()
    import time

    t.loader_start(); time.sleep(0.02); t.loader_stop()
    t.step_start(); time.sleep(0.02); t.step_stop()
    assert 20 < t.loader_stall_pct < 80
    assert t.images_per_sec(10) > 0


def test_metric_logger_jsonl_fallback(tmp_path, monkeypatch):
    import json
    import sys

    monkeypatch.setitem(sys.modules, "wandb", None)  # force import failure
    path = tmp_path / "m.jsonl"
    logger = MetricLogger(enabled=True, jsonl_path=str(path))
    logger.log({"loss": 1.5, "epoch": 0}, step=0)
    logger.finish()
    rec = json.loads(path.read_text().strip())
    assert rec["loss"] == 1.5 and rec["step"] == 0


class TestCompileCache:
    """maybe_enable_compile_cache: whoever launches the process places the
    cache; the code sets a directory only when nobody did."""

    @staticmethod
    def _recorded_updates(monkeypatch):
        import lance_distributed_training_tpu.trainer as tm

        calls = {}
        monkeypatch.setattr(
            tm.jax.config, "update", lambda k, v: calls.__setitem__(k, v)
        )
        return tm, calls

    def test_variable_set_means_no_directory_set_in_code(self, monkeypatch,
                                                         tmp_path):
        tm, calls = self._recorded_updates(monkeypatch)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        for platform in ("tpu", "cpu"):
            # Reports what JAX itself holds; sets nothing on either platform.
            assert tm.maybe_enable_compile_cache(platform) == (
                tm.jax.config.jax_compilation_cache_dir
            )
        assert tm.maybe_enable_compile_cache("tpu", enabled=False) == (
            tm.jax.config.jax_compilation_cache_dir
        )
        assert calls == {}

    def test_unset_on_tpu_is_the_checkout_cache(self, monkeypatch):
        import os

        tm, calls = self._recorded_updates(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        expected = os.path.join(repo, ".jax_cache")
        assert tm.maybe_enable_compile_cache("tpu") == expected
        assert calls == {"jax_compilation_cache_dir": expected}
        # A fixed path: the same on every call, nothing of pid or time.
        assert tm.maybe_enable_compile_cache("tpu") == expected

    def test_unset_on_cpu_is_none(self, monkeypatch):
        tm, calls = self._recorded_updates(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert tm.maybe_enable_compile_cache("cpu") is None
        assert tm.maybe_enable_compile_cache("tpu", enabled=False) is None
        assert calls == {}

    def test_test_process_itself_runs_uncached(self):
        """conftest drops the variable before importing jax, so the tier-1
        process never round-trips XLA:CPU executables through a cache."""
        import os

        import jax

        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
        assert jax.config.jax_compilation_cache_dir is None

    def test_train_config_has_no_cache_dir_knob(self):
        import dataclasses

        from lance_distributed_training_tpu.cli import build_parser
        from lance_distributed_training_tpu.trainer import TrainConfig

        fields = {f.name for f in dataclasses.fields(TrainConfig)}
        assert "compile_cache_dir" not in fields
        with pytest.raises(SystemExit):
            build_parser().parse_args(
                ["--dataset_path", "/d", "--compile_cache_dir", "/x"]
            )
