"""The epoch handover as ``train()`` drives it (trainer._train_loop with
data/placement.py): which epochs get a successor, and what a SIGTERM in the
middle of one leaves behind. The ring itself is pinned in
tests/test_placement.py, the phases and the step record across a boundary in
tests/test_phases.py. No assertion is on a duration."""

import threading

import pytest

from lance_distributed_training_tpu.data.placement import PlacedLoader
from lance_distributed_training_tpu.trainer import (
    TrainConfig,
    _loader_buffer_pool,
    train,
)


def _config(dataset, **kw):
    return TrainConfig(**{**dict(
        dataset_path=dataset.uri, num_classes=10, model_name="resnet18",
        image_size=32, batch_size=48, epochs=3, no_wandb=True, augment=False,
        eval_at_end=False, log_every=2, autotune=False, no_ddp=True), **kw})


def _ring_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith(("ldt-placement", "ldt-producer"))]


@pytest.mark.parametrize("kw,chained_epochs", [
    (dict(epochs=3), [0, 1]),  # never the last epoch
    (dict(epochs=3, max_steps=8), [0]),  # epoch 1 is where max_steps ends
    (dict(epochs=3, max_steps=5), []),  # ... even exactly at its end
    (dict(epochs=3, device_cache=True), []),  # a replay has no loader
    (dict(epochs=1), []),  # a one-epoch run chains nothing
])
def test_which_epochs_get_a_successor(image_dataset, monkeypatch, kw,
                                      chained_epochs):
    asked = []
    real = PlacedLoader.set_successor

    def set_successor(self, build):
        asked.append(build.keywords["epoch"] - 1)
        real(self, build)

    monkeypatch.setattr(PlacedLoader, "set_successor", set_successor)
    results = train(_config(image_dataset, **kw))
    assert asked == chained_epochs
    assert results["steps"] == kw.get("max_steps", 5 * kw["epochs"])
    assert not _ring_threads()


def test_sigterm_with_a_started_successor_leaves_nothing(image_dataset,
                                                         monkeypatch):
    """The benchmark's way out: SIGTERM (delivered for real, at the last
    step of an epoch whose ring is already reading the next) goes through
    the preemption path, which closes the epoch's iterator. No placement or
    producer thread of either epoch outlives ``train()`` and every lease of
    the process pool is back."""
    import gc

    from lance_distributed_training_tpu.utils import chaos

    config = _config(image_dataset)
    pool = _loader_buffer_pool(config)

    def outstanding():
        for _ in range(50):
            gc.collect()
            pool.sweep()
            stats = pool.stats()
            if not (stats["outstanding"] or stats["pending"]):
                break
        return stats["outstanding"] + stats["pending"]

    before = outstanding()  # what earlier tests of this process left, if any
    started = threading.Event()
    build = PlacedLoader.build_successor
    on_step = chaos.TrainerChaos.on_step

    def build_successor(self):
        loader = build(self)
        started.set()
        return loader

    def wait_then_on_step(self, steps_completed):
        # five batches an epoch: once the loop holds the fifth the thread
        # has read epoch 0 out and turns to epoch 1 whatever the loop does
        if steps_completed == 5:
            assert started.wait(timeout=60), "the successor never started"
        on_step(self, steps_completed)

    monkeypatch.setattr(PlacedLoader, "build_successor", build_successor)
    monkeypatch.setattr(chaos.TrainerChaos, "on_step", wait_then_on_step)
    monkeypatch.setenv(chaos.CHAOS_ENV, "sigterm@5")
    results = train(config)
    assert results["preempted"] is True and results["steps"] == 5
    assert not _ring_threads()
    assert outstanding() <= before
