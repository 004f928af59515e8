"""Placement-plane tests: bit-parity, lease lifecycle, stripe mapping, ZeRO.

Pins the r7 acceptance contracts:

* the async placement plane yields global arrays **bit-identical** to the
  synchronous ``make_global_batch`` path (same sharding, same bytes);
* fleet stripe→training-process assignment is deterministic, disjoint, and
  covering across process counts;
* ``BufferPool`` leases release at transfer dispatch (effectively
  transfer-complete, via the refcount sweep) — an abandoned iterator
  mid-ring strands nothing;
* ZeRO-1 (``zero_opt``) shards only the optimizer state over the data axis
  and trains bit-compatibly with the replicated path.
"""

import gc

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from lance_distributed_training_tpu.data import (
    ImageClassificationDecoder,
    PlacementPlane,
    make_train_pipeline,
)
from lance_distributed_training_tpu.data.buffers import BufferPool
from lance_distributed_training_tpu.fleet.balancer import members_for_process
from lance_distributed_training_tpu.obs.registry import MetricsRegistry
from lance_distributed_training_tpu.parallel import get_mesh, make_global_batch


def _batch(rng, rows=16, px=8):
    return {
        "image": rng.integers(0, 255, (rows, px, px, 3)).astype(np.uint8),
        "label": rng.integers(0, 10, rows).astype(np.int32),
    }


# -- per-device slicing + global assembly ------------------------------------


def test_place_batch_matches_make_global_batch_bitwise():
    mesh = get_mesh()
    assert len(jax.devices()) == 8  # conftest forced 8 CPU devices
    plane = PlacementPlane(mesh, registry=MetricsRegistry())
    batch = _batch(np.random.default_rng(0))
    placed = plane.place_batch(batch)
    ref = make_global_batch(batch, mesh)
    for key in batch:
        assert placed[key].shape == ref[key].shape
        assert placed[key].sharding == ref[key].sharding
        np.testing.assert_array_equal(
            np.asarray(placed[key]), np.asarray(ref[key])
        )
    # Explicitly per-device: 16 rows over 8 devices -> 2-row shards.
    assert placed["image"].sharding.spec == P("data")
    assert placed["image"].addressable_shards[0].data.shape[0] == 2


def test_place_batch_seq_axis_parity():
    mesh = get_mesh(seq_parallelism=2)
    plane = PlacementPlane(mesh, seq_axis="seq", registry=MetricsRegistry())
    tokens = {
        "tokens": np.random.default_rng(1).integers(
            0, 100, (8, 16)
        ).astype(np.int32)
    }
    placed = plane.place_batch(tokens)
    ref = make_global_batch(tokens, mesh, seq_axis="seq")
    assert placed["tokens"].sharding == ref["tokens"].sharding
    assert placed["tokens"].sharding.spec == P("data", "seq")
    np.testing.assert_array_equal(
        np.asarray(placed["tokens"]), np.asarray(ref["tokens"])
    )


def test_placed_stream_bit_identical_to_sync_path(image_dataset):
    """The acceptance pin: wrapping a host-batch pipeline in the plane
    yields the same batch sequence, bit for bit, as the reference
    function ``make_global_batch`` applied to the host batches of a second
    pipeline over the same plan."""
    mesh = get_mesh()
    decode = ImageClassificationDecoder(image_size=32)
    host = make_train_pipeline(image_dataset, "batch", 16, 0, 1, decode)
    second = make_train_pipeline(image_dataset, "batch", 16, 0, 1, decode)
    plane = PlacementPlane(mesh, registry=MetricsRegistry())
    placed_batches = list(plane.wrap(host))
    want_batches = [make_global_batch(b, mesh) for b in second]
    assert len(placed_batches) == len(want_batches) == len(host)
    for got, want in zip(placed_batches, want_batches):
        for key in want:
            assert got[key].sharding == want[key].sharding
            np.testing.assert_array_equal(
                np.asarray(got[key]), np.asarray(want[key])
            )


def test_placed_loader_delegates_len_set_epoch_and_counts(image_dataset):
    registry = MetricsRegistry()
    mesh = get_mesh()
    plane = PlacementPlane(mesh, registry=registry)
    inner = make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        ImageClassificationDecoder(image_size=32),
    )
    loader = plane.wrap(inner)
    assert len(loader) == len(inner)
    loader.set_epoch(3)  # DataPipeline has no set_epoch: must be a no-op
    n = sum(1 for _ in loader)
    assert n == len(inner)
    # Satellite telemetry: per-batch H2D histogram + ring-depth gauge.
    hist = registry.histogram("trainer_h2d_ms")
    assert hist.count == n
    assert registry.counter("placement_batches_placed").value == n
    text = registry.render_prometheus()
    assert "trainer_h2d_ms_bucket" in text
    assert "placement_buffer_depth" in text


def test_placed_iterator_propagates_decode_error(image_dataset):
    def bad_decode(table):
        raise RuntimeError("boom behind the plane")

    mesh = get_mesh()
    plane = PlacementPlane(mesh, registry=MetricsRegistry())
    inner = make_train_pipeline(image_dataset, "batch", 16, 0, 1, bad_decode)
    with pytest.raises(RuntimeError, match="boom behind the plane"):
        list(plane.wrap(inner))


# -- BufferPool lease lifecycle ----------------------------------------------


def _drain_pool(pool, rounds=50):
    """Sweep until jax's async-transfer references are dropped (CPU backend:
    a handful of GC passes at most)."""
    for _ in range(rounds):
        gc.collect()
        pool.sweep()
        stats = pool.stats()
        if stats["outstanding"] == 0 and stats["pending"] == 0:
            return stats
    return pool.stats()


def test_leases_release_on_transfer_dispatch_not_pickup():
    """The placement thread returns each host batch's leases right after
    dispatching its transfers — by the time the CONSUMER first touches a
    batch, its pages must already be back (outstanding only covers batches
    still upstream of placement)."""
    mesh = get_mesh()
    pool = BufferPool(registry=MetricsRegistry())
    plane = PlacementPlane(mesh, registry=MetricsRegistry(),
                           buffer_pool=pool, depth=1)
    rng = np.random.default_rng(2)

    def leased_batches(n):
        for _ in range(n):
            batch = {"image": pool.lease((8, 4, 4, 3), np.uint8),
                     "label": pool.lease((8,), np.int32)}
            batch["image"][...] = rng.integers(0, 255, (8, 4, 4, 3))
            batch["label"][...] = rng.integers(0, 10, 8)
            yield batch

    seen = 0
    for batch in plane.wrap(leased_batches(6)):
        seen += 1
        # depth=1 ring: upstream holds at most the batch being placed plus
        # the generator's in-flight one; everything older was released.
        assert pool.stats()["outstanding"] <= 2 * 2  # 2 leaves x 2 batches
        del batch
    assert seen == 6
    stats = _drain_pool(pool)
    assert stats["outstanding"] == 0 and stats["pending"] == 0
    assert stats["free"] > 0  # pages actually recycled, not dropped


def test_abandoned_iterator_mid_ring_leaks_nothing():
    """Consumer walks away after one batch with the ring full: teardown
    must drain the ring and return every lease (the no-leak satellite)."""
    mesh = get_mesh()
    pool = BufferPool(registry=MetricsRegistry())
    plane = PlacementPlane(mesh, registry=MetricsRegistry(),
                           buffer_pool=pool, depth=2)

    def leased_batches(n):
        rng = np.random.default_rng(3)
        for _ in range(n):
            page = pool.lease((8, 4, 4, 3), np.uint8)
            page[...] = rng.integers(0, 255, (8, 4, 4, 3))
            yield {"image": page}

    it = iter(plane.wrap(leased_batches(10)))
    first = next(it)
    assert isinstance(first["image"], jax.Array)
    it.close()  # abandon mid-ring: generator finally drains + joins
    del it, first
    stats = _drain_pool(pool)
    assert stats["outstanding"] == 0 and stats["pending"] == 0


# -- epoch handover: one ring across the epoch boundary ------------------------


def _ring_threads():
    import threading

    return [t.name for t in threading.enumerate()
            if t.name.startswith(("ldt-placement", "ldt-producer"))]


def _wait_for(cond, what, timeout=60.0):
    """Bounded wait for another thread to get somewhere; fails, never
    hangs, when it does not."""
    import time

    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


def _epoch_loaders(dataset, epochs, *, shuffle=False, depth=2, pool=None,
                   decode=None, registry=None, batch=16):
    """One cold loader an epoch, as the trainer's per-epoch rebuild makes
    them: a plane and a pipeline each."""
    mesh = get_mesh()
    decode = decode or ImageClassificationDecoder(image_size=32)
    registry = registry if registry is not None else MetricsRegistry()
    return [
        PlacementPlane(mesh, registry=registry, depth=depth,
                       buffer_pool=pool).wrap(make_train_pipeline(
                           dataset, "batch", batch, 0, 1, decode,
                           producers=2, shuffle=shuffle, seed=3, epoch=e,
                           buffer_pool=pool))
        for e in range(epochs)
    ]


def _chain(loaders):
    """Each loader's successor is the next; the last has none."""
    for a, b in zip(loaders, loaders[1:]):
        a.set_successor(lambda b=b: b)
    return loaders


def _host(batch):
    return {k: np.asarray(v) for k, v in batch.items()}


@pytest.mark.parametrize("shuffle", [False, True])
def test_chained_epochs_serve_what_cold_rebuilds_serve(image_dataset,
                                                       monkeypatch, shuffle):
    """Three epochs through one ring: batch k of every epoch is byte for
    byte what a cold loader of that epoch serves, the cursor at every step
    boundary is the unchained one (read-ahead into the next epoch is never
    part of it), the ring never holds more than its depth, and a resume
    from a mid-epoch cursor of the last epoch serves the chained tail."""
    depths = []
    real = PlacementPlane._set_depth
    monkeypatch.setattr(
        PlacementPlane, "_set_depth",
        lambda self, n: (depths.append(n), real(self, n))[1])
    cold = [[(_host(b), loader.state_dict()) for b in loader]
            for loader in _epoch_loaders(image_dataset, 3, shuffle=shuffle)]
    cold_ends = depths.count(0)
    chained = _chain(_epoch_loaders(image_dataset, 3, shuffle=shuffle))
    loader, got = chained[0], []
    while loader is not None:
        got.append([(_host(b), loader.state_dict()) for b in loader])
        assert loader.state_dict()["step"] == len(got[-1])
        loader = loader.take_successor()
    assert len(got) == 3 and [len(e) for e in got] == [len(e) for e in cold]
    for want_epoch, got_epoch in zip(cold, got):
        for (want, want_sd), (have, have_sd) in zip(want_epoch, got_epoch):
            assert have_sd == want_sd
            assert set(have) == set(want)
            for key in want:
                np.testing.assert_array_equal(have[key], want[key])
    if shuffle:  # the epochs differ, so the order above meant something
        assert any(
            not np.array_equal(a[0]["label"], b[0]["label"])
            for a, b in zip(got[0], got[1]))
    assert max(depths) <= 2  # one ring of depth 2, across both boundaries
    assert depths.count(0) - cold_ends >= 1  # ... reset when the chain ends
    assert [l.handover for l in chained][0] == "cold"
    assert not _ring_threads()

    resumed = _epoch_loaders(image_dataset, 3, shuffle=shuffle)[2]
    resumed.load_state_dict({"epoch": 2, "step": 4})
    tail = [_host(b) for b in resumed]
    assert len(tail) == len(got[2]) - 4
    for have, (want, _) in zip(tail, got[2][4:]):
        for key in want:
            np.testing.assert_array_equal(have[key], want[key])


def test_successor_is_placed_before_it_is_asked_for(image_dataset):
    """The point of the handover: by the time the consumer turns to the
    next epoch a batch of it is in the ring, through the same thread."""
    import threading

    first, second = _chain(_epoch_loaders(image_dataset, 2))
    seen = set()
    for _ in first:
        seen |= {t.ident for t in threading.enumerate()
                 if t.name == "ldt-placement"}
    assert first.handover == "cold"  # nothing had read it before
    handed = first._handed  # (marker, ring): the ring went on
    assert handed is not None and handed[0].successor is second
    _wait_for(lambda: not handed[1].q.empty(), "the successor's first batch")
    assert first.take_successor() is second
    assert first.take_successor() is None  # handed over once
    n = 0
    for _ in second:
        n += 1
        seen |= {t.ident for t in threading.enumerate()
                 if t.name == "ldt-placement"}
    assert second.handover == "warm"
    assert n == len(second)
    assert len(seen) == 1  # one placement thread served both epochs
    assert second.take_successor() is None  # no successor: the ring ended
    assert not _ring_threads()


def test_close_with_a_started_successor_leaks_nothing(image_dataset):
    """``close()`` one batch before the end of an epoch whose successor
    the ring is already reading: both epochs' threads end, every lease
    comes back, and the successor was never handed to anyone."""
    pool = BufferPool(registry=MetricsRegistry())
    first, second = _chain(_epoch_loaders(image_dataset, 2, pool=pool))
    it = iter(first)
    for _ in range(len(first) - 1):
        next(it)
    _wait_for(lambda: any(t.startswith("ldt-producer")
                          and t != "ldt-producer" for t in _ring_threads())
              and second.plane.counters.snapshot().get(
                  "placement_batches_placed", 0) >= 1,
              "the successor's producers and its first placed batch")
    it.close()
    assert not _ring_threads()
    assert first.take_successor() is None
    del it
    stats = _drain_pool(pool)
    assert stats["outstanding"] == 0 and stats["pending"] == 0


def test_dropped_successor_stops_the_ring(image_dataset):
    """A successor that was started and never consumed: dropping the
    loaders (as a loop that dies between two epochs does) stops the thread
    and returns the leases; no close() is owed."""
    pool = BufferPool(registry=MetricsRegistry())
    first, second = _chain(_epoch_loaders(image_dataset, 2, pool=pool))
    for _ in first:
        pass
    _wait_for(lambda: second.plane.counters.snapshot().get(
        "placement_batches_placed", 0) >= 1, "the successor's first batch")
    assert _ring_threads()
    del first, second
    gc.collect()
    _wait_for(lambda: not _ring_threads(), "the ring's threads to end")
    stats = _drain_pool(pool)
    assert stats["outstanding"] == 0 and stats["pending"] == 0


@pytest.mark.parametrize("fails", ["decode", "build"])
def test_successor_error_belongs_to_the_successor(image_dataset, fails):
    """An error while building or reading epoch e+1 never disturbs epoch
    e: a decode error is raised at the successor's first ``next``, a build
    error where the successor is asked for; either way no thread and no
    lease is left."""
    pool = BufferPool(registry=MetricsRegistry())

    def bad_decode(table):
        raise RuntimeError("boom in the next epoch")

    first, = _epoch_loaders(image_dataset, 1, pool=pool)
    if fails == "decode":
        second, = _epoch_loaders(image_dataset, 1, pool=pool,
                                 decode=bad_decode)
        first.set_successor(lambda: second)
    else:
        def build():
            raise RuntimeError("boom in the next epoch")

        first.set_successor(build)
    assert sum(1 for _ in first) == len(first)  # the whole epoch, untouched
    with pytest.raises(RuntimeError, match="boom in the next epoch"):
        next(iter(first.take_successor()))
    _wait_for(lambda: not _ring_threads(), "the ring's threads to end")
    stats = _drain_pool(pool)
    assert stats["outstanding"] == 0 and stats["pending"] == 0


# -- fleet stripe → process mapping ------------------------------------------


@pytest.mark.parametrize("n_members,n_procs", [
    (1, 1), (2, 1), (4, 2), (5, 2), (8, 3), (7, 4), (12, 8),
])
def test_members_for_process_disjoint_and_covering(n_members, n_procs):
    members = [{"server_id": f"s{i:02d}", "addr": f"h{i}:1"}
               for i in range(n_members)]
    slices = [members_for_process(members, p, n_procs)
              for p in range(n_procs)]
    # Deterministic: same inputs, same slices.
    assert slices == [members_for_process(members, p, n_procs)
                      for p in range(n_procs)]
    flat = [m["server_id"] for s in slices for m in s]
    # Disjoint and covering: every member served by exactly one process.
    assert sorted(flat) == sorted(m["server_id"] for m in members)
    assert len(set(flat)) == len(flat)
    # Balanced within one.
    sizes = [len(s) for s in slices]
    assert max(sizes) - min(sizes) <= 1


def test_members_for_process_fewer_members_than_processes():
    members = [{"server_id": "a", "addr": "a:1"},
               {"server_id": "b", "addr": "b:1"}]
    slices = [members_for_process(members, p, 4) for p in range(4)]
    # Every process still gets exactly one member (shared round-robin) and
    # every member is used by someone.
    assert all(len(s) == 1 for s in slices)
    assert {s[0]["server_id"] for s in slices} == {"a", "b"}


def test_members_for_process_stable_under_membership_growth():
    """Adding a member must not reshuffle other processes' members wholesale
    — slices stay contiguous in sorted-server_id order, so a join shifts at
    most the boundary members."""
    members = [{"server_id": f"s{i}", "addr": f"h{i}:1"} for i in range(6)]
    before = members_for_process(members, 0, 2)
    after = members_for_process(members + [
        {"server_id": "s9", "addr": "h9:1"}
    ], 0, 2)
    assert [m["server_id"] for m in before][:3] == ["s0", "s1", "s2"]
    assert [m["server_id"] for m in after][:3] == ["s0", "s1", "s2"]


# -- ZeRO-1 optimizer-state sharding ------------------------------------------


def test_zero_axis_shards_only_opt_state():
    import optax
    from flax.training import train_state

    from lance_distributed_training_tpu.parallel.sharding import (
        state_shardings,
    )

    class TS(train_state.TrainState):
        batch_stats: object = None

    params = {"dense": {"kernel": np.zeros((256, 256), np.float32),
                        "bias": np.zeros((256,), np.float32)}}
    state = TS.create(apply_fn=None, params=params, batch_stats=None,
                      tx=optax.sgd(0.1, momentum=0.9))
    mesh = get_mesh()
    shardings = state_shardings(
        jax.eval_shape(lambda: state), mesh, (), zero_axis="data",
    )
    kernel_opt = shardings.opt_state[0].trace["dense"]["kernel"]
    assert kernel_opt.spec == P("data")  # momentum sharded 1/8 per device
    assert shardings.params["dense"]["kernel"].spec == P()  # params replicated
    # Small leaves stay replicated (latency-bound collectives buy nothing).
    assert shardings.opt_state[0].trace["dense"]["bias"].spec == P()


def test_zero2_shards_gradient_accumulation():
    """ZeRO-2's persistent half: level 1 leaves the MultiSteps acc_grads
    buffer replicated (moments only), level 2 shards it too."""
    import optax
    from flax.training import train_state

    from lance_distributed_training_tpu.parallel.sharding import (
        state_shardings,
    )

    class TS(train_state.TrainState):
        batch_stats: object = None

    params = {"dense": {"kernel": np.zeros((256, 256), np.float32),
                        "bias": np.zeros((256,), np.float32)}}
    state = TS.create(
        apply_fn=None, params=params, batch_stats=None,
        tx=optax.MultiSteps(optax.sgd(0.1, momentum=0.9),
                            every_k_schedule=2),
    )
    mesh = get_mesh()
    abstract = jax.eval_shape(lambda: state)
    lvl1 = state_shardings(abstract, mesh, (), zero_axis="data",
                           zero_level=1)
    lvl2 = state_shardings(abstract, mesh, (), zero_axis="data",
                           zero_level=2)
    acc1 = lvl1.opt_state.acc_grads["dense"]["kernel"]
    acc2 = lvl2.opt_state.acc_grads["dense"]["kernel"]
    assert acc1.spec == P()           # ZeRO-1: grads buffer replicated
    assert acc2.spec == P("data")     # ZeRO-2: grads buffer sharded
    # Moments shard at BOTH levels; params replicated at both.
    assert lvl1.opt_state.inner_opt_state[0].trace["dense"]["kernel"].spec \
        == P("data")
    assert lvl2.params["dense"]["kernel"].spec == P()
    # Small leaves (bias, step counters) stay replicated everywhere.
    assert lvl2.opt_state.acc_grads["dense"]["bias"].spec == P()


def test_grad_partition_specs_mirror_state_policy():
    from lance_distributed_training_tpu.parallel.sharding import (
        grad_partition_specs,
    )

    mesh = get_mesh()
    params = {"dense": {"kernel": np.zeros((256, 256), np.float32),
                        "bias": np.zeros((256,), np.float32)}}
    specs = grad_partition_specs(params, mesh)
    assert specs["dense"]["kernel"] == P("data")
    assert specs["dense"]["bias"] == P()  # small leaf: replicated


@pytest.mark.slow
def test_zero2_trains_like_replicated(image_dataset, tmp_path):
    """The pinned ZeRO-2 parity run: gradient-accumulation sharding plus
    the in-step reduce-scatter constraint are pure re-layouts — the loss
    after N accumulated steps must match the unsharded run."""
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    common = dict(
        dataset_path=image_dataset.uri, num_classes=10, image_size=32,
        batch_size=16, epochs=1, max_steps=4, no_wandb=True,
        eval_at_end=False, log_every=0, model_name="resnet18",
        optimizer="adamw", lr=0.001, grad_accum=2,
    )
    base = train(TrainConfig(**common))
    zero2 = train(TrainConfig(**common, zero_opt=2))
    assert zero2["loss"] == pytest.approx(base["loss"], rel=1e-5)


def test_zero_level_validation(tmp_path):
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    with pytest.raises(ValueError, match="zero_opt must be"):
        train(TrainConfig(dataset_path=str(tmp_path / "missing"),
                          zero_opt=3))


@pytest.mark.slow
def test_zero_opt_trains_like_replicated(image_dataset, tmp_path):
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    common = dict(
        dataset_path=image_dataset.uri, num_classes=10, image_size=32,
        batch_size=16, epochs=1, max_steps=3, no_wandb=True,
        eval_at_end=False, log_every=0, model_name="resnet18",
        optimizer="adamw", lr=0.001,
    )
    base = train(TrainConfig(**common))
    zero = train(TrainConfig(**common, zero_opt=True))
    assert zero["loss"] == pytest.approx(base["loss"], rel=1e-5)


def test_zero_and_fsdp_mutually_exclusive(tmp_path):
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    with pytest.raises(ValueError, match="mutually exclusive"):
        train(TrainConfig(dataset_path=str(tmp_path / "missing"),
                          fsdp=True, zero_opt=True))
