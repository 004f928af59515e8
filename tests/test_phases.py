"""Phase spans, compile spans and step scopes (obs/spans.py, trainer.py,
data/placement.py). Every assertion is on counts, names, parent ids and
equalities of recorded times; none on how long anything took."""

import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lance_distributed_training_tpu.obs import default_registry
from lance_distributed_training_tpu.obs import spans as spans_mod
from lance_distributed_training_tpu.obs.spans import SpanTracer

LOOP_NAMES = {
    "startup.devices", "startup.dataset", "startup.state", "startup.restore",
    "startup.loader", "train.loader", "train.transform",
    "train.step", "train.drain", "train.log", "train.bookkeep",
    "train.epoch_end", "train.epoch_start", "train.shutdown",
}
PLACEMENT_NAMES = {"placement.wait_input", "placement.h2d",
                   "placement.wait_ring"}


@pytest.fixture()
def tracer(monkeypatch):
    """A fresh process-wide tracer with room for a whole tiny run."""
    fresh = SpanTracer(capacity=1 << 16)
    monkeypatch.setattr(spans_mod, "_DEFAULT", fresh)
    return fresh


def by_thread(spans, names):
    threads = {}
    for s in spans:
        if s.name in names:
            threads.setdefault(s.thread_id, []).append(s)
    return [sorted(own, key=lambda s: (s.start_ns, s.end_ns))
            for own in threads.values()]


def assert_tiles(own):
    for a, b in zip(own, own[1:]):
        assert a.end_ns == b.start_ns, (a.name, b.name)


def test_phases_of_one_thread_tile_exactly():
    tr = SpanTracer()
    for name in ("a", "b", "c"):
        tr.phase(name, n=1)
    tr.end_phase()
    tr.end_phase()  # nothing open: a no-op
    a, b, c = tr.spans()
    assert [s.name for s in (a, b, c)] == ["a", "b", "c"]
    assert a.end_ns == b.start_ns and b.end_ns == c.start_ns
    assert {s.parent_id for s in (a, b, c)} == {0}
    assert a.attrs == {"n": 1}


def test_span_inside_a_phase_has_it_as_parent_per_thread():
    tr = SpanTracer()
    tr.phase("outer")
    with tr.span("inner"):
        with tr.span("innermost"):
            pass
    seen = []

    def other():
        with tr.span("elsewhere"):  # another thread: no phase, so a root
            pass
        seen.append(True)

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=30)
    assert seen
    tr.phase("next")
    with tr.span("later"):
        pass
    tr.end_phase()
    got = {s.name: s for s in tr.spans()}
    assert got["inner"].parent_id == got["outer"].span_id
    assert got["innermost"].parent_id == got["inner"].span_id
    assert got["elsewhere"].parent_id == 0
    assert got["later"].parent_id == got["next"].span_id
    assert got["outer"].end_ns == got["next"].start_ns


def test_phases_tile_on_every_thread_under_contention():
    """More threads than cores switching phases at once, with a short switch
    interval: each thread still tiles on its own, ids stay unique."""
    import sys

    tr = SpanTracer(capacity=1 << 16)
    workers, switches = 16, 150
    barrier = threading.Barrier(workers)

    def work(k):
        barrier.wait(timeout=30)
        for i in range(switches):
            tr.phase(f"t{k}.{i % 3}", k=k)
            if i % 7 == 0:
                with tr.span(f"inner{k}"):
                    pass
        tr.end_phase()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    got = tr.spans()
    assert len({s.span_id for s in got}) == len(got)
    phases = {s.span_id: s for s in got if s.name.startswith("t")}
    for k in range(workers):
        own = sorted((s for s in phases.values() if s.attrs["k"] == k),
                     key=lambda s: (s.start_ns, s.end_ns, s.span_id))
        assert len(own) == switches
        assert len({s.thread_id for s in own}) == 1
        assert_tiles(own)
        inner = [s for s in got if s.name == f"inner{k}"]
        assert len(inner) == len(range(0, switches, 7))
        assert all(phases[s.parent_id].attrs["k"] == k for s in inner)


def test_record_complete_keeps_its_times_and_finds_its_parent(tmp_path):
    path = tmp_path / "spans.jsonl"
    tr = SpanTracer(jsonl_path=str(path))
    tr.record_complete("root.before", 5, 9)
    attrs = tr.phase("holder")
    attrs["late"] = True  # attrs known mid-phase, as span() allows
    tr.record_complete("xla.compile", 1_000, 4_000, fun_name="f")
    tr.end_phase()
    tr.close()
    got = {s.name: s for s in tr.spans()}
    assert (got["xla.compile"].start_ns, got["xla.compile"].end_ns) == (
        1_000, 4_000)
    assert got["xla.compile"].parent_id == got["holder"].span_id
    assert got["xla.compile"].attrs == {"fun_name": "f"}
    assert got["root.before"].parent_id == 0
    assert got["holder"].attrs == {"late": True}
    names = [line.split('"name": "')[1].split('"')[0]
             for line in path.read_text().splitlines()]
    assert names == ["ldt.clock_sync", "root.before", "xla.compile", "holder"]


def test_compile_raises_counter_and_leaves_span_under_its_phase(tracer):
    spans_mod.watch_xla_compiles()
    spans_mod.watch_xla_compiles()  # registers once a process
    counter = default_registry().counter("xla_compiles_total")
    seconds = default_registry().counter("xla_compile_seconds_total")
    before, before_s = counter.value, seconds.value

    def never_seen_before(x):
        return x * 3 + 1

    tracer.phase("somewhere")
    jax.jit(never_seen_before)(jnp.arange(7.0)).block_until_ready()
    tracer.end_phase()
    got = tracer.spans()
    holder = next(s for s in got if s.name == "somewhere")
    compiles = [s for s in got if s.name == "xla.compile"
                and "never_seen_before" in s.attrs["fun_name"]]
    assert len(compiles) == 1
    assert compiles[0].parent_id == holder.span_id
    assert holder.start_ns <= compiles[0].end_ns <= holder.end_ns
    assert counter.value - before == sum(
        1 for s in got if s.name == "xla.compile")
    assert seconds.value > before_s


def _hlo_of_step(task, batch):
    from lance_distributed_training_tpu.parallel import (
        get_mesh,
        make_global_batch,
    )
    from lance_distributed_training_tpu.trainer import (
        TrainConfig,
        create_train_state,
        make_train_step,
    )

    mesh = get_mesh(jax.devices()[:1])
    state = create_train_state(jax.random.key(0), task,
                               TrainConfig(dataset_path=""))
    step = make_train_step(task, mesh, donate=False)
    return step.lower(state, make_global_batch(batch, mesh),
                      jax.random.key(1)).compile().as_text()


@pytest.mark.parametrize("task_type", ["classification", "masked_lm"])
def test_compiled_step_carries_the_three_scopes(task_type):
    from lance_distributed_training_tpu.models import get_task

    gen = np.random.default_rng(0)
    if task_type == "classification":
        task = get_task("classification", num_classes=4,
                        model_name="resnet18", image_size=16)
        batch = {"image": gen.integers(0, 255, (2, 16, 16, 3), np.uint8),
                 "label": gen.integers(0, 4, 2).astype(np.int32)}
    else:
        task = get_task("masked_lm", model_name="bert_small", vocab_size=64,
                        seq_len=8)
        batch = {"input_ids": gen.integers(5, 64, (2, 8)).astype(np.int32),
                 "attention_mask": np.ones((2, 8), np.int8)}
    names = set(re.findall(r'op_name="([^"]*)"', _hlo_of_step(task, batch)))
    for scope in ("jvp(forward)", "transpose(jvp(forward))", "optimizer"):
        assert any(scope in n.split("/") for n in names), scope
    # the masked-LM task applies its head and its loss to the masked
    # positions inside the forward, under a scope of its own
    if task_type == "classification":
        assert any("loss" in n for n in names)
    else:  # and computes the head's gradients there with its value, inside
        # the one conditional: the backward products read ``transpose(``
        # further down their name, which is what a trace's phases go by
        head = [n for n in names if "mlm_head" in n.split("/")]
        assert any("transpose(" not in n for n in head)
        assert any("transpose(" in n and "dot_general" in n for n in head)
    # flax's module names ride under the scope: the blocks are named
    assert any(re.search(r"jvp\(forward\)/\w+/\w+", n) for n in names)


def _image_run_config(path, **kw):
    from lance_distributed_training_tpu.trainer import TrainConfig

    return TrainConfig(**{**dict(
        dataset_path=path.uri, num_classes=10, model_name="resnet18",
        image_size=32, batch_size=48, epochs=2, no_wandb=True, augment=False,
        eval_at_end=False, log_every=2, autotune=False, no_ddp=True), **kw})


def test_train_leaves_loop_and_placement_threads_tiled(tracer, image_dataset):
    from lance_distributed_training_tpu.trainer import train

    main = threading.get_ident() % 2**31
    results = train(_image_run_config(image_dataset))
    assert results["steps"] == 10  # 240 rows: 5 batches of 48, twice
    got = tracer.spans()
    assert tracer.dropped == 0

    (loop,) = by_thread(got, LOOP_NAMES)
    assert {s.thread_id for s in loop} == {main}
    assert {s.parent_id for s in loop} == {0}
    assert_tiles(loop)
    names = [s.name for s in loop]
    assert not [s.name for s in got if s.thread_id == main
                and s.name.startswith(("train.", "startup."))
                and s.name not in LOOP_NAMES]
    assert names[:5] == ["startup.devices", "startup.dataset",
                         "startup.state", "startup.loader", "train.loader"]
    assert names[-2:] == ["train.epoch_end", "train.shutdown"]
    # one turnover between the two epochs: end, start, the first wait
    assert names.count("train.epoch_end") == 2
    assert names.count("train.epoch_start") == 1
    at = names.index("train.epoch_start")
    assert names[at - 1] == "train.epoch_end"
    assert names[at + 1] == "train.loader"
    assert loop[at + 1].attrs["epoch_step"] == 0
    assert loop[at].attrs["epoch"] == 1
    assert names.count("train.step") == 10
    assert names.count("train.loader") == 12  # 10 batches, 2 exhaustions
    assert names.count("train.log") == 5 and names.count("train.drain") == 5
    assert [s.attrs["step"] for s in loop if s.name == "train.step"] == list(
        range(10))
    # the step's compile happened inside the first train.step phase
    first_step = next(s for s in loop if s.name == "train.step")
    assert [s for s in got if s.name == "xla.compile"
            and s.parent_id == first_step.span_id
            and "step" in s.attrs["fun_name"]]

    # one placement thread for the run: the first epoch's ring goes on to
    # the second's loader, so its phases tile across the boundary, from the
    # first wait to the last marker's put, with no end and restart between
    (own,) = by_thread(got, PLACEMENT_NAMES)
    assert main not in {s.thread_id for s in own}
    assert_tiles(own)
    assert own[0].name == "placement.wait_input"
    assert own[-1].name == "placement.wait_ring"
    assert [s.attrs["batch_seq"] for s in own
            if s.name == "placement.h2d"] == 2 * list(range(5))
    # producers: every decoded batch is followed by its hand-over
    decode = [s for s in got if s.name == "pipeline.decode"]
    waits = [s for s in got if s.name == "pipeline.wait_out"]
    assert len(decode) == len(waits) == 10


def _ring_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith(("ldt-placement", "ldt-producer"))]


def _hold_epoch_lines_until_placed(monkeypatch, epochs):
    """Make "warm" a fact and not a race: an epoch's metrics line (written
    on the loop thread between the boundary marker and the next epoch's
    first next) waits, bounded, until the ring holds a batch. Only the
    next epoch's can be in it by then. The last epoch has no successor."""
    import time

    from lance_distributed_training_tpu.utils.metrics import MetricLogger

    depth = default_registry().gauge("placement_buffer_depth")
    real = MetricLogger.log

    def log(self, entry, *a, **kw):
        if "epoch_time" in entry and entry["epoch"] < epochs - 1:
            deadline = time.monotonic() + 60
            while depth.value < 1:
                assert time.monotonic() < deadline, "no successor batch"
                time.sleep(0.005)
        return real(self, entry, *a, **kw)

    monkeypatch.setattr(MetricLogger, "log", log)


@pytest.mark.parametrize("shuffle", [False, True])
def test_handover_keeps_the_steps_and_warms_every_later_epoch(
        tracer, image_dataset, tmp_path, monkeypatch, shuffle):
    """Three epochs: the per-step record (step, batch hash, loss) is the
    one the per-epoch cold rebuild gives; at every boundary the loop passes
    epoch_end, epoch_start and the first loader wait, in that order and
    tiling; one placement thread tiles across both boundaries; the first
    epoch starts cold and every later one warm."""
    from lance_distributed_training_tpu.data.placement import PlacedLoader
    from lance_distributed_training_tpu.trainer import train
    from lance_distributed_training_tpu.utils import chaos

    def run(name):
        monkeypatch.setenv(chaos.TRACE_ENV, str(tmp_path / name))
        results = train(_image_run_config(image_dataset, epochs=3,
                                          shuffle=shuffle, seed=5))
        monkeypatch.delenv(chaos.TRACE_ENV)
        assert not _ring_threads()
        return results, chaos.read_trace(str(tmp_path / name))

    with monkeypatch.context() as cold:
        # the parent's behaviour: no loader ever gets a successor, so every
        # epoch rebuilds and starts its own ring
        cold.setattr(PlacedLoader, "set_successor", lambda self, build: None)
        rebuilt, want = run("rebuilt.jsonl")
    assert [h["epoch_handover"] for h in rebuilt["history"]] == 3 * ["cold"]

    counters = {state: default_registry().counter(
        f"epoch_handover_{state}_total") for state in ("warm", "cold")}
    before = {state: c.value for state, c in counters.items()}
    _hold_epoch_lines_until_placed(monkeypatch, epochs=3)
    tracer.clear()
    results, got = run("chained.jsonl")
    assert len(got) == 15 and got == want
    assert [t["epoch"] for t in got] == [0] * 5 + [1] * 5 + [2] * 5
    assert results["loss"] == rebuilt["loss"]
    assert [h["epoch_handover"] for h in results["history"]] == [
        "cold", "warm", "warm"]
    assert {state: c.value - before[state]
            for state, c in counters.items()} == {"cold": 1, "warm": 2}

    spans = tracer.spans()
    (loop,) = by_thread(spans, LOOP_NAMES)
    assert_tiles(loop)
    names = [s.name for s in loop]
    boundaries = [i for i, n in enumerate(names) if n == "train.epoch_start"]
    assert [loop[i].attrs["epoch"] for i in boundaries] == [1, 2]
    for i in boundaries:
        assert names[i - 1:i + 2] == ["train.epoch_end", "train.epoch_start",
                                      "train.loader"]
        assert loop[i + 1].attrs["epoch_step"] == 0
    (own,) = by_thread(spans, PLACEMENT_NAMES)
    assert_tiles(own)
    assert [s.attrs["batch_seq"] for s in own
            if s.name == "placement.h2d"] == 3 * list(range(5))


def test_train_that_raises_leaves_no_phase_open(tracer, tmp_path):
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    with pytest.raises(FileNotFoundError):
        train(TrainConfig(dataset_path=str(tmp_path / "absent"),
                          no_wandb=True))
    names = [s.name for s in tracer.spans()
             if s.name.startswith("startup.")]
    assert names == ["startup.devices", "startup.dataset"]
    count = len(tracer.spans())
    tracer.end_phase()  # nothing was left open for this to close
    assert len(tracer.spans()) == count


def test_probe_compiles_nothing_for_a_second_grid_shape(tracer, tmp_path):
    """The sampled device-time probe awaits the packed leaf: no slice, no
    squeeze, no program of its own, whatever the grid's shape."""
    from lance_distributed_training_tpu.data.authoring import (
        create_variable_length_token_dataset,
    )
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    path = tmp_path / "toks"
    create_variable_length_token_dataset(
        str(path), rows=544, vocab_size=64, max_len=32, mean_len=8)
    shapes = default_registry().counter("pack_new_shapes_total")
    sampled = default_registry().histogram("pack_device_ms")
    shapes_before, sampled_before = shapes.value, sampled.count
    train(TrainConfig(
        dataset_path=str(path), task_type="masked_lm",
        model_name="bert_small", vocab_size=64, seq_len=32, batch_size=32,
        epochs=1, no_wandb=True, eval_at_end=False, token_pack=True,
        pack_rows_multiple=2, log_every=0, autotune=False, no_ddp=True))
    assert shapes.value - shapes_before >= 2  # more than one grid met
    assert sampled.count - sampled_before >= 1  # batch 16 was probed
    got = tracer.spans()
    transforms = {s.span_id for s in got if s.name == "train.transform"}
    assert len(transforms) == 17
    compiled = {s.attrs["fun_name"] for s in got if s.name == "xla.compile"
                and s.parent_id in transforms}
    assert any("pack_token_batch" in name for name in compiled)
    assert not [name for name in compiled
                if "slice" in name or "squeeze" in name], compiled
