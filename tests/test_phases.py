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
    # a compile's parent is the innermost span it happened in: since PR 35
    # the transform's dispatch and its sampled await inside the phase
    inside = transforms | {s.span_id for s in got
                           if s.name.startswith("loop.transform_")
                           and s.parent_id in transforms}
    compiled = {s.attrs["fun_name"] for s in got if s.name == "xla.compile"
                and s.parent_id in inside}
    assert any("pack_token_batch" in name for name in compiled)
    assert not [name for name in compiled
                if "slice" in name or "squeeze" in name], compiled


# -- the calls inside the phases, steps in flight, slow intervals (PR 35) -----

LOOP_CALLS = {
    "train.bookkeep": {"loop.rng_split", "loop.loss_sum", "loop.stats_add",
                       "loop.stats_pack", "loop.cursor"},
    "train.transform": {"loop.transform_dispatch", "loop.transform_await"},
    "train.log": {"loop.log_entry", "loop.log_lr", "loop.stats_fetch",
                  "loop.log_write"},
}


def _fake_clock(jump_at=None):
    """A log-point clock for ``_SlowIntervals``: a second a call, behind the
    spans' clock (so the ring's spans count as since the last log point),
    and ten seconds more at call ``jump_at``."""
    import time

    base = time.monotonic_ns() - 10**12
    calls = []

    def clock():
        calls.append(1)
        late = 10 if jump_at is not None and len(calls) > jump_at else 0
        return base + (len(calls) + late) * 10**9

    return clock


@pytest.fixture(scope="module")
def even_run(tmp_path_factory, image_table):
    """One tiny run for the cases below: ten steps in one epoch, a log point
    every second step, its log points exactly a second apart by a stubbed
    clock. Yields spans, results, metrics lines and counter differences."""
    import json

    from lance_distributed_training_tpu import trainer
    from lance_distributed_training_tpu.data import write_dataset

    tmp = tmp_path_factory.mktemp("even")
    dataset = write_dataset(image_table, tmp / "ds", mode="create",
                            max_rows_per_file=100)
    names = ("train_steps_dispatched_total", "train_dispatch_starved_total",
             "train_drain_ahead_total", "train_drain_empty_total",
             "log_interval_slow_total")
    registry = default_registry()
    before = {n: registry.counter(n).value for n in names}
    fresh = SpanTracer(capacity=1 << 16)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(spans_mod, "_DEFAULT", fresh)
        patch.setattr(trainer._SlowIntervals, "clock",
                      staticmethod(_fake_clock()))
        patch.setenv("LDT_METRICS_PATH", str(tmp / "metrics.jsonl"))
        results = trainer.train(_image_run_config(
            dataset, batch_size=24, epochs=1))
    lines = [json.loads(x) for x in open(tmp / "metrics.jsonl")]
    return {"spans": fresh.spans(), "results": results, "lines": lines,
            "counters": {n: registry.counter(n).value - before[n]
                         for n in names},
            "gauge": registry.gauge("train_steps_in_flight_max").value}


def test_every_loop_call_lies_inside_a_phase_of_its_thread(even_run):
    got = even_run["spans"]
    assert even_run["results"]["steps"] == 10
    phases = {s.span_id: s for s in got if s.name.startswith("train.")}
    calls = [s for s in got if s.name.startswith("loop.")]
    for s in calls:
        parent = phases[s.parent_id]  # a phase, and none of startup.*
        assert s.name in LOOP_CALLS[parent.name], (s.name, parent.name)
        assert parent.thread_id == s.thread_id
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
        assert "step" in s.attrs
    per_step = {"loop.rng_split", "loop.loss_sum", "loop.stats_add",
                "loop.cursor"}
    counts = {n: sum(1 for s in calls if s.name == n)
              for n in per_step | {"loop.log_entry", "loop.stats_pack",
                                   "loop.stats_fetch", "loop.log_write"}}
    assert counts == {**dict.fromkeys(per_step, 10), "loop.log_entry": 5,
                      "loop.stats_pack": 5, "loop.stats_fetch": 5,
                      "loop.log_write": 5}
    # each per-step call once a step, under that step's number
    for name in per_step:
        steps = sorted(s.attrs["step"] for s in calls if s.name == name)
        assert steps == list(range(10)) or steps == list(range(1, 11)), name


def test_loop_and_placement_threads_still_tile_with_calls_inside(even_run):
    (loop,) = by_thread(even_run["spans"], LOOP_NAMES)
    assert_tiles(loop)
    assert {s.parent_id for s in loop} == {0}
    (own,) = by_thread(even_run["spans"], PLACEMENT_NAMES)
    assert_tiles(own)
    # nothing named train.* was opened inside a phase
    assert not [s.name for s in even_run["spans"]
                if s.name.startswith("train.") and s.parent_id]


def test_steps_in_flight_ride_the_step_phase_and_the_counters(even_run):
    (loop,) = by_thread(even_run["spans"], LOOP_NAMES)
    steps = [s for s in loop if s.name == "train.step"]
    assert len(steps) == 10
    assert all(s.attrs["in_flight"] >= 0 for s in steps)
    assert all(0 <= s.attrs["in_flight_after"] <= s.attrs["in_flight"] + 1
               for s in steps)
    # a drain waits for its own step with the next one dispatched: the phase
    # before it is that step's bookkeeping, and the step after it finds at
    # most that one in flight (on the CPU it may have finished already;
    # tests/test_drain_ahead.py holds it at 1 with a step that finishes late)
    names = [s.name for s in loop]
    drains = [i for i, n in enumerate(names) if n == "train.drain"]
    assert [loop[i].attrs["step"] for i in drains] == [1, 3, 5, 7, 9]
    for i in drains[:-1]:
        dispatched = [s.attrs["step"] for s in loop[:i]
                      if s.name == "train.step"]
        assert dispatched[-1] == loop[i].attrs["step"] + 1
        assert names[i + 1] == "train.log"
        after = loop[names.index("train.step", i)]
        assert after.attrs["in_flight"] in (0, 1)
    # the epoch's last step: nothing follows, so its drain empties the queue
    assert names[drains[-1] - 2:drains[-1]] == ["train.bookkeep",
                                                "train.loader"]
    assert steps[0].attrs["in_flight"] == 0
    counters = even_run["counters"]
    assert counters["train_steps_dispatched_total"] == 10
    # the first step is never starved; any other may find the CPU done
    assert 0 <= counters["train_dispatch_starved_total"] <= 9
    assert counters["train_drain_ahead_total"] == 4
    assert counters["train_drain_empty_total"] == 1
    progress = [ln for ln in even_run["lines"]
                if "images_per_sec_dispatch" in ln]
    assert len(progress) == 5
    # two steps between drains and the one dispatched before a drain waits
    assert all(0 <= ln["train_steps_in_flight_max"] <= 3 for ln in progress)
    assert even_run["gauge"] == progress[-1]["train_steps_in_flight_max"]


def test_an_even_run_logs_no_slow_interval(even_run):
    assert even_run["counters"]["log_interval_slow_total"] == 0
    assert not [ln for ln in even_run["lines"] if "slow_interval" in ln]


def test_an_interval_made_slow_logs_one_line_and_counts_once(
        tracer, image_dataset, tmp_path, monkeypatch):
    """The same run with ten seconds put into its fourth interval by the
    stubbed clock: one ``slow_interval`` line, steps 6 to 8, held against
    the two intervals before it in which nothing compiled (the first holds
    the step's compile and is left out of the median), its spans summed by
    name from the ring."""
    import json

    from lance_distributed_training_tpu import trainer

    slow = default_registry().counter("log_interval_slow_total")
    before = slow.value
    monkeypatch.setattr(trainer._SlowIntervals, "clock",
                        staticmethod(_fake_clock(jump_at=3)))
    monkeypatch.setenv("LDT_METRICS_PATH", str(tmp_path / "metrics.jsonl"))
    trainer.train(_image_run_config(image_dataset, batch_size=24, epochs=1))
    assert slow.value - before == 1
    lines = [json.loads(x) for x in open(tmp_path / "metrics.jsonl")]
    (line,) = [ln["slow_interval"] for ln in lines if "slow_interval" in ln]
    assert set(line) == {"steps", "seconds", "median_seconds", "by_span",
                         "in_flight_min", "compiles", "spans_dropped"}
    assert line["steps"] == [6, 8]
    assert (line["seconds"], line["median_seconds"]) == (11.0, 1.0)
    assert line["compiles"] == 0 and line["spans_dropped"] == 0
    assert line["in_flight_min"] is None or line["in_flight_min"] >= 0
    assert {"train.loader", "train.step", "train.drain", "train.bookkeep",
            "train.log", "loop.rng_split", "loop.loss_sum", "loop.stats_add",
            "loop.cursor", "loop.log_write"} <= set(
                line["by_span"])
    assert all(n.startswith(("train.", "loop.")) for n in line["by_span"])
    # the line is the loop thread's: nothing of the placement thread in it
    assert not set(line["by_span"]) & PLACEMENT_NAMES


class _Lines:
    def __init__(self):
        self.lines = []

    def log(self, entry, **kw):
        self.lines.append(entry)


@pytest.mark.parametrize("case", ["third_on", "turnover_against_its_like",
                                  "compile_left_out_of_the_median"])
def test_slow_intervals_are_judged_against_their_like(case, monkeypatch):
    from lance_distributed_training_tpu import trainer

    times = iter(())
    monkeypatch.setattr(trainer._SlowIntervals, "clock",
                        staticmethod(lambda: next(times)))
    slow = trainer._SlowIntervals()
    compiles = default_registry().counter("xla_compiles_total")
    out = _Lines()
    s = 10**9
    if case == "third_on":
        # (log point at second, step, epoch): the second interval is ten
        # times the first and is not judged, the fourth is
        points = [(0, 0, 0), (1, 2, 0), (11, 4, 0), (12, 6, 0), (30, 8, 0),
                  (31, 10, 0)]
        want = [[6, 8]]
    elif case == "turnover_against_its_like":
        # every third interval holds an epoch's turnover and takes 5 s:
        # held against the other turnovers it is even; the last takes 9
        points = [(0, 0, 0), (1, 2, 0), (2, 4, 0), (7, 6, 1), (8, 8, 1),
                  (9, 10, 1), (14, 12, 2), (15, 14, 2), (16, 16, 2),
                  (25, 18, 3)]
        want = [[16, 18]]
    else:
        # the third interval compiles and takes 20 s: its line says so, and
        # the fifth is still held against a median of 1 s
        points = [(0, 0, 0), (1, 2, 0), (2, 4, 0), (22, 6, 0), (23, 8, 0),
                  (25, 10, 0)]
        want = [[4, 6], [8, 10]]
    times = iter([p[0] * s for p in points])
    for i, (_, step, epoch) in enumerate(points):
        if case == "compile_left_out_of_the_median" and step == 6:
            compiles.inc(2)
        slow.check(step, epoch, None, out)
    got = [ln["slow_interval"] for ln in out.lines]
    assert [ln["steps"] for ln in got] == want
    if case == "compile_left_out_of_the_median":
        assert [ln["compiles"] for ln in got] == [2, 0]
        assert [ln["median_seconds"] for ln in got] == [1.0, 1.0]


class _Loss:
    def __init__(self):
        self.done = False

    def is_ready(self):
        return self.done


def test_starved_counts_an_empty_queue_the_loop_did_not_empty():
    from lance_distributed_training_tpu import trainer

    registry = default_registry()
    names = ("train_steps_dispatched_total", "train_dispatch_starved_total")
    before = {n: registry.counter(n).value for n in names}
    flight = trainer._StepsInFlight()
    a, b, c, d = (_Loss() for _ in range(4))
    assert flight.began() == 0  # the run's first step: not starved
    assert flight.dispatched(a) == 1
    assert flight.began() == 1
    assert flight.dispatched(b) == 2
    a.done = b.done = True  # the device ran dry behind the host's back
    assert flight.began() == 0  # starved
    assert flight.dispatched(c) == 1
    entry = {}
    assert flight.publish(entry) == 0  # the fewest in flight at a dispatch
    assert entry == {"train_steps_in_flight_max": 2}
    flight.emptied()  # a drain
    assert flight.began() == 0  # the loop emptied it itself: not starved
    c.done = True
    assert flight.dispatched(d) == 1
    assert flight.publish({}) is None  # no step but the one after the drain
    assert {n: registry.counter(n).value - before[n] for n in names} == {
        "train_steps_dispatched_total": 4, "train_dispatch_starved_total": 1}


def test_probed_transform_has_its_dispatch_and_its_await_named(tracer,
                                                               tmp_path):
    """Under --token_pack every batch's transform has its dispatch as a
    span, the sampled one its await too, and the step after an await finds
    nothing in flight without being counted as starved for it."""
    from lance_distributed_training_tpu.data.authoring import (
        create_variable_length_token_dataset,
    )
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    path = tmp_path / "toks"
    create_variable_length_token_dataset(
        str(path), rows=544, vocab_size=64, max_len=32, mean_len=8)
    train(TrainConfig(
        dataset_path=str(path), task_type="masked_lm",
        model_name="bert_small", vocab_size=64, seq_len=32, batch_size=32,
        epochs=1, no_wandb=True, eval_at_end=False, token_pack=True,
        pack_rows_multiple=2, log_every=0, autotune=False, no_ddp=True))
    got = tracer.spans()
    transforms = {s.span_id: s for s in got if s.name == "train.transform"}
    dispatches = [s for s in got if s.name == "loop.transform_dispatch"]
    awaits = [s for s in got if s.name == "loop.transform_await"]
    assert len(transforms) == len(dispatches) == 17
    assert [s.attrs["step"] for s in awaits] == [0, 16]  # every 16th batch
    for s in dispatches + awaits:
        assert s.parent_id in transforms
    for s in awaits:
        first = next(d for d in dispatches if d.parent_id == s.parent_id)
        assert first.end_ns <= s.start_ns
    steps = {s.attrs["step"]: s for s in got if s.name == "train.step"}
    assert [steps[s.attrs["step"]].attrs["in_flight"] for s in awaits] == [
        0, 0]


# -- the span file is written in batches (PR 35) ------------------------------


def _names_in(path):
    import json

    return [json.loads(x)["name"] for x in path.read_text().splitlines()]


@pytest.mark.parametrize("by", ["size", "age", "flush", "close"])
def test_span_file_is_written_in_batches(tmp_path, by):
    path = tmp_path / "spans.jsonl"
    tr = SpanTracer(jsonl_path=str(path))
    tr.FLUSH_BYTES, tr.FLUSH_AGE_NS = 1 << 30, 1 << 62
    with tr.span("first"):
        pass
    # the first span opens the file: a live reader finds the clock anchor
    assert _names_in(path) == ["ldt.clock_sync", "first"]
    for i in range(3):
        with tr.span(f"held{i}"):
            pass
    assert _names_in(path) == ["ldt.clock_sync", "first"]
    if by == "size":
        tr.FLUSH_BYTES = 1
    elif by == "age":
        tr.FLUSH_AGE_NS = 0
    if by in ("size", "age"):
        with tr.span("last"):
            pass
        want = ["held0", "held1", "held2", "last"]
    else:
        getattr(tr, by)()
        want = ["held0", "held1", "held2"]
    assert _names_in(path) == ["ldt.clock_sync", "first"] + want
    tr.close()
    tr.close()  # idempotent
    with tr.span("after close"):  # the ring still takes it, the file not
        pass
    assert _names_in(path) == ["ldt.clock_sync", "first"] + want
    assert tr.spans()[-1].name == "after close"


def test_benchmark_loop_readers_check_passes():
    """The five readers of the loop's calls read the hand-made spans as
    worked out by eye, and None on a span file from before PR 35."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmark", "check_loop.py")],
        cwd=repo, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "loop ok"
