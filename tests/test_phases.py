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


# -- start-up read from inside (PR 51) ----------------------------------------

JAX_KINDS = ("jax.trace", "jax.lower", "xla.compile")


def _union_ns(spans):
    """Nanoseconds some span of the list is open, by a sweep over start and
    end points (the program's own sum walks sorted intervals)."""
    points = sorted([(s.start_ns, 1) for s in spans]
                    + [(s.end_ns, -1) for s in spans],
                    key=lambda p: (p[0], -p[1]))
    depth = total = last = 0
    for t, step in points:
        if depth:
            total += t - last
        depth, last = depth + step, t
    return total


@pytest.fixture()
def compile_cache(tmp_path):
    """A persistent compile cache under ``tmp_path`` that takes every program,
    however quick its compile; afterwards none again (see conftest.py: the
    suite runs without one, and nothing but the small element-wise programs
    of the test is ever read back from this one)."""
    from jax.experimental.compilation_cache import compilation_cache

    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        yield tmp_path / "cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
        compilation_cache.reset_cache()


def _fresh(name):
    """A new function object a call, the same program every time: a fresh
    ``jit`` of it is traced, lowered and handed to the backend anew (the
    in-memory caches go by the object), under the same cache key."""
    def body(x):
        for i in range(40):  # long enough to trace and lower to leave spans
            x = jnp.cos(x) * 5 + i
        return x

    body.__name__ = body.__qualname__ = name
    return jax.jit(body)


def test_compile_leaves_trace_lower_compile_and_what_the_cache_said(
        tracer, compile_cache):
    """XLA:CPU takes part in the persistent cache under this JAX, so the hit
    is a real one."""
    spans_mod.watch_xla_compiles()
    x = np.arange(9.0, dtype=np.float32)  # no program of its own
    tracer.phase("cold")
    _fresh("cached_once")(x).block_until_ready()
    tracer.phase("warm")
    _fresh("cached_once")(x).block_until_ready()
    tracer.end_phase()
    got = tracer.spans()
    phases = {s.name: s for s in got if s.name in ("cold", "warm")}
    own = {name: [s for s in got if s.parent_id == phase.span_id
                  and "cached_once" in s.attrs["fun_name"]]
           for name, phase in phases.items()}
    for name, outcome in (("cold", "miss"), ("warm", "hit")):
        by_kind = {s.name: s for s in own[name]}
        assert sorted(by_kind) == sorted(JAX_KINDS), (name, own[name])
        assert len(own[name]) == 3
        assert by_kind["xla.compile"].attrs["cache"] == outcome
        assert ("retrieval_s" in by_kind["xla.compile"].attrs) \
            == (outcome == "hit")
        assert "cache" not in by_kind["jax.trace"].attrs
        # in the order JAX does them, inside the phase of their thread
        assert phases[name].start_ns <= by_kind["jax.trace"].end_ns \
            <= by_kind["jax.lower"].end_ns <= by_kind["xla.compile"].end_ns \
            <= phases[name].end_ns
    assert own["warm"][-1].attrs["retrieval_s"] >= 0
    assert any(f.name.startswith("jit_cached_once")
               for f in compile_cache.iterdir())


def test_compile_without_a_cache_directory_reads_off(tracer):
    spans_mod.watch_xla_compiles()
    assert not jax.config.jax_compilation_cache_dir
    hits = default_registry().counter("compile_cache_hits_total")
    misses = default_registry().counter("compile_cache_misses_total")
    before = hits.value, misses.value
    _fresh("never_cached")(np.arange(5.0, dtype=np.float32))
    (compiled,) = [s for s in tracer.spans() if s.name == "xla.compile"]
    assert compiled.attrs == {"fun_name": "jit(never_cached)", "cache": "off"}
    assert (hits.value, misses.value) == before


def test_cache_events_stay_with_the_thread_that_compiles(tracer):
    """JAX's events fed by hand: what the cache said to one thread's compile
    rides that thread's span and no other's, and is used once."""
    import jax.monitoring as monitoring

    spans_mod.watch_xla_compiles()
    jax.config.update("jax_compilation_cache_dir", "/nonexistent/ldt-test")
    try:
        asked = threading.Event()
        done = threading.Event()

        def other():
            monitoring.record_event(spans_mod._CACHE_REQUEST)
            monitoring.record_event(spans_mod._CACHE_HIT)
            monitoring.record_event_duration_secs(
                spans_mod._CACHE_RETRIEVAL, 0.25)
            asked.set()
            assert done.wait(timeout=30)
            for fun_name in ("theirs", "theirs_again"):
                monitoring.record_event_duration_secs(
                    "/jax/core/compile/backend_compile_duration", 0.5,
                    fun_name=fun_name)

        t = threading.Thread(target=other)
        t.start()
        assert asked.wait(timeout=30)
        monitoring.record_event(spans_mod._CACHE_REQUEST)
        monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.125,
            fun_name="mine")
        done.set()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
    got = {s.attrs["fun_name"]: s for s in tracer.spans()}
    assert got["mine"].attrs["cache"] == "miss"
    assert got["theirs"].attrs == {"fun_name": "theirs", "cache": "hit",
                                   "retrieval_s": 0.25}
    assert got["theirs_again"].attrs["cache"] == "off"
    assert got["mine"].thread_id != got["theirs"].thread_id
    # back-dated from the event by its duration
    assert got["theirs"].end_ns - got["theirs"].start_ns == 500_000_000


def test_a_jit_inside_a_trace_nests_and_the_union_is_the_outer_one(tracer):
    spans_mod.watch_xla_compiles()
    def doubled(x):
        for i in range(60):  # a trace of more than a millisecond
            x = x * 2 + i
        return x

    inner = jax.jit(doubled)

    def outer_never_seen(x):
        return inner(x).sum() + inner(x[:3]).sum()  # two shapes: two traces

    tracer.phase("tracing")
    jax.jit(outer_never_seen).lower(np.arange(6.0, dtype=np.float32))
    tracer.end_phase()
    got = tracer.spans()
    holder = next(s for s in got if s.name == "tracing")
    traces = [s for s in got if s.name == "jax.trace"
              and s.attrs["fun_name"] in ("outer_never_seen", "doubled")]
    outer = next(s for s in traces if s.attrs["fun_name"] != "doubled")
    nested = [s for s in traces if s is not outer]
    assert len(nested) == 2
    assert {s.parent_id for s in traces} == {holder.span_id}
    assert {s.thread_id for s in traces} == {holder.thread_id}
    for s in nested:  # ended first, and lies inside the outer one
        assert outer.start_ns <= s.start_ns and s.end_ns <= outer.end_ns
    assert sum(s.end_ns - s.start_ns for s in traces) \
        > outer.end_ns - outer.start_ns
    assert _union_ns(traces) == outer.end_ns - outer.start_ns
    # lowered once, as one module; nothing compiled
    assert [s.attrs["fun_name"] for s in got if s.name == "jax.lower"] \
        == ["jit(outer_never_seen)"]
    assert not [s for s in got if s.name == "xla.compile"]


def test_a_trace_under_a_millisecond_counts_and_leaves_no_span(tracer):
    """JAX's events fed by hand: the thousands of `jnp` functions a step
    traces inside its own trace would push a start-up out of the ring."""
    import jax.monitoring as monitoring

    spans_mod.watch_xla_compiles()
    registry = default_registry()
    events = {"jax_trace_seconds_total":
              "/jax/core/compile/jaxpr_trace_duration",
              "jax_lower_seconds_total":
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "xla_compile_seconds_total":
              "/jax/core/compile/backend_compile_duration"}
    before = {n: registry.counter(n).value for n in events}
    for event in events.values():
        monitoring.record_event_duration_secs(event, 0.0005, fun_name="tiny")
        monitoring.record_event_duration_secs(event, 0.002, fun_name="long")
    for name in events:
        assert registry.counter(name).value - before[name] \
            == pytest.approx(0.0025)
    assert sorted((s.name, s.attrs["fun_name"]) for s in tracer.spans()) == [
        ("jax.lower", "long"), ("jax.trace", "long"),
        ("xla.compile", "long"), ("xla.compile", "tiny")]


def test_the_four_counters_match_the_spans(tracer, compile_cache):
    spans_mod.watch_xla_compiles()
    registry = default_registry()
    names = ("compile_cache_hits_total", "compile_cache_misses_total",
             "jax_trace_seconds_total", "jax_lower_seconds_total",
             "xla_compiles_total", "xla_compile_seconds_total")
    before = {n: registry.counter(n).value for n in names}
    x = np.arange(11.0, dtype=np.float32)
    for _ in range(3):  # a miss, then two hits
        _fresh("counted")(x).block_until_ready()
    got = tracer.spans()
    rose = {n: registry.counter(n).value - before[n] for n in names}
    compiles = [s for s in got if s.name == "xla.compile"]
    assert rose["compile_cache_misses_total"] == sum(
        1 for s in compiles if s.attrs["cache"] == "miss") == 1
    assert rose["compile_cache_hits_total"] == sum(
        1 for s in compiles if s.attrs["cache"] == "hit") == 2
    assert rose["xla_compiles_total"] == len(compiles) == 3
    for counter, name in (("jax_trace_seconds_total", "jax.trace"),
                          ("jax_lower_seconds_total", "jax.lower"),
                          ("xla_compile_seconds_total", "xla.compile")):
        spans_s = sum(s.end_ns - s.start_ns for s in got
                      if s.name == name) / 1e9
        # a span's times are whole nanoseconds, the counter's a float; the
        # counters also take the `jnp` functions traced in microseconds
        # inside `counted`'s trace, which leave no span
        short = 0.0 if name == "xla.compile" else 40 * 3 * 1e-3
        slack = 1e-6 * len(got)
        assert spans_s - slack <= rose[counter] <= spans_s + short + slack
        assert spans_s > 0


def test_placing_the_cache_twice_registers_one_listener(monkeypatch):
    """``maybe_enable_compile_cache`` is where the listeners go on: the
    benchmark calls it before its model check, ``train()`` calls it again."""
    import jax.monitoring as monitoring

    from lance_distributed_training_tpu import trainer

    registered = []
    monkeypatch.setattr(spans_mod, "_COMPILE_LISTENER_ON", False)
    monkeypatch.setattr(monitoring, "register_event_listener",
                        lambda f: registered.append(("event", f)))
    monkeypatch.setattr(monitoring, "register_event_duration_secs_listener",
                        lambda f: registered.append(("duration", f)))
    monkeypatch.delenv("LDT_TRACE_PATH", raising=False)
    assert trainer.maybe_enable_compile_cache("cpu") is None
    assert trainer.maybe_enable_compile_cache("cpu") is None
    spans_mod.watch_xla_compiles()
    assert sorted(kind for kind, _ in registered) == ["duration", "event"]


@pytest.mark.parametrize("case", ["untraced", "traced", "no_log_point"])
def test_train_writes_one_startup_record(case, image_dataset, tmp_path,
                                         monkeypatch):
    """One ``startup`` line a run, with ``LDT_TRACE_PATH`` unset too, summed
    from the ring: its phases tile entry to the end of the first
    ``train.step``. A run with no log point writes it as it shuts down."""
    import json

    from lance_distributed_training_tpu import trainer

    metadata_in_key = jax.config.jax_compilation_cache_include_metadata_in_key
    if case == "traced":
        monkeypatch.setenv("LDT_TRACE_PATH", str(tmp_path / "spans.jsonl"))
    else:
        monkeypatch.delenv("LDT_TRACE_PATH", raising=False)
    monkeypatch.setenv("LDT_METRICS_PATH", str(tmp_path / "metrics.jsonl"))
    fresh = SpanTracer(capacity=1 << 16)
    monkeypatch.setattr(spans_mod, "_DEFAULT", fresh)
    try:
        trainer.train(_image_run_config(
            image_dataset, epochs=1,
            log_every=0 if case == "no_log_point" else 2))
    finally:
        fresh.close()
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          metadata_in_key)
    lines = [json.loads(x) for x in open(tmp_path / "metrics.jsonl")]
    (at,) = [i for i, ln in enumerate(lines) if "startup" in ln]
    record = lines[at]["startup"]
    if case == "no_log_point":
        assert not [ln for ln in lines if "images_per_sec_dispatch" in ln]
    else:  # right behind the first progress line
        assert [i for i, ln in enumerate(lines)
                if "images_per_sec_dispatch" in ln][0] == at - 1
    assert set(record) == {
        "process_age_s", "entry_to_first_step_s", "phases", "in_train",
        "before_train", "programs", "cache", "spans_dropped"}
    got = fresh.spans()
    entry = next(s for s in got if s.name == "startup.devices")
    first_step = next(s for s in got if s.name == "train.step")
    assert record["entry_to_first_step_s"] == pytest.approx(
        (first_step.end_ns - entry.start_ns) / 1e9, abs=1e-5)
    assert sum(record["phases"].values()) == pytest.approx(
        record["entry_to_first_step_s"], abs=1e-3)
    assert list(record["phases"]) == [
        "startup.devices", "startup.dataset", "startup.state",
        "startup.loader", "train.loader", "train.bookkeep", "train.step"]
    assert record["phases"]["train.step"] == pytest.approx(
        (first_step.end_ns - first_step.start_ns) / 1e9, abs=1e-5)
    # the entry phase carries the process's age and the cache as found
    assert entry.attrs["process_age_s"] == record["process_age_s"] > 0
    assert record["cache"] == {
        "dir": None, "entries": 0, "bytes": 0,
        "key_metadata": case == "traced"}
    assert {k: entry.attrs[k] for k in entry.attrs if k != "process_age_s"} \
        == {"cache_" + k: v for k, v in record["cache"].items()}
    sums = record["in_train"]
    assert set(sums) == set(record["before_train"]) == {
        "trace_s", "lower_s", "compile_s", "cache_load_s", "hits", "misses",
        "off"}
    inside = [s for s in got if s.name in JAX_KINDS
              and entry.start_ns <= s.start_ns and s.end_ns <= first_step.end_ns
              and s.thread_id == entry.thread_id]
    others = [s for s in got if s.name in JAX_KINDS
              and s.thread_id != entry.thread_id
              and s.start_ns < first_step.end_ns]
    if not others:  # the loop thread's alone: the unions, by another route
        for key, name in (("trace_s", "jax.trace"), ("lower_s", "jax.lower"),
                          ("compile_s", "xla.compile")):
            assert sums[key] == pytest.approx(_union_ns(
                [s for s in inside if s.name == name]) / 1e9, abs=1e-5)
    assert sums["hits"] == sums["misses"] == 0 and sums["off"] >= 2
    assert sums["cache_load_s"] == 0.0
    assert 0 < sums["compile_s"] < record["entry_to_first_step_s"]
    assert 0 < sums["trace_s"] and 0 < sums["lower_s"]
    # the step's own compile is inside its phase, and long or not, every
    # program listed took half a second and says what it was
    for program in record["programs"]:
        assert program["seconds"] >= 0.5
        assert program["kind"] in ("trace", "lower", "compile")
        assert ("cache" in program) == (program["kind"] == "compile")
    assert record["spans_dropped"] == 0
    if case == "traced":
        names = {json.loads(x)["name"]
                 for x in open(tmp_path / "spans.jsonl")}
        assert set(JAX_KINDS) <= names


def test_benchmark_startup_readers_check_passes():
    """The twelve readers of start-up read the hand-made spans as worked out
    by eye and the recorded ones as a sweep gives them, tile, and read None
    on a span file from before PR 51."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmark", "check_startup.py")],
        cwd=repo, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "startup ok"


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
