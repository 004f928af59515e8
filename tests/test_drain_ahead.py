"""A drain point's wait leaves a step in flight (trainer._train_loop).

The rule these hold: from the moment step n + 1 is dispatched until step
n + 2 is, the loop waits for nothing that was enqueued behind step n + 1. A
drain point's step n is waited for once n + 1 is dispatched, its stats are
packed ahead of n + 1, the schedule's value is taken on the host, and the
record of n is written after the fetch of its loss. Where nothing may follow
step n (the epoch's last step, ``max_steps``, a due checkpoint, a preemption
flag, a chaos hook) the drain empties the queue, as it always did.

Order is observed on the CPU with a stub step whose loss is ready only once
somebody has waited for it; equality with the old order against a run whose
every drain empties the queue (a chaos hook that never fires).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lance_distributed_training_tpu.obs import default_registry
from lance_distributed_training_tpu.obs import spans as spans_mod
from lance_distributed_training_tpu.obs.spans import SpanTracer

DRAINS = ("train_drain_ahead_total", "train_drain_empty_total")


def _config(dataset, **kw):
    from lance_distributed_training_tpu.trainer import TrainConfig

    return TrainConfig(**{**dict(
        dataset_path=dataset.uri, num_classes=10, model_name="resnet18",
        image_size=32, batch_size=48, epochs=1, no_wandb=True, augment=False,
        eval_at_end=False, log_every=2, autotune=False, no_ddp=True,
        seed=11), **kw})


def _counters(names):
    registry = default_registry()
    return {n: registry.counter(n).value for n in names}


class _Late:
    """A device scalar of step ``n`` that is ready only once the loop has
    waited for it or for a later one (the device runs in order), and says
    so in ``events``."""

    def __init__(self, events, what, n, value, ready):
        self.events, self.what, self.n = events, what, n
        self.value, self.ready = value, ready

    def is_ready(self):
        return self.ready["upto"] >= self.n

    def __float__(self):
        self.events.append((self.what, self.n))
        self.ready["upto"] = max(self.ready["upto"], self.n)
        return self.value

    def __array__(self, dtype=None, copy=None):
        self.events.append((self.what, self.n))
        self.ready["upto"] = max(self.ready["upto"], self.n)
        return np.asarray(self.value, dtype)

    def __radd__(self, other):  # the epoch's loss sum: not what is observed
        return other


def _stub_run(monkeypatch, dataset, **kw):
    """``train()`` with the step, the stats' packing, the schedule and the
    step trace replaced by recorders: returns (events, lines, spans, counter
    differences, the schedule's values by step). Step n (from 1) returns
    loss n / 8."""
    from lance_distributed_training_tpu import trainer
    from lance_distributed_training_tpu.utils import chaos, metrics

    events, lines, rates = [], [], {}
    ready = {"upto": 0}
    n = {"dispatched": 0}

    def step(state, batch, rng):
        n["dispatched"] += 1
        k = n["dispatched"]
        events.append(("dispatch", k))
        return (state, _Late(events, "wait_loss", k, k / 8, ready),
                _Late(events, "wait_gnorm", k, float(k), ready),
                {"seen_total": jnp.float32(2.0), "fill_pct": jnp.float32(k)})

    def pack(scalars):
        k = n["dispatched"]
        events.append(("pack", k))
        return _Late(events, "fetch_stats", k,
                     [float(x) for x in scalars], ready)

    schedule_of = trainer.lr_schedule_fn

    def lr_schedule_fn(config, total_steps=None):
        schedule = schedule_of(config, total_steps)

        def at(count):
            value = schedule(count)
            if isinstance(count, int):  # the loop's call, not the optimizer's
                events.append(("lr", count, jax.config.jax_default_device))
                rates[count] = float(np.float32(value))
            return value
        return at

    class Recorder:
        @classmethod
        def from_env(cls, env=None):
            return cls()

        def record(self, step, epoch, batch, loss):
            events.append(("record", step, loss.n))

        def close(self):
            pass

    log = metrics.MetricLogger.log

    def logged(logger, record, *args, **kwargs):
        if "images_per_sec_dispatch" in record:
            events.append(("log", record["step"]))
            lines.append(dict(record))
        return log(logger, record, *args, **kwargs)

    monkeypatch.setattr(trainer, "make_train_step", lambda *a, **k: step)
    monkeypatch.setattr(trainer, "_pack_scalars", pack)
    monkeypatch.setattr(trainer, "lr_schedule_fn", lr_schedule_fn)
    monkeypatch.setattr(chaos, "StepTrace", Recorder)
    monkeypatch.setattr(metrics.MetricLogger, "log", logged)
    fresh = SpanTracer(capacity=1 << 16)
    monkeypatch.setattr(spans_mod, "_DEFAULT", fresh)
    before = _counters(DRAINS + ("train_dispatch_starved_total",))
    trainer.train(_config(dataset, **{**dict(
        batch_size=24, log_grad_norm=True, lr_schedule="cosine",
        warmup_steps=4), **kw}))
    after = _counters(before)
    return (events, lines, fresh.spans(),
            {name: after[name] - before[name] for name in before}, rates)


def test_drain_point_is_waited_for_behind_the_next_dispatch(
        monkeypatch, image_dataset):
    """Ten steps, a log point every second one, stats, a gradient norm and a
    warm-up schedule: (a) and (d) of the issue."""
    events, lines, spans, counters, rates = _stub_run(monkeypatch,
                                                       image_dataset)
    at = {e[:2]: i for i, e in enumerate(events)}
    for n in (2, 4, 6, 8):
        # n's stats are packed, and n + 1 dispatched, before n's loss is
        # fetched; the record follows the fetches
        assert (at["dispatch", n] < at["pack", n] < at["dispatch", n + 1]
                < at["wait_loss", n] < at["log", n] < at["dispatch", n + 2])
        for fetch in ("wait_gnorm", "fetch_stats", "lr"):
            assert at["wait_loss", n] < at[fetch, n] < at["log", n], fetch
    # the epoch's last step: no step follows, the queue is emptied
    assert at["pack", 10] < at["wait_loss", 10] < at["log", 10]
    # the rule: between two dispatches nothing is waited for that was
    # enqueued behind the first of them, and nothing at all that is not a
    # drain point's
    newest = 0
    for event in events:
        if event[0] == "dispatch":
            newest = event[1]
        elif event[0] in ("wait_loss", "wait_gnorm", "fetch_stats"):
            assert event[1] % 2 == 0
            assert event[1] < newest or event[1] == 10, event
    # every wait once (the record's loss is a second read of the drained one)
    assert [e for e in events if e[0] == "wait_loss"] == [
        ("wait_loss", n) for n in (2, 2, 4, 4, 6, 6, 8, 8, 10, 10)]
    # the schedule's value: on the host, equal to float32 at that step
    host = jax.local_devices(backend="cpu")[0]
    assert [e for e in events if e[0] == "lr"] == [
        ("lr", n, host) for n in (2, 4, 6, 8, 10)]
    # records: each once, in order, step n with loss n
    assert [ln["step"] for ln in lines] == [2, 4, 6, 8, 10]
    assert [ln["loss"] for ln in lines] == [round(n / 8, 4)
                                            for n in (2, 4, 6, 8, 10)]
    assert [ln["grad_norm"] for ln in lines] == [2.0, 4.0, 6.0, 8.0, 10.0]
    assert [ln["fill_pct"] for ln in lines] == [2.0, 4.0, 6.0, 8.0, 10.0]
    assert [ln["lr"] for ln in lines] == [rates[n] for n in (2, 4, 6, 8, 10)]
    assert 0 < lines[0]["lr"] < lines[1]["lr"]  # the warm-up, by step
    # the step trace: once a step, in step order, with that step's loss
    assert [e[1:] for e in events if e[0] == "record"] == [
        (n, n) for n in range(1, 11)]
    # (d) a step after a drain point begins with the one step in flight
    steps = sorted((s for s in spans if s.name == "train.step"),
                   key=lambda s: s.start_ns)
    assert [s.attrs["in_flight"] for s in steps] == [0, 1, 2, 1, 2, 1, 2, 1,
                                                     2, 1]
    assert [ln["train_steps_in_flight_max"] for ln in lines] == [3, 3, 3, 3,
                                                                 2]
    assert counters == {"train_drain_ahead_total": 4,
                        "train_drain_empty_total": 1,
                        "train_dispatch_starved_total": 0}
    drains = sorted((s for s in spans if s.name == "train.drain"),
                    key=lambda s: s.start_ns)
    assert [s.attrs["step"] for s in drains] == [1, 3, 5, 7, 9]


@pytest.mark.parametrize("case", ["max_steps", "sync_every", "every_step"])
def test_where_the_queue_is_still_emptied(monkeypatch, image_dataset, case):
    """``max_steps`` on a drain point drains it to empty and off one leaves
    the last record written; a ``sync_every`` drain that logs nothing waits
    behind the next dispatch too; with a log point every step each is
    waited for behind the next."""
    kw = {"max_steps": {"max_steps": 7}, "sync_every": {"log_every": 0},
          "every_step": {"log_every": 1, "max_steps": 4}}[case]
    if case == "sync_every":
        # sixty steps: the drain of step 50 is waited for after dispatch 51
        kw.update(batch_size=4)
    events, lines, _, counters, _ = _stub_run(monkeypatch, image_dataset,
                                              **kw)
    at = {e[:2]: i for i, e in enumerate(events)}
    if case == "max_steps":
        assert [ln["step"] for ln in lines] == [2, 4, 6]
        assert at["dispatch", 7] < at["wait_loss", 6] < at["log", 6]
        assert ("dispatch", 8) not in at
        assert counters["train_drain_ahead_total"] == 3
        assert counters["train_drain_empty_total"] == 0
    elif case == "sync_every":
        assert not lines and ("pack", 50) not in at
        assert at["dispatch", 51] < at["wait_loss", 50] < at["dispatch", 52]
        assert [e for e in events if e[0] == "wait_loss"] == [
            ("wait_loss", 50)]
        assert counters["train_drain_ahead_total"] == 1
        assert counters["train_drain_empty_total"] == 0
    else:
        assert [ln["step"] for ln in lines] == [1, 2, 3, 4]
        for n in (1, 2, 3):
            assert (at["pack", n] < at["dispatch", n + 1]
                    < at["wait_loss", n] < at["log", n])
        # the step max_steps stops at: drained where it was dispatched
        assert at["dispatch", 4] < at["wait_loss", 4] < at["log", 4]
        assert counters["train_drain_ahead_total"] == 3
        assert counters["train_drain_empty_total"] == 1


def test_drains_count_ahead_or_empty_and_only_empty_excuses_a_dry_queue():
    from lance_distributed_training_tpu import trainer

    class Loss:
        done = False

        def is_ready(self):
            return self.done

    names = DRAINS + ("train_dispatch_starved_total",)
    before = _counters(names)
    flight = trainer._StepsInFlight()
    a, b, c = Loss(), Loss(), Loss()
    flight.began()
    flight.dispatched(a)
    flight.began()
    flight.dispatched(b)
    a.done = True  # the wait for a has returned, b is the device's to run
    flight.drained(ahead=True)
    assert flight.began() == 1  # b: the one step in flight after a drain
    flight.dispatched(c)
    b.done = c.done = True
    flight.drained(ahead=True)  # ahead, and the device ran dry all the same
    assert flight.began() == 0  # starved: the loop had left it a step
    flight.dispatched(Loss())
    flight.drained(ahead=False)
    assert flight.began() == 0  # emptied by the loop itself: not starved
    after = _counters(names)
    assert {n: after[n] - before[n] for n in names} == {
        "train_drain_ahead_total": 2, "train_drain_empty_total": 1,
        "train_dispatch_starved_total": 1}


def _real_run(tmp_path, name, dataset, monkeypatch, empty_every_drain=False,
              **kw):
    """A real tiny run: its progress records, its step-trace lines, its
    results and its drains. ``empty_every_drain``: under a chaos hook that
    never fires, so every drain empties the queue where its step was
    dispatched, which is the order every run had before."""
    from lance_distributed_training_tpu.trainer import train
    from lance_distributed_training_tpu.utils import chaos

    monkeypatch.setenv(chaos.TRACE_ENV, str(tmp_path / f"{name}.trace"))
    monkeypatch.setenv("LDT_METRICS_PATH", str(tmp_path / f"{name}.jsonl"))
    if empty_every_drain:
        monkeypatch.setenv(chaos.CHAOS_ENV, "drain@1000000")
    else:
        monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    before = _counters(DRAINS)
    results = train(_config(dataset, **kw))
    after = _counters(DRAINS)
    lines = [json.loads(x) for x in open(tmp_path / f"{name}.jsonl")]
    records = [{k: ln[k] for k in ("step", "epoch", "loss", "lr")}
               for ln in lines if "images_per_sec_dispatch" in ln]
    return {"records": records,
            "trace": chaos.read_trace(str(tmp_path / f"{name}.trace")),
            "results": results,
            "drains": {n: after[n] - before[n] for n in DRAINS},
            "epochs": [ln["loss"] for ln in lines if "epoch_time" in ln]}


@pytest.mark.parametrize("max_steps", [0, 7])
def test_a_seeded_run_logs_what_the_old_order_logged(
        tmp_path, image_dataset, monkeypatch, max_steps):
    """(b): two epochs of five steps, a log point every second step, with
    and without ``max_steps`` off a log point: the same records, losses and
    step-trace lines as with every drain emptying the queue."""
    kw = dict(epochs=2, max_steps=max_steps, lr_schedule="cosine",
              warmup_steps=3)
    new = _real_run(tmp_path, "new", image_dataset, monkeypatch, **kw)
    old = _real_run(tmp_path, "old", image_dataset, monkeypatch,
                    empty_every_drain=True, **kw)
    steps = max_steps or 10
    assert new["results"]["steps"] == old["results"]["steps"] == steps
    assert [r["step"] for r in new["records"]] == list(range(2, steps + 1, 2))
    assert new["records"] == old["records"]
    assert [t["step"] for t in new["trace"]] == list(range(1, steps + 1))
    assert new["trace"] == old["trace"]
    assert new["epochs"] == old["epochs"]
    # each record's loss is its own step's
    by_step = {t["step"]: t["loss"] for t in new["trace"]}
    assert [r["loss"] for r in new["records"]] == [
        round(by_step[r["step"]], 4) for r in new["records"]]
    assert old["drains"]["train_drain_ahead_total"] == 0
    if max_steps:
        # 2 and 4 ahead; 6 ahead of the step the run stops at
        assert new["drains"] == {"train_drain_ahead_total": 3,
                                 "train_drain_empty_total": 0}
    else:
        # 2, 4, 6, 8 ahead; 10 is the second epoch's last step
        assert new["drains"] == {"train_drain_ahead_total": 4,
                                 "train_drain_empty_total": 1}


def test_checkpoint_preemption_and_epoch_end_save_the_drained_state(
        tmp_path, image_dataset, monkeypatch):
    """(c): a log point every step. A due checkpoint and the epoch's last
    step drain to empty; a preemption flag raised behind step 3 stops the
    run with step 3's state saved; the resumed run replays the batches and
    losses of an uninterrupted one from step 4 on."""
    from lance_distributed_training_tpu.utils import chaos, signals

    kw = dict(epochs=2, log_every=1)
    control = _real_run(tmp_path, "control", image_dataset, monkeypatch, **kw)
    assert [r["step"] for r in control["records"]] == list(range(1, 11))
    # steps 5 and 10 end their epochs; every other drain is ahead
    assert control["drains"] == {"train_drain_ahead_total": 8,
                                 "train_drain_empty_total": 2}

    handlers = []
    install = signals.PreemptionHandler.install
    monkeypatch.setattr(signals.PreemptionHandler, "install",
                        lambda self: handlers.append(self) or install(self))
    record = chaos.StepTrace.record

    def raise_the_flag_behind_step_3(self, step, epoch, batch, loss):
        record(self, step, epoch, batch, loss)
        if step == 3:
            handlers[-1].request()

    monkeypatch.setattr(chaos.StepTrace, "record",
                        raise_the_flag_behind_step_3)
    ck = dict(checkpoint_dir=str(tmp_path / "ck"), checkpoint_every_steps=2)
    first = _real_run(tmp_path, "first", image_dataset, monkeypatch,
                      **kw, **ck)
    monkeypatch.setattr(chaos.StepTrace, "record", record)
    assert first["results"]["preempted"] is True
    assert first["results"]["steps"] == 3
    assert [r["step"] for r in first["records"]] == [1, 2, 3]
    # 1 ahead; 2: the checkpoint is due; 3: the flag, no step follows
    assert first["drains"] == {"train_drain_ahead_total": 1,
                               "train_drain_empty_total": 2}
    rest = _real_run(tmp_path, "rest", image_dataset, monkeypatch, **kw, **ck)
    assert "preempted" not in rest["results"]
    assert [t["step"] for t in rest["trace"]] == list(range(4, 11))
    assert first["trace"] + rest["trace"] == control["trace"]
    # a resumed run counts its records from 1: each loss once, in order
    assert [r["step"] for r in rest["records"]] == list(range(1, 8))
    assert [r["loss"] for r in first["records"] + rest["records"]] == [
        r["loss"] for r in control["records"]]
