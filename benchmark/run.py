#!/usr/bin/env python3
"""One run of one benchmark cell through ``cli.main(["train", ...])``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips for its whole life. It authors the cell's
data set from the seed (or finds it again), compares the program's model with
the plain reference, then calls the program's own entry point in this process
and measures it from outside: the clock is the loop's own log points (at each
the loop has just fetched the loss, so every step up to it is finished on the
device), the window opens at the first log point after warm-up and closes at
the last one before ``--seconds`` are over, and the run is stopped the way an
orchestrator stops a job, by SIGTERM. Everything that belongs to one cell,
configuration, traffic mix or metric is a file found by its name in
``BENCHMARK.json``; this file names none of them (see ``PERF.md``, recipes).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced, ``breakdown``);
everything else worth reading is on earlier lines. Anything but a TPU with the
cell's number of chips is refused at once, with no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 3.0
WARMUP_CAP_S = 600.0  # the window opens after this long whatever happened
KEEP_DATASETS = 3


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py``, found by the name in a data file."""
    path = os.path.join(HERE, folder, f"{name}.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"{folder}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def overlay(part: dict, over: dict) -> dict:
    """``over``'s keys laid on ``part``'s, one level deep into groups."""
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(part.get(key), dict):
            part[key] = {**part[key], **value}
        else:
            part[key] = value
    return part


def load_traffic(name: str) -> dict:
    """A traffic file; one that names a ``base`` is that mix with its own
    keys laid over it, so a variant states only what differs."""
    traffic = load_json("traffic", f"{name}.json")
    if "base" in traffic:
        traffic = overlay(load_traffic(traffic.pop("base")), traffic)
    return traffic


def load_cell(workload: str, rehearsal: bool = False) -> dict:
    """The cell's manifest entry and the files it points to; with
    ``rehearsal`` each file's ``rehearsal`` block overrides its real sizes."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next((w for w in manifest["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; have "
                         f"{[w['name'] for w in manifest['workloads']]}")
    config = load_json("configs", f"{entry['config']}.json")
    traffic = load_traffic(entry["traffic"])
    if rehearsal:
        for part in (config, traffic):
            overlay(part, part.get("rehearsal", {}))

    def wanted(kind):
        return [m for m in manifest[kind]
                if workload in m.get("workloads", [workload])]

    return {"name": workload, "entry": entry, "config": config,
            "traffic": traffic, "chips": int(entry["chips"]),
            "end_to_end": wanted("end_to_end"),
            "per_layer": wanted("per_layer")}


# -- data set ----------------------------------------------------------------


def author_dataset(traffic: dict, seed: int, data_root: str) -> tuple:
    """The cell's data set, keyed by generator, its version, the parameters
    and the seed, so that a later run with that seed finds it again."""
    generator = importlib.import_module(f"traffic.{traffic['generator']}")
    key = hashlib.sha1(json.dumps(
        [traffic["generator"], generator.VERSION, traffic["dataset"], seed],
        sort_keys=True).encode()).hexdigest()[:12]
    path = os.path.join(data_root, f"{traffic['generator']}-{key}")
    done = os.path.join(path, ".complete")
    if os.path.exists(done):
        os.utime(done)
        with open(done) as f:
            return generator, path, dict(json.load(f), found=True)
    os.makedirs(data_root, exist_ok=True)
    old = sorted((d for d in os.listdir(data_root)
                  if os.path.isdir(os.path.join(data_root, d))),
                 key=lambda d: os.path.getmtime(os.path.join(data_root, d)))
    for stale in old[:max(len(old) - (KEEP_DATASETS - 1), 0)]:
        shutil.rmtree(os.path.join(data_root, stale), ignore_errors=True)
    shutil.rmtree(path, ignore_errors=True)
    info = generator.generate(traffic["dataset"], seed, path,
                              len(os.sched_getaffinity(0)))
    info["bytes_on_disk"] = sum(
        os.path.getsize(os.path.join(d, f))
        for d, _, fs in os.walk(path) for f in fs)
    with open(done, "w") as f:
        json.dump(info, f)
    return generator, path, dict(info, found=False)


# -- the program's model against the plain reference -------------------------


def check_model(cell: dict, dataset_dir: str, seed: int) -> dict:
    """Eval-mode logits of the program's model, built through the public
    ``get_task`` and initialised from the seed, against the plain float32
    reference fed the same parameters, on the plain reader's first 8 rows.
    Initialisation, both forwards and the comparison are one jitted program
    each (two in all, both kept by the compile cache); only scalars come
    back to the host."""
    import jax
    import jax.numpy as jnp

    from lance_distributed_training_tpu.models import get_task

    from reference import reader

    config, traffic = cell["config"], cell["traffic"]
    ref = load_module("reference", config["name"])
    rows = reader.read_rows(dataset_dir, 0, 8)
    batch = getattr(ref, traffic.get("model_check", "eval_batch"))(rows, config)
    task = get_task(**config["task"])

    @jax.jit
    def make(key):
        key_init, key_perturb = jax.random.split(key)
        return ref.perturb(task.init_variables(key_init), key_perturb)

    @jax.jit
    def compare(variables, b):
        got = task.forward(variables, b, False, None)[0]
        got = (got[0] if isinstance(got, tuple) else got).astype(jnp.float32)
        want = ref.forward(variables, b)
        live = ref.live(b, want)  # dead slots mean nothing
        live = live.reshape(live.shape + (1,) * (want.ndim - live.ndim))
        n = live.sum() * (want.size // live.size)
        mean = jnp.where(live, want, 0).sum() / n
        spread = jnp.sqrt(jnp.where(live, (want - mean) ** 2, 0).sum() / n)
        worst = jnp.where(live, jnp.abs(got - want), 0).max()
        return worst, spread, jnp.isfinite(jnp.where(live, got, 0)).all()

    worst, spread, finite = (float(x) for x in compare(
        make(jax.random.key(seed)), batch))
    return {"worst_over_spread": worst / spread, "tolerance": ref.TOLERANCE,
            "spread": spread, "rows": len(rows),
            "ok": bool(finite and spread > 0
                       and worst / spread <= ref.TOLERANCE)}


# -- what the run looks like from outside ------------------------------------


class Observer:
    """Everything the benchmark notes while ``train()`` runs: a stamp on every
    record the loop logs, the first four batches as they sit on the device,
    every step's leaf shapes, every compilation, and the window's edges."""

    def __init__(self, seconds: float, trace: bool, out_dir: str,
                 min_log_points: int):
        self.seconds, self.trace, self.out_dir = seconds, trace, out_dir
        self.min_log_points = max(int(min_log_points), 2)
        self.log_points: list = []  # progress records, stamped
        self.epoch_ends: list = []  # (t_ns, epoch)
        self.compiles: list = []  # (t_ns, fun_name, seconds)
        self.batches: list = []  # steps 1..4, on the host
        self.step_shapes: list = []  # per step: {leaf: shape}
        self.step_times: list = []  # per step: t_ns after dispatch
        self.open_at = self.close_at = None  # indices into log_points
        self.trace_span = None  # (t_ns start, t_ns stop, wall_ns - mono_ns)
        self.warmup_capped = False
        self.t_train = None
        self.in_train = False
        self._stop_sent = False
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="bench-observer")
        self._done = threading.Event()

    # hooks, installed from the benchmark's side; the program is not edited
    def install(self):
        import jax.monitoring
        import numpy as np

        from lance_distributed_training_tpu.obs.registry import (
            default_registry,
        )
        from lance_distributed_training_tpu.utils import chaos, metrics

        obs = self
        registry = default_registry()

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                obs.compiles.append((time.monotonic_ns(),
                                     kw.get("fun_name", "?"), duration))

        jax.monitoring.register_event_duration_secs_listener(on_duration)

        original_log = metrics.MetricLogger.log

        def log(logger, record, *args, **kwargs):
            t = time.monotonic_ns()
            if "images_per_sec_dispatch" in record:
                obs.log_points.append({
                    "t": t, "step": int(record["step"]),
                    "epoch": int(record["epoch"]),
                    "loss": float(record["loss"]),
                    "compiles": len(obs.compiles),
                    "counters": {n: m.value
                                 for n, m in registry.metrics().items()
                                 if hasattr(m, "value")}})
            elif "epoch_time" in record:
                obs.epoch_ends.append((t, int(record["epoch"])))
            return original_log(logger, record, *args, **kwargs)

        metrics.MetricLogger.log = log

        class Capture:
            """Stands in for the trainer's per-step trace writer: copies
            steps 1-4 to the host (during warm-up, outside the window) and
            afterwards only notes shapes, so no D2H falls inside it."""

            @classmethod
            def from_env(cls, env=None):
                return cls()

            def record(self, step, epoch, batch, loss):
                if step <= 4:
                    obs.batches.append({k: np.asarray(v)
                                        for k, v in batch.items()
                                        if hasattr(v, "shape")})
                obs.step_shapes.append({k: tuple(v.shape)
                                        for k, v in batch.items()
                                        if hasattr(v, "shape")})
                obs.step_times.append(time.monotonic_ns())

            def close(self):
                pass

        original_trace = chaos.StepTrace
        chaos.StepTrace = Capture
        signal.signal(signal.SIGTERM, lambda *_: None)  # never the default

        def restore():
            metrics.MetricLogger.log = original_log
            chaos.StepTrace = original_trace

        return restore

    def start(self):
        self.t_train = time.monotonic()
        self.in_train = True
        self._thread.start()

    def finish(self):
        self.in_train = False
        self._done.set()
        self._thread.join()

    # the window
    def _quiet(self, i: int) -> bool:
        """No compilation between log points ``i - 1`` and ``i``. The
        autotuner's decisions are counted and printed but do not hold the
        window back: on the chip it never settles (a decision every 8 to 15
        s for as long as a run lasts, PERF.md section 6), and waiting for a
        quiet interval moved the window by a log point from run to run."""
        return self.log_points[i - 1]["compiles"] == \
            self.log_points[i]["compiles"]

    def _stop(self):
        if not self._stop_sent and self.in_train:
            self._stop_sent = True
            os.kill(os.getpid(), signal.SIGTERM)

    def _watch(self):
        seen = 0
        tracing = None
        while not self._done.wait(0.02):
            now = time.monotonic()
            points = self.log_points
            while seen < len(points):
                i, seen = seen, seen + 1
                if self.open_at is None:
                    capped = now - self.t_train > WARMUP_CAP_S
                    if i + 1 >= self.min_log_points and (
                            self._quiet(i) or capped):
                        self.open_at, self.warmup_capped = i, capped
                        self.deadline = points[i]["t"] + int(
                            self.seconds * 1e9)
                elif self.close_at is None:
                    inside = points[self.open_at:i + 1]
                    gaps = [b["t"] - a["t"]
                            for a, b in zip(inside, inside[1:])]
                    if points[i]["t"] > self.deadline:
                        # no log point fell inside: close here, a little long
                        self.close_at = i if i - 1 == self.open_at else i - 1
                    elif points[i]["t"] + 1.05 * statistics.median(gaps) \
                            > self.deadline:
                        self.close_at = i  # the next one would be too late
            if self.open_at is not None and self.trace and tracing is None:
                t_open = points[self.open_at]["t"] / 1e9
                if now >= t_open + max(self.seconds / 2 - TRACE_SECONDS / 2,
                                       1.0):
                    tracing = self._start_trace()
            if tracing is not None and self.trace_span is None \
                    and time.monotonic() >= tracing[0] + TRACE_SECONDS:
                self._stop_trace(tracing)
            if self.close_at is not None and (
                    not self.trace or self.trace_span is not None):
                self._stop()
            elif now - self.t_train > WARMUP_CAP_S + 4 * self.seconds:
                self._stop()  # the loop logs nothing: end it, with no result
        if tracing is not None and self.trace_span is None:
            self._stop_trace(tracing)

    def _start_trace(self):
        import jax

        options = jax.profiler.ProfileOptions()
        # the host's story comes from the program's spans; the runtime's own
        # host events (one per image row transposed on its way to the
        # device) made the first image trace 421 MB and 19 s long
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        anchor = time.time_ns() - time.monotonic_ns()
        jax.profiler.start_trace(os.path.join(self.out_dir, "profile"),
                                 profiler_options=options)
        return (time.monotonic(), time.monotonic_ns(), anchor)

    def _stop_trace(self, tracing):
        import jax

        t_stop = time.monotonic_ns()
        jax.profiler.stop_trace()
        self.trace_span = (tracing[1], t_stop, tracing[2])


# -- one run -----------------------------------------------------------------


def cache_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", rehearsal: bool = False) -> tuple:
    """Returns ``(exit code, result or None)``. ``platform`` and
    ``rehearsal`` are for ``benchmark/rehearse.py`` alone; this file's own
    entry point always asks for a TPU at the cell's real sizes."""
    for path in (HERE, ROOT):  # the program, and this directory's packages
        if path not in sys.path:
            sys.path.insert(0, path)
    cell = load_cell(workload, rehearsal)
    config, traffic, chips = cell["config"], cell["traffic"], cell["chips"]

    import jax

    devices = jax.devices()
    found = (devices[0].platform, len(devices))
    if found != (platform, chips):
        sys.stderr.write(
            f"{workload} needs {chips} {platform} device(s); JAX found "
            f"{found[1]} of platform {found[0]!r} "
            f"({devices[0].device_kind!r})\n")
        return 3, None
    kind = devices[0].device_kind
    peaks = load_json("peaks.json").get(kind)
    if peaks is None and not rehearsal:
        sys.stderr.write(f"no peaks for device_kind {kind!r} in "
                         "benchmark/peaks.json: add them with their source\n")
        return 3, None
    say(f"cell={workload} seed={seed} seconds={seconds} trace={int(trace)} "
        f"platform={found[0]} device_kind={kind!r} count={chips}")

    from lance_distributed_training_tpu import cli, trainer
    from lance_distributed_training_tpu.obs import spans as program_spans

    # one run's files at a time: the last run's stay until the next begins
    # (benchmark/fixtures/make_fixture.py cuts a fixture from a traced one)
    runs_dir = os.path.join(HERE, "out", "rehearsal" if rehearsal else "runs")
    shutil.rmtree(runs_dir, ignore_errors=True)
    out_dir = os.path.join(runs_dir, workload, f"seed{seed}-trace{int(trace)}")
    os.makedirs(out_dir)
    os.environ["LDT_METRICS_PATH"] = os.path.join(out_dir, "metrics.jsonl")
    span_path = os.path.join(out_dir, "spans.jsonl")
    if trace:
        os.environ["LDT_TRACE_PATH"] = span_path
    else:
        os.environ.pop("LDT_TRACE_PATH", None)
    # the program places the cache (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache); the model check below compiles before train()
    cache_dir = trainer.maybe_enable_compile_cache(found[0])
    entries_before = cache_entries(cache_dir)

    t = time.monotonic()
    generator, dataset_dir, info = author_dataset(
        traffic, seed, os.path.join(HERE, "data"))
    authoring_s = time.monotonic() - t
    say(f"data set: {dataset_dir} {json.dumps(info)} authoring_s="
        f"{authoring_s:.2f}")
    batch = int(traffic["global_batch"])
    plan = generator.Plan(traffic["dataset"], seed, batch)

    t = time.monotonic()
    model = check_model(cell, dataset_dir, seed)
    model_check_s = time.monotonic() - t
    say(f"model against plain reference: {json.dumps(model)} "
        f"model_check_s={model_check_s:.2f}")

    obs = Observer(seconds, trace, out_dir,
                   traffic.get("warmup_min_log_points", 2))
    restore = obs.install()
    argv = ["train", "--dataset_path", dataset_dir, "--batch_size", str(batch),
            "--epochs", "1000000", "--seed", str(seed), "--no_wandb",
            "--no_eval_at_end", *config["train_flags"],
            *traffic["train_flags"]]
    say("argv: " + " ".join(argv))
    usage0 = _rusage()
    obs.start()
    try:
        results = cli.main(argv)
    finally:
        obs.finish()
        restore()
    usage1 = _rusage()
    train_s = time.monotonic() - obs.t_train
    if obs.open_at is None or obs.close_at is None:
        sys.stderr.write(f"no window: {len(obs.log_points)} log points, "
                         f"open={obs.open_at} close={obs.close_at}\n")
        return 4, None
    program_spans.default_tracer().close()

    lo, hi = obs.log_points[obs.open_at], obs.log_points[obs.close_at]
    window_s = (hi["t"] - lo["t"]) / 1e9
    steps = hi["step"] - lo["step"]
    compiles_in_window = hi["compiles"] - lo["compiles"]
    setup_s = lo["t"] / 1e9 - T_PROCESS
    warmup_s = lo["t"] / 1e9 - obs.t_train
    first_step_s = (obs.step_times[0] / 1e9 - obs.t_train
                    if obs.step_times else float("nan"))
    say(f"window: {window_s:.3f} s, {steps} steps, {obs.close_at - obs.open_at}"
        f" log intervals, steps {lo['step']}..{hi['step']}, "
        f"window_compiles={compiles_in_window}, warmup_capped="
        f"{obs.warmup_capped}, autotune_decisions_in_window="
        f"{hi['counters'].get('autotune_decisions_total', 0) - lo['counters'].get('autotune_decisions_total', 0):.0f}"
        f", epoch_ends_in_window="
        f"{sum(1 for t, _ in obs.epoch_ends if lo['t'] < t <= hi['t'])}")
    say(f"set-up: setup_s={setup_s:.2f} = to_devices+imports "
        f"{setup_s - authoring_s - model_check_s - warmup_s:.2f} + authoring "
        f"{authoring_s:.2f} + model check {model_check_s:.2f} + train() to "
        f"window {warmup_s:.2f} (first step dispatched after "
        f"{first_step_s:.2f}); preempted={results.get('preempted')} "
        f"train_s={train_s:.2f}")
    say("compilations: " + json.dumps(
        [(round(t / 1e9 - obs.t_train, 2), n, round(d, 2))
         for t, n, d in obs.compiles if d >= 0.5]))

    samples = plan.samples(lo["step"], hi["step"])
    scheduled = plan.scheduled(lo["step"], hi["step"])
    counters = {k: hi["counters"].get(k, 0) - lo["counters"].get(k, 0)
                for k in hi["counters"]}
    losses = [p["loss"] for p in obs.log_points[obs.open_at:obs.close_at + 1]]
    truncated = int(counters.get("pack_truncated_tokens_total", 0))
    failed = truncated + (0 if all(map(math.isfinite, losses)) else scheduled)

    problems = load_module("batch_checks", traffic["batch_check"]).check(
        obs.batches[:4], dataset_dir, batch, traffic, config)
    if len(obs.batches) < 4:
        problems.append(f"only {len(obs.batches)} batches were captured")
    say(f"first 4 device batches against the plain reader: "
        f"{'equal' if not problems else problems}")
    correct = bool(model["ok"] and not problems and failed == 0)

    memory = [d.memory_stats() or {} for d in jax.local_devices()]
    # this runtime keeps a program's scratch apart from its buffers:
    # peak_bytes_in_use counts buffers, peak_bytes_reserved the scratch
    peak_bytes = max((m.get("peak_bytes_in_use", 0)
                      + m.get("peak_bytes_reserved", 0)) for m in memory)
    cpu_s = (usage1[0] - usage0[0])
    say(f"host: cpu_s_over_train={cpu_s:.1f} ({cpu_s / max(train_s, 1e-9):.2f}"
        f" cores), peak_rss_gib={usage1[1] / 2**20:.2f}; device memory: "
        + json.dumps([{k: m.get(k) for k in ("peak_bytes_in_use",
                                              "peak_bytes_reserved")}
                      for m in memory]))

    ctx = {
        "cell": cell, "chips": chips, "peaks": peaks, "plan": plan,
        "window_ns": (lo["t"], hi["t"]), "window_s": window_s,
        "steps": steps, "samples": samples, "setup_s": setup_s,
        "peak_bytes": peak_bytes, "counters": counters,
        "compiles_in_window": compiles_in_window,
        "cache_new_entries": cache_entries(cache_dir) - entries_before,
        "cache_was_empty": entries_before == 0,
        "step_shapes": obs.step_shapes[lo["step"]:hi["step"]],
        "all_step_shapes": obs.step_shapes,
        "epoch_ends": obs.epoch_ends, "log_points": obs.log_points,
        "flops": load_module("flops", config["name"]),
        "spans": [], "trace": None,
    }
    rates = {}
    for metric in cell["end_to_end"]:
        value = load_module("end_to_end", metric["name"]).read(ctx)
        if value is not None:
            rates[metric["name"]] = {"value": value, "unit": metric["unit"]}
    say(("traced" if trace else "untraced") + " run, end to end: "
        + json.dumps(rates))

    device = {"platform": found[0], "kind": kind, "count": chips,
              "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": correct, "attempted": int(scheduled),
              "failed": int(failed), "metrics": rates, "device": device}
    if trace:
        from reduce import breakdown, spans, xplane

        ctx["spans"] = spans.read(span_path)
        ctx["trace"] = xplane.reduce_profile(
            os.path.join(out_dir, "profile"), obs.trace_span)
        say("trace: " + json.dumps(ctx["trace"]["summary"]))
        with open(os.path.join(out_dir, "trace_span.json"), "w") as f:
            json.dump(obs.trace_span, f)  # for fixtures/make_fixture.py
        layer = {}
        for metric in cell["per_layer"]:
            reader_module = load_module("layer_metrics", metric["name"])
            value = reader_module.read(ctx) if reader_module else None
            if value is not None and math.isfinite(value):
                layer[metric["name"]] = {"value": value,
                                         "unit": metric["unit"]}
        result["metrics"] = layer
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        result["breakdown"] = breakdown.make(ctx)
        result["correct"] = bool(correct and (device["busy_s"] > 0
                                             or rehearsal))
    return 0, result


def _rusage():
    import resource

    u = resource.getrusage(resource.RUSAGE_SELF)
    return u.ru_utime + u.ru_stime, u.ru_maxrss


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    code, result = run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace))
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
