#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, holding the chip for its whole life, drives the main path once
through the entry point a user calls — ``cli.main(["train", ...])`` at
ResNet-50 width: columnar iterable loader, sharded-batch plan, global batch
128 x local devices, every default on (buffer pool, placement ring,
autotuner, native libjpeg decode, eval at end), 12 steps on a dataset it
authors from a seed — and checks what came out:

* JAX found a TPU (anything else fails at once, naming what was found);
* the native decoder was built by this machine from ``ldt_decode.cpp``;
* 12 steps ran, every loss is finite, ``train_acc`` exists;
* the batch that reached HBM is the batch the host decoded, step for step
  (sha256 of the device batch against the same plan decoded on the host);
* with several devices: one 128-row shard of every batch leaf on each,
  parameters replicated on all, memory in use on all.

It prints per-phase wall time, the compile cache in use, HBM figures, and
whether ``block_until_ready`` waits. The last line of stdout is one JSON
object, printed only when every check passed; any failure exits non-zero.

``--also`` adds opt-in checks, each a few steps through the same CLI:
``flash`` (Pallas attention, forced and chosen, against dense, bert_base at seq 512),
``device_decode`` and ``token_pack`` (device kernels against their host
twins), ``workers`` (spawned decode workers beside the chip's holder),
``service`` (a ``serve-data`` child on the trainer's host) and
``ddp_parity`` (global batch 128 on all devices against ``--no_ddp``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 20260926
STEPS = 12
PER_DEVICE_BATCH = 128
ALSO = ("flash", "device_decode", "token_pack", "workers", "service",
        "ddp_parity")


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}", flush=True)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- the probe: what each step looked like from the host -------------------


def install_probe(keep=()):
    """Swap the trainer's per-step trace writer (``LDT_STEP_TRACE_PATH``)
    for one that also notes, before the digest fetches anything: how long
    ``block_until_ready(loss)`` and then ``float(loss)`` take, where the
    batch's shards sit, and each device's memory. Returns ``(log,
    restore)``; ``keep`` names batch leaves to copy to the host at step 1."""
    import jax
    import numpy as np

    from lance_distributed_training_tpu.utils import chaos

    log: list = []
    original = chaos.StepTrace

    class ProbedTrace(original):
        def record(self, step, epoch, batch, loss):
            t0 = time.perf_counter()
            jax.block_until_ready(loss)
            t1 = time.perf_counter()
            float(loss)
            t2 = time.perf_counter()
            obs = {
                "ready_ms": (t1 - t0) * 1e3,
                "fetch_ms": (t2 - t1) * 1e3,
                "shards": {
                    k: [(s.device.id, s.data.shape[0])
                        for s in v.addressable_shards]
                    for k, v in batch.items() if hasattr(v, "sharding")
                },
                "bytes_in_use": [
                    (d.memory_stats() or {}).get("bytes_in_use")
                    for d in jax.local_devices()
                ],
                "replicated_bytes": sum(
                    a.nbytes for a in jax.live_arrays()
                    if a.is_fully_replicated
                    and len(a.sharding.device_set) == jax.device_count()
                ),
            }
            if not log:
                obs["kept"] = {k: np.asarray(batch[k]) for k in keep}
            super().record(step, epoch, batch, loss)
            obs["t_done"] = time.perf_counter()
            log.append(obs)

    chaos.StepTrace = ProbedTrace

    def restore():
        chaos.StepTrace = original

    return log, restore


def run_train(workdir: str, tag: str, argv: list, keep=()):
    """One ``cli.main(["train", ...])`` call with the step trace on.
    Returns ``(results, trace_records, probe_log, seconds)``."""
    from lance_distributed_training_tpu import cli
    from lance_distributed_training_tpu.utils.chaos import (
        TRACE_ENV,
        read_trace,
    )

    trace_path = os.path.join(workdir, f"{tag}.trace.jsonl")
    os.environ[TRACE_ENV] = trace_path
    os.environ["LDT_METRICS_PATH"] = os.path.join(workdir,
                                                  f"{tag}.metrics.jsonl")
    log, restore = install_probe(keep)
    t0 = time.perf_counter()
    try:
        results = cli.main(["train", *argv, "--no_wandb"])
    finally:
        restore()
        os.environ.pop(TRACE_ENV, None)
    return results, read_trace(trace_path), log, time.perf_counter() - t0


def check_losses(trace: list, results: dict, steps: int) -> list:
    losses = [r["loss"] for r in trace]
    check(results["steps"] == steps and len(trace) == steps,
          f"{steps} steps ran (results['steps']={results['steps']}, "
          f"{len(trace)} traced)")
    check(all(math.isfinite(x) for x in losses),
          "every loss is finite: " + " ".join(f"{x:.4f}" for x in losses))
    return losses


def host_batches(uri: str, decode, global_batch: int, steps: int):
    """The same plan the trainer built, decoded on the host and kept there
    (as bench.py builds its decode-only pipeline): fresh allocations, no
    buffer pool, no device."""
    from lance_distributed_training_tpu.data import (
        Dataset,
        make_train_pipeline,
    )

    pipe = make_train_pipeline(
        Dataset(uri), "batch", global_batch, 0, 1, decode,
        prefetch=2, producers=4,
    )
    it = iter(pipe)
    try:
        for _ in range(steps):
            yield next(it)
    finally:
        it.close()  # stops the producer threads


def check_digests(trace: list, uri: str, image_size: int, global_batch: int,
                  what: str) -> None:
    from lance_distributed_training_tpu.data import ImageClassificationDecoder
    from lance_distributed_training_tpu.utils.chaos import batch_digest

    want = [
        batch_digest(b) for b in host_batches(
            uri, ImageClassificationDecoder(image_size=image_size),
            global_batch, len(trace))
    ]
    got = [r["batch_sha256"] for r in trace]
    bad = [i + 1 for i, (g, w) in enumerate(zip(got, want)) if g != w]
    check(not bad and len(got) == len(want),
          f"{what}: device batch == host batch for all {len(want)} steps"
          + (f" — MISMATCH at steps {bad}" if bad else ""))


def resnet_argv(uri, backend, model_name, image_size, global_batch, steps):
    return [
        "--dataset_path", uri, "--backend", backend,
        "--model_name", model_name, "--image_size", str(image_size),
        "--num_classes", "101", "--loader_style", "iterable",
        "--sampler_type", "batch", "--batch_size", str(global_batch),
        "--epochs", "1", "--max_steps", str(steps),
    ]


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# -- item 1: the main path ---------------------------------------------------


def main_path(workdir: str, *, backend: str = "tpu",
              model_name: str = "resnet50", image_size: int = 224,
              per_device_batch: int = PER_DEVICE_BATCH,
              steps: int = STEPS) -> dict:
    import jax

    from lance_distributed_training_tpu.data import (
        create_synthetic_classification_dataset,
    )
    from lance_distributed_training_tpu.models import get_task
    from lance_distributed_training_tpu.native import jpeg as native_jpeg

    n = jax.device_count()
    global_batch = per_device_batch * n
    rows = steps * global_batch
    report: dict = {"devices": n, "global_batch": global_batch}

    say("== native decoder ==")
    lib = native_jpeg.library_path()
    was_there = os.path.exists(lib)
    check(native_jpeg.native_available() and os.path.exists(lib),
          f"native decoder loaded from {os.path.relpath(lib, REPO)} "
          f"({'found, same source+command+CPU' if was_there else 'built now'}"
          ")")

    say(f"== authoring {rows} unique {image_size}px JPEGs (seed {SEED}) ==")
    t = time.perf_counter()
    uri = os.path.join(workdir, "food101")
    create_synthetic_classification_dataset(
        uri, rows, num_classes=101, image_size=image_size,
        fragment_size=max(rows // 4, 1), unique_images=rows, seed=SEED,
    )
    report["authoring_s"] = time.perf_counter() - t
    report["uri"] = uri

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache")
    entries_before = cache_entries(cache_dir)

    say(f"== train: {model_name} {image_size}px, global batch "
        f"{global_batch} on {n} device(s), {steps} steps ==")
    t_start = time.perf_counter()
    results, trace, log, _ = run_train(
        workdir, "main",
        resnet_argv(uri, backend, model_name, image_size, global_batch,
                    steps))
    t_end = time.perf_counter()
    report["first_step_s"] = log[0]["t_done"] - t_start if log else None
    report["remaining_steps_s"] = (
        log[-1]["t_done"] - log[0]["t_done"] if log else None)
    report["eval_and_teardown_s"] = t_end - log[-1]["t_done"] if log else None

    say("== checks ==")
    dev = jax.devices()[0]
    check(
        (results.get("platform"), results.get("device_kind"),
         results.get("device_count")) == (dev.platform, dev.device_kind, n),
        f"train() result names the device: platform={results.get('platform')}"
        f" device_kind={results.get('device_kind')!r} "
        f"device_count={results.get('device_count')}")
    report["losses"] = check_losses(trace, results, steps)
    acc = results.get("train_acc")
    check(acc is not None and math.isfinite(acc),
          f"eval ran: train_acc={acc}")
    check_digests(trace, uri, image_size, global_batch, "buffer pool premise")

    in_use = jax.config.jax_compilation_cache_dir
    if backend == "tpu":
        placed_by = ("JAX_COMPILATION_CACHE_DIR"
                     if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                     else "<checkout>/.jax_cache")
        check(in_use == cache_dir,
              f"compile cache is {in_use!r} ({placed_by})")
    report["compile_cache"] = {
        "dir": in_use, "entries_before": entries_before,
        "entries_after": cache_entries(in_use) if in_use else 0,
    }

    if n > 1:
        for leaf, shards in log[0]["shards"].items():
            check(len({d for d, _ in shards}) == n
                  and all(r == per_device_batch for _, r in shards),
                  f"batch leaf {leaf!r}: one {per_device_batch}-row shard on "
                  f"each of {n} devices {shards}")
        task = get_task("classification", num_classes=101,
                        model_name=model_name, image_size=image_size)
        shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
        param_bytes = sum(
            math.prod(x.shape) * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(shapes["params"]))
        check(log[-1]["replicated_bytes"] >= param_bytes,
              f"{log[-1]['replicated_bytes']} bytes live and fully "
              f"replicated on all {n} devices (parameters alone: "
              f"{param_bytes})")
        if backend == "tpu":
            check(all(b for b in log[-1]["bytes_in_use"]),
                  f"memory in use on every device: {log[-1]['bytes_in_use']}")

    report["sync"] = [(o["ready_ms"], o["fetch_ms"]) for o in log]
    report["hbm"] = [
        {k: (d.memory_stats() or {}).get(k)
         for k in ("peak_bytes_in_use", "bytes_limit")}
        for d in jax.local_devices()
    ]
    return report


def u8_batch_hbm_bytes(per_device_batch: int, image_size: int):
    """How much HBM the device layout gives one [B, S, S, 3] uint8 batch
    shard (minor dimension 3), against its host size."""
    import jax
    import numpy as np

    dev = jax.local_devices()[0]
    host = np.zeros((per_device_batch, image_size, image_size, 3), np.uint8)
    before = dev.memory_stats()["bytes_in_use"]
    arr = jax.block_until_ready(jax.device_put(host, dev))
    after = dev.memory_stats()["bytes_in_use"]
    del arr
    return host.nbytes, after - before


def sync_probe():
    """Does ``block_until_ready`` wait? One jitted chain of 200 4096^3
    float32 matmuls ending in a scalar, compiled and run once beforehand so
    nothing but the device's work stands between dispatch and the value:
    time the wait, then the value fetch after it, in ms."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(0, 200, lambda _, y: y @ x, x)[0, 0]

    x = jnp.full((4096, 4096), 1.0 / 4096, jnp.float32)
    float(chain(x))
    out = chain(x)
    t0 = time.perf_counter()
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    float(out)
    t2 = time.perf_counter()
    return (t1 - t0) * 1e3, (t2 - t1) * 1e3


def print_report(report: dict) -> None:
    say("== phases (wall seconds) ==")
    say(f"  authoring                    {report['authoring_s']:8.1f}")
    say(f"  first step (init + compile)  {report['first_step_s']:8.1f}")
    say(f"  steps 2..{STEPS} (each fetched back for its digest) "
        f"{report['remaining_steps_s']:8.1f}")
    say(f"  eval + teardown              {report['eval_and_teardown_s']:8.1f}")
    cc = report["compile_cache"]
    say(f"== compile cache: {cc['dir']} entries {cc['entries_before']} -> "
        f"{cc['entries_after']} ==")
    say("== HBM ==")
    for i, m in enumerate(report["hbm"]):
        say(f"  device {i}: peak_bytes_in_use={m['peak_bytes_in_use']} "
            f"bytes_limit={m['bytes_limit']}")
    if "u8_batch" in report:
        host, hbm = report["u8_batch"]
        say(f"  one [{PER_DEVICE_BATCH},224,224,3] uint8 batch shard: "
            f"{host} bytes on the host, {hbm} bytes of HBM "
            f"({hbm / host:.2f}x)")
    say("== does block_until_ready wait? ==")
    ready, fetch = report["sync"][-1]
    say(f"  last step's loss: block_until_ready {ready:.2f} ms, then "
        f"float() {fetch:.2f} ms")
    if "sync_probe" in report:
        ready, fetch = report["sync_probe"]
        say(f"  one jitted chain of 200 4096^3 f32 matmuls: "
            f"block_until_ready {ready:.2f} ms, then float() {fetch:.2f} ms")
        say("  verdict: " + (
            "it waits (the fetch after it finds the value ready)"
            if fetch < 0.1 * ready or fetch < 1.0 else
            "it returns EARLY (the fetch after it still had to wait)"))


# -- opt-in checks -----------------------------------------------------------


def also_flash(workdir: str, ctx: dict) -> str:
    """bert_base at seq 512 on the Pallas kernel (forward and backward, head
    dimension 64, padding as SegmentIds) against the dense arm."""
    import numpy as np

    from lance_distributed_training_tpu.data import create_text_token_dataset

    batch, steps, seq = 16 * ctx["n"], 4, 512
    gen = np.random.default_rng(SEED)
    docs = [gen.integers(2, 30522, gen.integers(seq // 2, seq + 1)).tolist()
            for _ in range(batch * steps)]
    uri = os.path.join(workdir, "tokens512")
    create_text_token_dataset(uri, docs, seq_len=seq, pack=False,
                              fragment_size=batch * steps)
    argv = ["--dataset_path", uri, "--backend", ctx["backend"],
            "--task_type", "masked_lm", "--model_name", ctx["text_model"],
            "--seq_len", str(seq), "--batch_size", str(batch),
            "--epochs", "1", "--max_steps", str(steps), "--no_eval_at_end"]
    from lance_distributed_training_tpu.ops import flash as flash_ops

    res_f, trace_f, _, secs_f = run_train(
        workdir, "flash", argv + ["--flash_attention"])
    flash = check_losses(trace_f, res_f, steps)
    # without the flag the rule picks the kernel too (since PR 29)
    res_c, trace_c, _, secs_c = run_train(workdir, "chosen", argv)
    chosen = check_losses(trace_c, res_c, steps)
    check(res_c.get("attention_fused") == float(ctx["backend"] == "tpu"),
          "without the flag the rule chose the kernel at 512 x 64 "
          f"(attention_fused={res_c.get('attention_fused')})")
    rule = flash_ops.fused_attention_applies
    flash_ops.fused_attention_applies = lambda *a, **k: False  # dense arm
    try:
        res_d, trace_d, _, secs_d = run_train(workdir, "dense", argv)
    finally:
        flash_ops.fused_attention_applies = rule
    dense = check_losses(trace_d, res_d, steps)
    for name, arm in (("flash", flash), ("chosen", chosen)):
        rel = abs(arm[0] - dense[0]) / abs(dense[0])
        check(rel <= 2e-2,
              f"step-1 loss {name} {arm[0]:.5f} vs dense {dense[0]:.5f} "
              f"(rel {rel:.2e} <= 2e-2)")
    return (f"flash {secs_f:.0f}s chosen {secs_c:.0f}s dense {secs_d:.0f}s; "
            f"losses flash {[round(x, 4) for x in flash]} chosen "
            f"{[round(x, 4) for x in chosen]} dense "
            f"{[round(x, 4) for x in dense]}")


def also_device_decode(workdir: str, ctx: dict) -> str:
    """The integer IDCT + resize kernel against host libjpeg, within the
    envelope the CPU tests pin."""
    import numpy as np

    from lance_distributed_training_tpu.data import ImageClassificationDecoder
    from lance_distributed_training_tpu.ops.jpeg_device import (
        HOST_PARITY_MAX_ABS_DIFF,
    )

    steps, gb = 4, ctx["per_device_batch"] * ctx["n"]
    argv = resnet_argv(ctx["uri"], ctx["backend"], ctx["model_name"],
                       ctx["image_size"], gb, steps)
    results, trace, log, secs = run_train(
        workdir, "device_decode",
        argv + ["--device_decode"], keep=("image",))
    check_losses(trace, results, steps)
    host = next(host_batches(
        ctx["uri"], ImageClassificationDecoder(image_size=ctx["image_size"]),
        gb, 1))["image"]
    dev = log[0]["kept"]["image"]
    diff = int(np.abs(dev.astype(np.int32) - host.astype(np.int32)).max())
    check(dev.shape == host.shape and diff <= HOST_PARITY_MAX_ABS_DIFF,
          f"device-decoded batch {dev.shape} vs host libjpeg: max abs diff "
          f"{diff} <= {HOST_PARITY_MAX_ABS_DIFF}")
    return f"{secs:.0f}s; max abs diff {diff}"


def also_token_pack(workdir: str, ctx: dict) -> str:
    """The pack scatter against a numpy twin built from the host's ragged
    batch and pack plan."""
    import numpy as np

    from lance_distributed_training_tpu.data.authoring import (
        create_variable_length_token_dataset,
    )
    from lance_distributed_training_tpu.data.decode import decoder_for_task
    from lance_distributed_training_tpu.data.token_pack import (
        OFFSETS_SUFFIX,
        PACK_META_KEY,
        PACK_SLOT_KEY,
        PACK_START_KEY,
        VALUES_SUFFIX,
        TokenPackConfig,
    )

    batch, steps, seq = 64 * ctx["n"], 4, 128
    uri = os.path.join(workdir, "ragged")
    create_variable_length_token_dataset(
        uri, batch * steps, vocab_size=30522, max_len=seq, seed=SEED,
        fragment_size=batch * steps)
    results, trace, log, secs = run_train(
        workdir, "token_pack",
        ["--dataset_path", uri, "--backend", ctx["backend"],
         "--task_type", "masked_lm", "--model_name", ctx["text_model"],
         "--seq_len", str(seq), "--batch_size", str(batch), "--epochs", "1",
         "--max_steps", str(steps), "--no_eval_at_end", "--token_pack"],
        keep=("input_ids", "segment_ids", "position_ids"))
    check_losses(trace, results, steps)
    decode = decoder_for_task(
        "masked_lm", 0, seq_len=seq,
        token_pack=TokenPackConfig(pack_len=seq, rows_multiple=8,
                                   rows_align=ctx["n"]))
    ragged = next(host_batches(uri, decode, batch, 1))
    rows, pack_len = (int(x) for x in ragged[PACK_META_KEY][:2])
    values = ragged["input_ids" + VALUES_SUFFIX]
    offsets = ragged["input_ids" + OFFSETS_SUFFIX]
    grid = np.zeros((rows, pack_len), values.dtype)
    seg = np.zeros((rows, pack_len), np.int32)
    pos = np.zeros((rows, pack_len), np.int32)
    for i, (row, st) in enumerate(zip(ragged[PACK_SLOT_KEY],
                                      ragged[PACK_START_KEY])):
        tokens = values[offsets[i]:offsets[i + 1]][:pack_len]
        grid[row, st:st + len(tokens)] = tokens
        seg[row, st:st + len(tokens)] = i + 1
        pos[row, st:st + len(tokens)] = np.arange(len(tokens))
    kept = log[0]["kept"]
    check(all(np.array_equal(kept[k], want) for k, want in
              (("input_ids", grid), ("segment_ids", seg),
               ("position_ids", pos))),
          f"packed ids/segments/positions {grid.shape} equal the numpy twin "
          f"({len(offsets) - 1} sequences)")
    return f"{secs:.0f}s; grid {grid.shape}"


def also_workers(workdir: str, ctx: dict) -> str:
    """Two spawned decode workers import the package (and with it jax)
    while this process holds the chip: none of them may claim it."""
    steps = 4
    gb = ctx["per_device_batch"] * ctx["n"]
    results, trace, _, secs = run_train(
        workdir, "workers",
        resnet_argv(ctx["uri"], ctx["backend"], ctx["model_name"],
                    ctx["image_size"], gb, steps)
        + ["--num_workers", "2", "--no_eval_at_end"])
    check_losses(trace, results, steps)
    check_digests(trace, ctx["uri"], ctx["image_size"], gb,
                  "through 2 worker processes")
    return f"{secs:.0f}s"


def also_service(workdir: str, ctx: dict) -> str:
    """``serve-data`` started on the trainer's host with the environment
    as it stands (no JAX_PLATFORMS override): it must serve and stay off
    the chip this process holds."""
    import socket

    steps, port = 4, 18477
    gb = ctx["per_device_batch"] * ctx["n"]
    log_path = os.path.join(workdir, "serve.log")
    with open(log_path, "w") as out:
        server = subprocess.Popen(
            [sys.executable, "-m", "lance_distributed_training_tpu.cli",
             "serve-data", "--dataset_path", ctx["uri"],
             "--host", "127.0.0.1", "--port", str(port),
             "--image_size", str(ctx["image_size"]), "--log_every_s", "0"],
            cwd=REPO, stdout=out, stderr=subprocess.STDOUT,
        )
    try:
        deadline = time.monotonic() + 120
        while True:
            if server.poll() is not None:
                raise CheckFailed(
                    f"serve-data exited {server.returncode}:\n"
                    + open(log_path).read()[-2000:])
            try:
                socket.create_connection(("127.0.0.1", port), 1).close()
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise CheckFailed("serve-data never listened:\n"
                                      + open(log_path).read()[-2000:])
                time.sleep(0.5)
        results, trace, _, secs = run_train(
            workdir, "service",
            resnet_argv(ctx["uri"], ctx["backend"], ctx["model_name"],
                        ctx["image_size"], gb, steps)
            + ["--data_service", f"127.0.0.1:{port}", "--no_eval_at_end"])
        check_losses(trace, results, steps)
        check_digests(trace, ctx["uri"], ctx["image_size"], gb,
                      "through the data service")
        check(server.poll() is None, "serve-data still alive after the run")
    finally:
        server.terminate()
        try:
            server.wait(10)
        except subprocess.TimeoutExpired:
            server.kill()
            server.wait()
    return f"{secs:.0f}s"


def also_ddp_parity(workdir: str, ctx: dict) -> str:
    """The jitted step is a global-view program: the same seed at global
    batch 128 on all devices and on one gives the same loss sequence, to
    the tolerance of bfloat16 compute reduced in another order."""
    if ctx["n"] < 2:
        raise CheckFailed("ddp_parity needs more than one device")
    gb, steps = ctx["per_device_batch"], STEPS
    argv = resnet_argv(ctx["uri"], ctx["backend"], ctx["model_name"],
                       ctx["image_size"], gb, steps) + ["--no_eval_at_end"]
    res_all, trace_all, _, _ = run_train(workdir, "ddp_all", argv)
    every = check_losses(trace_all, res_all, steps)
    res_one, trace_one, _, _ = run_train(workdir, "ddp_one",
                                         argv + ["--no_ddp"])
    one = check_losses(trace_one, res_one, steps)
    check([r["batch_sha256"] for r in trace_all]
          == [r["batch_sha256"] for r in trace_one],
          "both arms consumed the same batches")
    rel = [abs(a - b) / abs(b) for a, b in zip(every, one)]
    check(rel[0] <= 1e-3 and max(rel) <= 1e-2,
          f"loss on {ctx['n']} devices vs 1: step-1 rel {rel[0]:.2e} "
          f"(<= 1e-3), worst of {steps} steps {max(rel):.2e} (<= 1e-2)")
    return (f"{ctx['n']} devices {[round(x, 4) for x in every]} vs one "
            f"{[round(x, 4) for x in one]}")


# -- entry -------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--also", default="",
                    help=f"comma-separated opt-in checks: {', '.join(ALSO)}")
    args = ap.parse_args(argv)
    also = [a for a in args.also.split(",") if a]
    unknown = sorted(set(also) - set(ALSO))
    if unknown:
        ap.error(f"unknown --also {unknown}; have {list(ALSO)}")

    t_start = time.perf_counter()
    import jax
    import jaxlib
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    devices = jax.devices()
    dev = devices[0]
    say(f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    if dev.platform != "tpu":
        say(f"chip_smoke: needs a TPU, JAX found platform={dev.platform!r}")
        return 1
    try:
        import lance_distributed_training_tpu  # noqa: F401
    except ImportError as e:
        say(f"chip_smoke: run me from the root of the checkout: {e}")
        return 1

    workdir = tempfile.mkdtemp(prefix="ldt-chip-smoke-")
    summaries, failed = {}, []
    try:
        report = main_path(workdir)
        report["u8_batch"] = u8_batch_hbm_bytes(PER_DEVICE_BATCH, 224)
        report["sync_probe"] = sync_probe()
        print_report(report)
        ctx = dict(uri=report["uri"], backend="tpu", n=len(devices),
                   model_name="resnet50", image_size=224,
                   per_device_batch=PER_DEVICE_BATCH, text_model="bert_base")
        for name in also:
            # Each opt-in check runs whatever the one before it did: a chip
            # call is dear, and every fault it can find is worth the visit.
            say(f"== also: {name} ==")
            try:
                summaries[name] = globals()[f"also_{name}"](workdir, ctx)
                say(f"  {name}: PASS — {summaries[name]}")
            except Exception as e:  # noqa: BLE001 — reported, then counted
                traceback.print_exc()
                summaries[name] = f"FAIL: {type(e).__name__}: {e}"
                failed.append(name)
                say(f"  {name}: {summaries[name]}")
    except CheckFailed as e:
        say(f"FAIL: {e}")
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    report.pop("uri")
    report["also"] = summaries
    report["wall_s"] = time.perf_counter() - t_start
    with open(os.path.join(out_dir, "chip_smoke_report.json"), "a") as f:
        f.write(json.dumps(report) + "\n")
    if failed:
        say(f"FAIL: {failed}")
        return 1
    say(f"chip_smoke: all checks passed in {report['wall_s']:.0f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
