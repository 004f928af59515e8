"""Device-only MFU sweep: batch × param-dtype grid + step breakdown.

Answers the r3 verdict's perf question — is ~30% MFU the chip's ceiling or
the framework's? — in ONE chip claim:

* per-chip batch sweep (128/256/512 by default) of the jitted DP train step
  on a RESIDENT synthetic batch (no loader, no H2D: the pure compute
  ceiling bench.py reports as ``device_only``),
* a bfloat16-params variant at each batch (halves weight/optimizer HBM
  traffic; ``ResNet.param_dtype``),
* a piecewise breakdown at the headline config — forward-only,
  forward+backward, full step — naming where the milliseconds go without
  needing trace-viewer tooling on this box,
* the A100-equivalence arithmetic from BASELINE.md's north star written
  into the artifact: ≥90% of an MLPerf-class A100's ~2700 img/s ResNet-50
  training rate ⇒ ≥2430 img/s/chip target.

Timing closes with a scalar value fetch.

Env knobs: BENCH_SWEEP_BATCHES="128,256,512", BENCH_SWEEP_STEPS (default
20), BENCH_SWEEP_TRACE=1 (profiler trace of the best config).

Prints ONE JSON line with the full grid.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

from _bench_init import emit_error, env_int, init_devices, log
from bench import peak_tflops_for

METRIC = "resnet50_device_only_mfu_sweep"

TRAIN_FLOPS_PER_IMAGE = 24.5e9  # fwd ≈ 8.2e9 (4.1e9 MACs × 2) × 3 for training
A100_IMAGES_PER_SEC = 2700.0  # MLPerf-class A100 ResNet-50 training throughput
NORTH_STAR_FRACTION = 0.90  # BASELINE.md: ≥90% of the A100 rate


def _time_steps(fn, fetch, n):
    """Run fn() n times; close the window with a value fetch of fetch()."""
    fetch()  # sync entry
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    fetch()
    return time.perf_counter() - t0


def _time_train_steps(step, state, batch, rng, n):
    """Time n donated train steps, rebinding state each iteration (the
    bench.py device-only pattern): donation invalidates the argument
    buffers, so the loop must thread the returned state through — and in
    exchange XLA updates params/optimizer state in place instead of
    copying ~300 MB of Adam state every step. Closes with a loss value
    fetch. Returns (wall_seconds, final_state)."""
    state, loss = step(state, batch, rng)
    float(loss)  # sync entry (and absorb any remaining compile)
    t0 = time.perf_counter()
    for _ in range(n):
        state, loss = step(state, batch, rng)
    float(loss)
    return time.perf_counter() - t0, state


def _run(jax, devices) -> dict:
    import jax.numpy as jnp

    # Same warm cache as bench.py; the one rule lives in the trainer.
    from lance_distributed_training_tpu.trainer import maybe_enable_compile_cache

    maybe_enable_compile_cache(devices[0].platform)

    from lance_distributed_training_tpu.models import get_task
    from lance_distributed_training_tpu.parallel import (
        get_mesh,
        make_global_batch,
        replicated_sharding,
    )
    from lance_distributed_training_tpu.trainer import (
        TrainConfig,
        create_train_state,
        make_train_step,
    )

    n_chips = len(devices)
    image_size = env_int("BENCH_SWEEP_IMAGE", 224)
    steps = env_int("BENCH_SWEEP_STEPS", 20)
    batches = [
        int(b) for b in
        os.environ.get("BENCH_SWEEP_BATCHES", "128,256,512").split(",")
    ]
    peak_tflops = peak_tflops_for(devices[0].device_kind)
    mesh = get_mesh()
    repl = replicated_sharding(mesh)
    rng = jax.random.key(1)
    gen = np.random.default_rng(0)

    grid = []
    best = None
    for param_dtype_name in ("float32", "bfloat16"):
        param_dtype = getattr(jnp, param_dtype_name)
        task = get_task(
            "classification", num_classes=101, model_name="resnet50",
            image_size=image_size, augment=False, param_dtype=param_dtype,
        )
        cfg = TrainConfig(dataset_path="", num_classes=101)
        # Donated step, same as training and bench.py's device-only pass:
        # without donation the optimizer update round-trips ~300 MB of
        # params + Adam moments through fresh HBM allocations every step,
        # and the sweep would understate the rate it exists to measure.
        step = make_train_step(task, mesh)
        for per_chip_batch in batches:
            global_batch = per_chip_batch * n_chips
            batch = make_global_batch(
                {
                    "image": gen.integers(
                        0, 255, (global_batch, image_size, image_size, 3)
                    ).astype(np.uint8),
                    "label": gen.integers(0, 101, global_batch),
                },
                mesh,
            )
            # Fresh state per point: donation consumes the previous one.
            state = jax.device_put(
                create_train_state(jax.random.key(0), task, cfg), repl
            )
            try:
                wall, state = _time_train_steps(step, state, batch, rng, steps)
            except Exception as e:  # noqa: BLE001 — OOM at big batches is data
                log(f"{param_dtype_name} b{per_chip_batch}: FAILED {e}")
                grid.append({
                    "param_dtype": param_dtype_name,
                    "per_chip_batch": per_chip_batch,
                    "error": str(e)[:300],
                })
                continue
            ran = steps
            step_ms = wall / ran * 1e3
            img_s_chip = ran * global_batch / wall / n_chips
            mfu = img_s_chip * TRAIN_FLOPS_PER_IMAGE / (peak_tflops * 1e12) * 100
            point = {
                "param_dtype": param_dtype_name,
                "per_chip_batch": per_chip_batch,
                "step_ms": round(step_ms, 2),
                "images_per_sec_per_chip": round(img_s_chip, 1),
                "mfu_pct": round(mfu, 2),
            }
            log(f"{param_dtype_name} b{per_chip_batch}: "
                f"{img_s_chip:.0f} img/s/chip, {step_ms:.1f} ms, {mfu:.1f}% MFU")
            grid.append(point)
            if best is None or img_s_chip > best[0]:
                best = (img_s_chip, task, state, step, batch, point)
            del batch

    if best is None:
        raise RuntimeError("every sweep point failed")
    _, task, state, step, best_batch, best_point = best

    # ---- piecewise breakdown at the best config: where does the step go?
    from lance_distributed_training_tpu.trainer import _variables

    def fwd_only(state, batch, rng):
        outputs, _ = task.forward(_variables(state), batch, True, rng)
        return task.loss(outputs, batch)

    def fwd_bwd(state, batch, rng):
        def loss_of(params):
            variables = dict(_variables(state), params=params)
            outputs, _ = task.forward(variables, batch, True, rng)
            return task.loss(outputs, batch)

        _, grads = jax.value_and_grad(loss_of)(state.params)
        # Reduce grads to a scalar the fetch depends on — XLA cannot
        # dead-code-eliminate the backward pass.
        return sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(grads)
        )

    jf = jax.jit(fwd_only)
    jfb = jax.jit(fwd_bwd)
    float(jf(state, best_batch, rng))
    float(jfb(state, best_batch, rng))
    n = max(steps // 2, 5)
    fwd_wall = _time_steps(
        lambda: jf(state, best_batch, rng),
        lambda: float(jf(state, best_batch, rng)), n,
    ) / (n + 1)
    fwd_bwd_wall = _time_steps(
        lambda: jfb(state, best_batch, rng),
        lambda: float(jfb(state, best_batch, rng)), n,
    ) / (n + 1)
    full_wall = best_point["step_ms"] / 1e3
    breakdown = {
        "basis": "piecewise jit timings at the best config; optimizer+BN = "
                 "full step minus fwd+bwd (can go negative within noise when "
                 "XLA fuses better in the full graph)",
        "forward_ms": round(fwd_wall * 1e3, 2),
        "backward_ms": round((fwd_bwd_wall - fwd_wall) * 1e3, 2),
        "optimizer_and_rest_ms": round((full_wall - fwd_bwd_wall) * 1e3, 2),
        "full_step_ms": round(full_wall * 1e3, 2),
    }
    log(f"breakdown: {breakdown}")

    trace_dir = None
    if os.environ.get("BENCH_SWEEP_TRACE", "") == "1":
        trace_dir = tempfile.mkdtemp(prefix="ldt-sweep-trace-")
        jax.profiler.start_trace(trace_dir)
        for _ in range(3):
            state, loss = step(state, best_batch, rng)
        float(loss)
        jax.profiler.stop_trace()
        log(f"trace written to {trace_dir}")

    target = A100_IMAGES_PER_SEC * NORTH_STAR_FRACTION
    mem = {}
    try:
        stats = devices[0].memory_stats() or {}
        for k_src, k_out in (("bytes_in_use", "hbm_bytes_in_use"),
                             ("peak_bytes_in_use", "hbm_peak_bytes_in_use"),
                             ("bytes_limit", "hbm_bytes_limit")):
            if k_src in stats:
                mem[k_out] = int(stats[k_src])
    except Exception:
        pass
    result = {
        "metric": METRIC,
        "value": best_point["mfu_pct"],
        "unit": "percent_mfu_device_only",
        "vs_baseline": round(
            best_point["images_per_sec_per_chip"] / target, 3
        ),
        "timing_basis": "wall_clock_value_fetch",
        "grid": grid,
        "best": best_point,
        "step_breakdown": breakdown,
        "north_star": {
            "a100_resnet50_images_per_sec": A100_IMAGES_PER_SEC,
            "fraction_required": NORTH_STAR_FRACTION,
            "target_images_per_sec_per_chip": target,
            "note": "BASELINE.md north star: >=90% of torch/A100 img/s; "
                    "vs_baseline above is best-config img/s over that target",
        },
        "peak_tflops_assumed": peak_tflops,
        "train_flops_per_image": TRAIN_FLOPS_PER_IMAGE,
        "chips": n_chips,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "measured_steps_per_point": steps,
        **mem,
    }
    if trace_dir:
        result["trace_dir"] = trace_dir
    return result


def main() -> None:
    try:
        jax, devices = init_devices()
        result = _run(jax, devices)
    except Exception as e:  # noqa: BLE001 — always leave a parseable line
        emit_error(METRIC, f"{type(e).__name__}: {e}")
        return
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
