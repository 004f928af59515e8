"""Shared process set-up for the bench scripts: logging, env knobs, and a
device claim that never hides which platform it got.

``init_devices`` raises unless JAX reports a TPU or the script pinned the
CPU itself with ``force_cpu`` (the CPU-by-definition A/B benches). stdout
carries one JSON line per run: a result, or the error record of
``emit_error``; progress goes to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

_T0 = time.perf_counter()
_forced_cpu = False


def log(msg: str) -> None:
    """Phase progress to stderr; stdout carries only the final JSON line."""
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except (TypeError, ValueError):
        log(f"ignoring unparseable env {name}={os.environ.get(name)!r}")
        return default


def force_cpu(n_devices: int = 1) -> None:
    """Pin this process to the host CPU backend with ``n_devices`` virtual
    devices. Shared by every CPU-by-definition bench so the pinning
    sequence can never diverge between them. Must run BEFORE any backend
    query."""
    global _forced_cpu
    import jax

    jax.config.update("jax_num_cpu_devices", n_devices)
    jax.config.update("jax_platforms", "cpu")
    _forced_cpu = True


def init_devices():
    """Claim the devices; returns ``(jax_module, devices)``. A bench that
    did not ask for the CPU and did not get a TPU fails here, so no CPU
    number is ever written under a device metric's name."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not _forced_cpu:
        raise RuntimeError(
            f"bench needs a TPU and JAX found platform={platform!r} "
            "(CPU-by-definition benches call force_cpu first)"
        )
    log(f"devices: {len(devices)} x {devices[0].device_kind} ({platform})")
    return jax, devices


def emit_error(metric: str, error: str) -> None:
    """Failure path: one structured JSON line on stdout, then rc=1."""
    print(json.dumps({
        "metric": metric,
        "value": None,
        "unit": None,
        "vs_baseline": None,
        "error": {"last_error": error[:2000]},
    }), flush=True)
    sys.exit(1)
