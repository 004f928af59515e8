"""The one door to the host-to-device placement surface.

``device_put`` and the two array-assembly entry points are re-exported
here so the placement plane (``data/placement.py``) and ``parallel/mesh.py``
reach H2D through a single module: that is what lets the LDT801 lint
reject stray ``jax.device_put`` calls on hot paths (a synchronous
consumer-thread ``device_put`` is exactly the stall the placement plane
exists to remove), and what lets the compile/transfer witness count real
H2D traffic per caller site.
"""

from __future__ import annotations

import jax

from ..utils import compiletrack

__all__ = [
    "device_put",
    "make_array_from_single_device_arrays",
    "make_array_from_process_local_data",
]

make_array_from_single_device_arrays = jax.make_array_from_single_device_arrays
make_array_from_process_local_data = jax.make_array_from_process_local_data

_raw_device_put = jax.device_put


def device_put(x, *args, **kwargs):
    # With LDT_COMPILE_SANITIZER=1 every placement through this door is
    # counted per caller site (depth=3 — the user's ``device_put(`` line),
    # so ``ldt check --compile-witness`` reports real H2D traffic next to
    # the static LDT801 funnel discipline.
    if compiletrack.enabled():
        compiletrack.track_transfer(
            "h2d", getattr(x, "nbytes", 0) or 0, depth=3)
    return _raw_device_put(x, *args, **kwargs)
