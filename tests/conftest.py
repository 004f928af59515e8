"""Test env: simulate an 8-device TPU mesh on CPU (SURVEY.md §4).

Must run before the first ``import jax`` anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Whoever launched pytest may place a compile cache for its own runs; the
# tests must not write XLA:CPU executables into it (see the note below).
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# NO persistent compile cache. XLA:CPU's persistent cache stores AOT machine
# code whose round-trip is unsound for shard_map collective programs: loading
# a cached ppermute executable (even on the same machine that wrote it) makes
# one device thread die, the other participants wait at the collective-permute
# rendezvous, and the 40 s rendezvous watchdog aborts the whole interpreter
# ("Fatal Python error: Aborted"). Cross-machine it is worse — the cache key
# omits host CPU features, so a cache written elsewhere poisons every heavy
# test. Within one pytest process jit's in-memory cache already dedups
# compiles, so persistence bought little; correctness wins.

import io
import sys

import numpy as np
import pyarrow as pa
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy trainer-loop integration (jit compiles, minutes on a "
        "small host) — run per-round: pytest -m slow",
    )
    config.addinivalue_line(
        "markers",
        "fast: sampler/format/pipeline invariants quick enough to gate "
        "every commit: pytest -m fast",
    )
    # Runtime lock-order witness (LDT1001's evidence half): under
    # LDT_LOCK_SANITIZER=1 every threading.Lock/RLock the package creates
    # is wrapped to record actual acquisition orderings; unconfigure dumps
    # the witness JSON for `ldt check --lock-witness`. Installed HERE —
    # before collection imports any package module — so module-level locks
    # (native/jpeg.py, data/buffers.py, obs/spans.py) are instrumented too.
    if os.environ.get("LDT_LOCK_SANITIZER") == "1":
        _load_util("lockorder").install()


def pytest_unconfigure(config):
    if os.environ.get("LDT_LOCK_SANITIZER") == "1":
        # Dump unconditionally (not gated on installed()): whatever the
        # suite recorded is the witness, even if a unit test toggled the
        # shim along the way (they snapshot/restore, belt and braces).
        lockorder = _load_util("lockorder")
        path = lockorder.dump()
        lockorder.uninstall()
        sys.stderr.write(f"\n[lockorder] witness written to {path}\n")
    if os.environ.get("LDT_LEAK_SANITIZER") == "1":
        # Resource-lease witness (LDT1201's evidence half): the buffer
        # plane's leaktrack hooks recorded every pool-page lease/release
        # and shm-token handoff across the suite; whatever is still
        # outstanding NOW is a leak by definition — dump for
        # `ldt check --leak-witness`.
        leaktrack = _load_util("leaktrack")
        path = leaktrack.dump()
        sys.stderr.write(f"\n[leaktrack] witness written to {path}\n")
    if os.environ.get("LDT_COMPILE_SANITIZER") == "1":
        # Compile/transfer witness (LDT1703's evidence half): the package's
        # jit funnels counted per-def-site trace signatures and the
        # placement door counted H2D/D2H events across the suite — dump for
        # `ldt check --compile-witness`.
        compiletrack = _load_util("compiletrack")
        path = compiletrack.dump()
        sys.stderr.write(f"\n[compiletrack] witness written to {path}\n")
    if os.environ.get("LDT_WIRE_SANITIZER") == "1":
        # Wire-traffic witness (LDT1403's evidence half): the protocol
        # hooks counted every (msg, field) tuple that crossed the
        # loopback wire across the suite — dump for
        # `ldt check --wire-witness`.
        wiretrack = _load_util("wiretrack")
        path = wiretrack.dump()
        sys.stderr.write(f"\n[wiretrack] witness written to {path}\n")


def _load_util(stem):
    """Load a ``utils/<stem>.py`` sanitizer WITHOUT importing the package
    __init__ (which would create module-level locks before the lockorder
    shim exists, leaving them uninstrumented — and eagerly import jax).
    Registered under the canonical dotted name so a later in-test import
    shares the same recorder state."""
    import importlib.util

    name = f"lance_distributed_training_tpu.utils.{stem}"
    if name in sys.modules:
        return sys.modules[name]
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "lance_distributed_training_tpu", "utils", f"{stem}.py",
    )
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def pytest_collection_modifyitems(items):
    """Everything not explicitly marked slow is fast — the deadlock/sampler/
    format/decode invariants that should gate every commit."""
    for item in items:
        if item.get_closest_marker("slow") is None:
            item.add_marker(pytest.mark.fast)


def register_preset(name: str, base: str, **changes) -> dict:
    """The ``causal_lm`` preset ``base`` under ``name``, with fields of its
    constructor changed (``moe``: a dict laid over the base's name-value
    pairs; ``parts``: a dict, class to the sizes of it that change, or, for a
    preset whose parts are ``(kind, partial)`` pairs, layer kind to them).
    Returns the table: whoever registers deletes, ``del table[name]``."""
    import functools

    from lance_distributed_training_tpu.models.transformer import CAUSAL_LMS

    base = CAUSAL_LMS[base]
    if "moe" in changes:
        changes["moe"] = tuple({**dict(base.ctor.keywords["moe"]),
                                **changes["moe"]}.items())
    if "parts" in changes:
        sizes = changes["parts"]
        changes["parts"] = tuple(
            (part[0], functools.partial(part[1], **sizes.get(part[0], {})))
            if isinstance(part, tuple)
            else functools.partial(part, **sizes.get(part.func, {}))
            for part in base.ctor.keywords["parts"])
    CAUSAL_LMS[name] = base._replace(
        ctor=functools.partial(base.ctor, **changes))
    return CAUSAL_LMS


def grouped_kernels_are_the_plain_form(task, variables, batch, groups,
                                       tol, monkeypatch) -> None:
    """A causal stack with ``ops/grouped.py`` choosing the library's kernels
    (interpret mode; one device; tiles of a whole width at test size), as a
    cell's shape with an entry does on the chip, against itself on
    ``jax.lax.ragged_dot``: logits, loss and every gradient group that
    ``groups`` makes of a tree of variables, each one jitted program waited
    for; ``grouped_products_fused`` reads 0 on the CPU path, 1 then."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from lance_distributed_training_tpu.obs.registry import default_registry
    from lance_distributed_training_tpu.ops import grouped

    def loss(v):
        outputs, _ = task.forward(v, batch, True, None)
        return task.loss(outputs, batch)

    def run(v):
        logits = task.forward(v, batch, False, None)[0][0]
        return logits, *jax.value_and_grad(loss)(v)

    gauge = default_registry().gauge("grouped_products_fused")
    gauge.set(0.0)
    want = jax.block_until_ready(jax.jit(run)(variables))
    assert gauge.value == 0.0
    monkeypatch.setattr(
        grouped, "grouped_tiling", lambda rows, groups, k, n, **_:
        grouped.Tiling((128, k, n), (128, n, k), (128, k, n)))
    with pltpu.force_tpu_interpret_mode():  # a new jit: traced anew
        got = jax.block_until_ready(jax.jit(lambda v: run(v))(variables))
    assert gauge.value == 1.0
    spread = float(jnp.std(want[0]))
    assert float(jnp.abs(got[0] - want[0]).max()) < tol * spread
    assert abs(float(got[1]) - float(want[1])) < tol * float(want[1])
    got, want = groups(got[2]), groups(want[2])
    assert got.keys() == want.keys()
    for group in want:
        error = float(jnp.linalg.norm(got[group] - want[group])
                      / jnp.linalg.norm(want[group]))
        assert error < tol, (group, error)


def make_jpeg(rng: np.ndarray, size: int = 32) -> bytes:
    """A small random JPEG payload (stands in for FOOD101 images)."""
    from PIL import Image

    arr = (rng.random((size, size, 3)) * 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG")
    return buf.getvalue()


@pytest.fixture(scope="session")
def image_table() -> pa.Table:
    """240-row {image: binary, label: int64} table — the schema written by the
    reference's dataset builder (create_datasets/classification.py:50-53)."""
    rng = np.random.default_rng(0)
    images = [make_jpeg(rng) for _ in range(240)]
    labels = rng.integers(0, 10, 240)
    return pa.table(
        {"image": pa.array(images, pa.binary()), "label": pa.array(labels, pa.int64())}
    )


@pytest.fixture()
def image_dataset(tmp_path, image_table):
    from lance_distributed_training_tpu.data import write_dataset

    return write_dataset(
        image_table, tmp_path / "ds", mode="create", max_rows_per_file=100
    )
