"""Placement plane: host batches → global device arrays, H2D off the step.

The one policy: *an engine yields host batches and never touches a device;
this plane places them and returns their leases.* A step must not sit
behind the H2D transfer of the batch it is about to consume, so the
transfer is dispatched from a thread of its own, ahead of the consumer (on
the chip the ring is full 81–98% of the window: PERF.md section 5,
``placement.wait_ring``). This module is the one shared exit from host
memory (the alpa ``DataLoader`` pattern in SNIPPETS.md: per-device shards +
``prefetch_size`` device buffers):

* :class:`PlacementPlane` — slices each host batch per **local device**
  along the mesh's data axis, dispatches one async ``device_put`` per
  device, and assembles the logical *global* array with
  ``make_array_from_single_device_arrays`` (both primitives imported from
  ``parallel/_compat.py``; LDT801 rejects direct ``jax.device_put`` on hot
  paths so this funnel stays the only one).
* the **ring** (:func:`_read_chain`) — a dedicated **placement thread**
  pulls decoded host batches from the upstream pipeline, places them, and
  keeps a depth-configurable (default 2) ring of device-resident batches
  ahead of the consumer, so ``next(loader)`` returns an already-transferred
  array and step N's compute overlaps batch N+1's DMA.
* the **epoch handover** — a loader that was given a successor
  (:meth:`PlacedLoader.set_successor`) does not end its ring with its
  epoch: once the thread has pulled the last host batch of epoch e it puts
  a boundary marker into the ring, builds epoch e+1's loader and goes on
  filling the *same* ring from it, so the next epoch's producer threads
  start while the trainer still consumes this one's tail and its first
  ``next`` finds a batch already placed. One ring, one bound: the batches
  on the device never exceed the ring's depth across the boundary, and
  epoch e+1's producers start only after epoch e's are done.
* :class:`PlacedLoader` — the thin wrapper ``trainer._build_loader`` puts
  around all five pipelines (``DataPipeline``, ``MapStylePipeline``,
  ``FolderDataPipeline``, ``RemoteLoader``, ``FleetLoader``): they
  yield HOST batches and this plane alone owns placement.

Buffer-plane contract: the placement thread releases each host batch's
:class:`~.buffers.BufferPool` leases immediately after the per-device
transfers are dispatched — *transfer-dispatch time, not consumer pickup*.
That is safe (and is effectively release-on-transfer-complete) because the
pool's refcount sweep only recycles a page once jax has dropped its own
reference to the host buffer, which happens when the async copy finishes;
until then the page parks on the pending list. Net effect: pages recycle
one-or-more batches earlier than the old after-yield release, and an
abandoned iterator can strand at most the ring's contents, which the
teardown drain releases.

Telemetry: ``trainer_h2d_ms`` histogram (per-batch dispatch+assembly time —
the H2D share the old accounting folded into ``trainer_loader_ms``),
``placement_buffer_depth`` gauge (device-resident batches ready in the
ring), and a ``placement_*`` :class:`~..utils.metrics.ServiceCounters`
window (``placement_h2d_s``) that ``StepTimer.attach_counters`` merges into
per-step progress lines as ``h2d_pct``.

Thread & queue policy (LDT201/LDT202): the placement thread is daemon, the
ring queue is bounded at ``depth``, and teardown is drain-then-join — the
same discipline as ``data/pipeline.py``. The ring is read through a
generator whose ``finally`` is that teardown, so closing it, or dropping
the last loader that holds it, stops the thread whichever epoch it is in.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, NamedTuple, Optional

import numpy as np

import jax

from ..obs.registry import MetricsRegistry, default_registry
from ..obs.spans import end_phase, phase
from ..parallel._compat import (
    device_put,
    make_array_from_process_local_data,
    make_array_from_single_device_arrays,
)
from ..tune.tunable import AdjustableQueue, Tunable, _LiveQueues
from ..utils.metrics import ServiceCounters

__all__ = ["PlacementPlane", "PlacedLoader"]


class _EpochEnd:
    """Boundary marker in the ring: every batch of the epoch before it has
    gone by. ``successor`` is the loader whose batches follow it in the same
    ring (``None``: the chain, and the thread, end here); ``error`` is what
    building that loader raised, kept for whoever asks for the successor."""

    __slots__ = ("successor", "error")

    def __init__(self, successor, error):
        self.successor = successor
        self.error = error


class _Ring(NamedTuple):
    """A running ring as one loader hands it to the next: the bounded queue
    (so the successor can see whether a batch is ready) and the generator
    that reads it and owns the thread's teardown."""

    q: AdjustableQueue
    batches: Iterator


def _read_chain(first: list, q: AdjustableQueue) -> Iterator:
    """Iterate a chain of loaders' host batches as already-placed global
    arrays, an :class:`_EpochEnd` after each loader's last. ``first`` is
    ``[loader]``: the thread takes the loader out, so that this generator
    (which a loader may come to hold, see ``PlacedLoader.take_successor``)
    keeps no loader alive.

    A dedicated placement thread pulls from ``loader.inner``, places each
    batch (async H2D dispatch), releases the host pages' pool leases, and
    fills the bounded ring ``q``; the consumer pops ready arrays. When the
    inner loader is exhausted (its producer threads have ended by then) the
    thread builds the loader's successor, puts the marker and reads on from
    the successor; without one it puts the marker and ends. Each loader's
    plane places its own batches, owns the ring's bound while the thread
    reads from it (the ring starts every epoch at that plane's ``depth``)
    and counts them. Teardown is drain-then-join, and the inner iterator is
    closed from the placement thread so upstream producer threads observe
    their stop flags.
    """
    stop = threading.Event()

    def produce() -> None:
        # This thread is always in one of three phases that tile its
        # time (obs/spans.py): wait_input (pulling the next host batch:
        # starved by read and decode; building a successor is in here),
        # h2d (dispatch, not transfer), wait_ring (put on a full ring:
        # ahead of the trainer). A train.loader gap on the loop thread
        # resolves to whichever this thread was inside meanwhile.
        try:
            phase("placement.wait_input")
            loader = first.pop()
            while loader is not None:
                plane = loader.plane
                q.set_maxsize(plane.depth)
                plane._live.install([q])
                it = iter(loader.inner)
                try:
                    for seq, host in enumerate(it):
                        if stop.is_set():
                            return
                        t0 = time.monotonic_ns()
                        phase("placement.h2d", batch_seq=seq)
                        dev = plane.place_batch(host)
                        phase("placement.wait_ring", batch_seq=seq)
                        dt_ms = (time.monotonic_ns() - t0) / 1e6
                        plane._h2d_hist.observe(dt_ms)
                        plane.counters.add("h2d_s", dt_ms / 1e3)
                        plane.counters.add("batches_placed")
                        # Transfers dispatched: leases go back NOW (the
                        # pool's refcount sweep defers actual recycling to
                        # transfer-complete), not at consumer pickup.
                        plane._release(host)
                        q.put(dev)
                        phase("placement.wait_input")
                        plane._set_depth(q.qsize())
                finally:
                    plane._live.clear()
                    close = getattr(it, "close", None)
                    if close is not None:
                        close()
                successor = error = None
                try:
                    successor = loader.build_successor()
                except Exception as exc:  # belongs to the next epoch
                    error = exc
                phase("placement.wait_ring")
                q.put(_EpochEnd(successor, error))
                loader = successor
                if loader is not None:
                    phase("placement.wait_input")
        except BaseException as exc:  # surface to the consumer
            q.put(exc)
        finally:
            end_phase()

    plane = first[0].plane
    thread = threading.Thread(
        target=produce, daemon=True, name="ldt-placement"
    )
    thread.start()
    try:
        while True:
            item = q.get()
            plane._set_depth(q.qsize())
            if isinstance(item, BaseException):
                raise item
            yield item
            if isinstance(item, _EpochEnd):
                if item.successor is None:
                    return
                plane = item.successor.plane
    finally:
        stop.set()
        # Drain so a blocked put() can observe the stop flag. Drained
        # items are device batches (host leases already released at
        # dispatch) — dropping them frees HBM via ordinary GC.
        while thread.is_alive():
            try:
                q.get_nowait()
            except queue.Empty:
                thread.join(timeout=0.1)
        plane._set_depth(0)


class PlacementPlane:
    """Mesh-native batch placement with double-buffered async H2D.

    Parameters
    ----------
    mesh: the device mesh (``parallel.mesh.get_mesh``).
    data_axis / seq_axis: batch layout axes, as ``make_global_batch`` takes
        them (rank-2 token arrays additionally shard over ``seq_axis``).
    depth: ring size — device-resident batches kept ahead of the consumer.
        2 double-buffers (one being consumed, one transferred); more only
        pins extra HBM without more overlap unless step times are bimodal.
    buffer_pool: the :class:`~.buffers.BufferPool` the decode plane leased
        its output pages from; leases release at transfer dispatch.
    """

    def __init__(
        self,
        mesh,
        *,
        data_axis: str = "data",
        seq_axis: Optional[str] = None,
        depth: int = 2,
        buffer_pool=None,
        registry: Optional[MetricsRegistry] = None,
    ):
        self.mesh = mesh
        self.data_axis = data_axis
        self.seq_axis = seq_axis
        self.depth = max(1, depth)
        self.buffer_pool = buffer_pool
        self.registry = registry if registry is not None else default_registry()
        self.counters = ServiceCounters(
            prefix="placement", registry=self.registry
        )
        self._h2d_hist = self.registry.histogram("trainer_h2d_ms")
        # (global_shape, local_shape, sharding) → per-device local slice
        # plan, or None when the local window is not expressible as slices
        # of the local array (fall back to the process-local assembly).
        self._plans: dict = {}
        # ndim → (NamedSharding, process_count): built once per rank, not
        # per leaf per batch — this runs on the hot placement thread.
        self._shardings: dict = {}
        # Autotune surface: the live ring queue of the current iteration.
        self._live = _LiveQueues()

    def set_ring_depth(self, depth: int) -> int:
        """Autotune actuator: move the device-resident ring bound, live.
        Each extra slot pins one more global batch in HBM, so the tunable's
        ``hi`` stays small; shrinking drains through the consumer (device
        batches are never dropped — they were already transferred)."""
        depth = max(1, int(depth))
        self.depth = depth  # ldt: ignore[LDT1002] -- atomic int swap; readers take any recent value
        self._live.resize_total(depth)
        return depth

    def tunables(self):
        """Autotune registration surface: the H2D ring depth."""
        return [Tunable(
            "ring_depth", lambda: self.depth, self.set_ring_depth,
            lo=1, hi=8,
            doc="device-resident global batches kept ahead of the step",
        )]

    # -- single-batch placement --------------------------------------------

    def _sharding_for(self, ndim: int):
        cached = self._shardings.get(ndim)
        if cached is not None:
            return cached
        from jax.sharding import NamedSharding

        from ..parallel.sharding import batch_partition_spec

        spec = batch_partition_spec(
            ndim, data_axis=self.data_axis, seq_axis=self.seq_axis
        )
        cached = NamedSharding(self.mesh, spec), jax.process_count()
        self._shardings[ndim] = cached
        return cached

    def _slice_plan(self, gshape, lshape, sharding):
        """``[(device, local_index_tuple), …]`` mapping each addressable
        device to the slice of THIS process's host array it receives;
        ``None`` when the global indices don't line up with a contiguous
        local window (exotic process→mesh layouts) — callers then fall back
        to ``jax.make_array_from_process_local_data``."""
        key = (tuple(gshape), tuple(lshape), sharding)
        if key in self._plans:
            return self._plans[key]
        plan = []
        try:
            imap = sharding.addressable_devices_indices_map(tuple(gshape))
            if not gshape:  # rank-0 leaf: replicated everywhere
                plan = [(d, ()) for d in imap]
            else:
                starts = [
                    (idx[0].start or 0) if idx else 0
                    for idx in imap.values()
                ]
                offset = min(starts) if starts else 0
                for d, idx in imap.items():
                    idx = tuple(idx)
                    local = []
                    for dim, (sl, gdim, ldim) in enumerate(
                        zip(idx, gshape, lshape)
                    ):
                        start = sl.start or 0
                        stop = sl.stop if sl.stop is not None else gdim
                        if dim == 0:
                            # The data axis spans processes: rebase the
                            # global row window onto this process's block.
                            start -= offset
                            stop -= offset
                        if start < 0 or stop > ldim or stop <= start:
                            raise ValueError("non-local window")
                        local.append(slice(start, stop))
                    plan.append((d, tuple(local)))
        except (ValueError, TypeError, AttributeError):
            plan = None
        self._plans[key] = plan
        return plan

    def _place_leaf(self, x):
        x = np.asarray(x)
        sharding, nproc = self._sharding_for(x.ndim)
        gshape = (
            (x.shape[0] * nproc,) + x.shape[1:]
            if nproc > 1 and x.ndim >= 1
            else x.shape
        )
        plan = self._slice_plan(gshape, x.shape, sharding)
        if plan is None:
            # Non-contiguous local window: the generic (slower) assembly
            # still yields the identical global array.
            if nproc == 1:
                return device_put(x, sharding)
            return make_array_from_process_local_data(sharding, x)
        # ONE device_put over the shard/device lists (jax fans it out):
        # eight separate calls cost ~8x the python dispatch on this thread.
        shards = device_put(
            [x[idx] for _, idx in plan], [d for d, _ in plan]
        )
        return make_array_from_single_device_arrays(
            tuple(gshape), sharding, shards
        )

    def _place_replicated(self, x):
        """Ragged token leaves (flat values pages, offsets, pack plans —
        no per-row leading dim to split over the data axis): replicate.
        The pack kernel consumes them whole; its packed output re-enters
        the data layout inside the jitted transform."""
        x = np.asarray(x)
        cached = self._shardings.get("repl")
        if cached is None:
            from jax.sharding import NamedSharding, PartitionSpec

            cached = (
                NamedSharding(self.mesh, PartitionSpec()),
                jax.process_count(),
            )
            self._shardings["repl"] = cached
        sharding, nproc = cached
        if nproc == 1:
            return device_put(x, sharding)
        return make_array_from_process_local_data(sharding, x)

    def place_batch(self, host_batch):
        """One host batch (pytree of numpy arrays) → global ``jax.Array``
        pytree, per-device transfers dispatched asynchronously. Bit-identical
        to ``make_global_batch(host_batch, mesh)`` — pinned by
        ``tests/test_placement.py``. Dict batches are key-aware for the
        ragged token convention: ``_host_*`` metadata passes through as
        numpy (read host-side by the pack transform), ragged leaves
        replicate, everything else shards over the data axis as always."""
        if isinstance(host_batch, dict):
            from .token_pack import is_host_meta_key, is_ragged_key

            return {
                k: (
                    np.asarray(v) if is_host_meta_key(k)
                    else self._place_replicated(v) if is_ragged_key(k)
                    else self._place_leaf(v)
                )
                for k, v in host_batch.items()
            }
        return jax.tree_util.tree_map(self._place_leaf, host_batch)

    def _release(self, host_batch) -> None:
        if self.buffer_pool is not None:
            self.buffer_pool.release_batch(host_batch)

    def _set_depth(self, n: int) -> None:
        # One write: the ServiceCounters gauge lands in the registry under
        # placement_buffer_depth (the /metrics series) AND in the
        # per-window merge StepTimer reads — no second direct-gauge copy.
        self.counters.gauge("buffer_depth", n)

    def wrap(self, inner) -> "PlacedLoader":
        return PlacedLoader(self, inner)


class PlacedLoader:
    """A pipeline that yields host batches, placed through a
    :class:`PlacementPlane`. Delegates ``len``/``set_epoch``; exposes the
    inner loader's ``counters`` (svc_*/fleet_* windows) unchanged plus the
    plane's ``placement_counters`` for ``StepTimer.attach_counters``.

    One iteration is one epoch. A loader that was given a successor
    (:meth:`set_successor`) leaves its ring running at its epoch's end:
    :meth:`take_successor` then returns the next epoch's loader, which the
    ring is already reading, and iterating that loader goes on popping the
    same ring. ``handover`` says what an iteration found at its first
    ``next``: ``"warm"`` (a batch of this epoch was already in the ring) or
    ``"cold"`` (the ring was empty: the thread had only just started, or
    the pipeline was behind)."""

    def __init__(self, plane: PlacementPlane, inner):
        self.plane = plane
        self.inner = inner
        self._start = 0
        self._yielded = 0
        self.handover: Optional[str] = None
        self._build_successor: Optional[Callable[[], "PlacedLoader"]] = None
        self._ring: Optional[_Ring] = None  # a predecessor's, reading us
        # (marker, ring) at the end of an epoch whose ring went on
        self._handed: Optional[tuple] = None

    def __len__(self) -> int:
        return len(self.inner)

    def set_epoch(self, epoch: int) -> None:
        set_epoch = getattr(self.inner, "set_epoch", None)
        if set_epoch is not None:
            set_epoch(epoch)
        self._start = 0
        self._yielded = 0

    # -- resume cursor (contract: data/pipeline.py) -------------------------
    #
    # The count must live HERE, not on the inner loader: the placement
    # thread runs the inner iterator up to `depth` batches AHEAD of the
    # trainer, so the inner cursor counts decoded-and-placed batches while
    # the checkpoint needs batches the trainer actually CONSUMED. On
    # restore the ring's in-flight batches are simply re-decoded — device-
    # resident state is never part of the cursor.

    def state_dict(self) -> dict:
        sd = {}
        inner_sd = getattr(self.inner, "state_dict", None)
        if inner_sd is not None:
            sd.update(inner_sd())
        sd["step"] = int(self._yielded)
        return sd

    def load_state_dict(self, state: dict) -> None:
        inner_load = getattr(self.inner, "load_state_dict", None)
        if inner_load is not None:
            inner_load(state)
        self._start = int(state.get("step", 0))
        self._yielded = self._start

    def tunables(self):
        """Autotune registration surface: the plane's ring depth plus
        whatever knobs the wrapped loader exposes (prefetch, stripe
        width) — the trainer collects from the outermost loader only."""
        out = list(self.plane.tunables())
        inner = getattr(self.inner, "tunables", None)
        if inner is not None:
            out.extend(inner())
        return out

    @property
    def counters(self):
        return getattr(self.inner, "counters", None)

    @property
    def placement_counters(self) -> ServiceCounters:
        return self.plane.counters

    # -- epoch handover ------------------------------------------------------

    def set_successor(
        self, build: Optional[Callable[[], "PlacedLoader"]]
    ) -> None:
        """Name what the ring reads once this loader's inner loader is
        exhausted: ``build()`` returns the next epoch's loader (anything
        that delegates to a :class:`PlacedLoader`, as a ``LoaderGraph``
        with a ``Place`` node does). It runs on the placement thread, after
        this epoch's producer threads have ended. Set it before the epoch
        is nearly read: a ring that finds none at that moment ends."""
        self._build_successor = build

    def build_successor(self) -> Optional["PlacedLoader"]:
        build = self._build_successor
        return build() if build is not None else None

    def take_successor(self) -> Optional["PlacedLoader"]:
        """After an iteration that ran to its end: the successor the ring
        went on to, ready to be iterated (once), or ``None`` when the ring
        ended with this epoch. Raises what building the successor raised.
        Until it is taken the running ring belongs to THIS loader, so
        dropping the loader stops the thread."""
        handed, self._handed = self._handed, None
        if handed is None:
            return None
        end, ring = handed
        if end.error is not None:
            raise end.error
        end.successor._adopt(ring)
        return end.successor

    def _adopt(self, ring: _Ring) -> None:
        # A method, not an attribute set by the predecessor: a successor
        # may be a LoaderGraph, which delegates calls to its PlacedLoader.
        self._ring = ring

    def __iter__(self) -> Iterator:
        # Not itself a generator: the ring moves into the epoch's frame
        # here, so an iterator dropped before its first next still stops
        # the thread of a ring it had adopted.
        ring, self._ring = self._ring, None
        return self._epoch(ring)

    def _epoch(self, ring: Optional[_Ring]) -> Iterator:
        # Count from the cursor THIS wrapper was loaded with — never from
        # the inner loader's privates (any state_dict-compliant inner
        # works, including future composed loaders).
        self._yielded = self._start
        self._handed = None
        if ring is None:  # nothing reads this loader yet: start a ring
            q = AdjustableQueue(self.plane.depth)
            ring = _Ring(q, _read_chain([self], q))
        self.handover = "cold" if ring.q.empty() else "warm"
        try:
            for item in ring.batches:
                if isinstance(item, _EpochEnd):
                    if item.successor is not None or item.error is not None:
                        self._handed = (item, ring)
                    if item.successor is not None:
                        ring = None  # it goes on with the successor
                    return
                self._yielded += 1
                yield item
        finally:
            if ring is not None:
                ring.batches.close()
