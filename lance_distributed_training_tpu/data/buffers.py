"""Pooled-buffer memory plane: recycled decode pages + shared-memory IPC.

Every loader arm bottoms out at the host's decode+copy rate. Two of the
copies are pure overhead:

* **output-buffer faulting** — each decoded batch faulted a fresh
  ``np.empty`` (~38 MB at 512×224px), so the kernel zero-fills new pages on
  every batch while warm, already-faulted pages from two batches ago sit in
  the allocator. :class:`BufferPool` keeps those pages alive and hands them
  back out: lease-based, keyed by ``(shape, dtype)``, thread-safe, bounded.
* **IPC pickling** — every worker-pool batch was pickled across the process
  boundary (serialise + pipe write + pipe read + deserialise = four full
  copies of the batch). :class:`ShmRing`/:class:`ShmSlotWriter` replace that
  with ``multiprocessing.shared_memory`` ring slots: the worker writes the
  decoded tensors into a slot and returns only a tiny descriptor ``(slot,
  shapes, dtypes, offsets)``; the consumer maps the same physical pages and
  copies once into a pooled buffer.

Lease-safety model (why release() can run before the data is dead): a
released page is only *recycled* once nothing else references it.
``jax.device_put`` on the CPU backend may zero-copy **alias** the numpy
buffer (jaxlib's ``kImmutableZeroCopy`` host-buffer semantics), and on
accelerator backends the runtime holds the source buffer until the async
H2D transfer completes — in both cases the jax machinery holds a Python
reference to the array. :meth:`BufferPool.release` therefore parks the page
on a *pending* list and a sweep recycles it only when ``sys.getrefcount``
shows the pool as the sole owner. Callers can release eagerly (right after
``device_put`` dispatch, or right after ``yield``) without ever corrupting
an in-flight transfer or an aliased device array.

Shared-memory lifecycle (Python 3.10 resource-tracker semantics): every
process that creates *or attaches* a segment registers its name with the
shared ``resource_tracker`` (a set, so re-registration is a no-op). We never
unregister manually — each segment is unlinked exactly once via
``SharedMemory.unlink()`` (which unregisters), in :meth:`ShmRing.cleanup`,
driven by ``WorkerPool.shutdown()`` or its ``weakref.finalize`` guard. Slot
names are deterministic (``ldtshm_<session>_<slot>``), so cleanup unlinks
every slot even when the worker that created it already crashed; the
tracker remains as the last-resort reaper if the whole process dies without
running finalizers.

Thread & queue policy: the free-slot queue is bounded (``nslots`` + poison
headroom) and every blocking ``get`` carries a timeout with a pickle
fallback, so a lost slot token (worker killed mid-batch) degrades
throughput instead of deadlocking the pool.

Metrics (process registry, served by ``/metrics``): ``bufpool_hit_total`` /
``bufpool_miss_total`` / ``bufpool_evict_total`` / ``bufpool_in_use`` /
``bufpool_pending`` and ``shm_batches_total`` / ``shm_bytes_total`` /
``shm_slot_resizes_total`` / ``shm_fallback_total`` / ``shm_slot_wait_ms``.
"""

from __future__ import annotations

import sys
import threading
import time
import uuid
import weakref
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..obs.registry import MetricsRegistry, default_registry
from ..utils import leaktrack

__all__ = [
    "BufferPool",
    "RaggedPage",
    "default_buffer_pool",
    "ShmRing",
    "ShmSlotWriter",
    "shm_available",
]


class RaggedPage(NamedTuple):
    """One variable-length column's pooled pages: a flat ``values`` page
    sized to a capacity *bucket* (so batches of nearby token counts recycle
    the same physical pages) and an exact ``offsets`` page. Both are
    ordinary pool leases — ``release``/``release_batch`` on the arrays (the
    consumer's existing discipline) reclaims them; there is no separate
    ragged release verb to forget."""

    values: np.ndarray  # [capacity_bucket] — caller fills [:total]
    offsets: np.ndarray  # int32 [n_sequences + 1]
    capacity: int  # the bucket the values page was keyed under

# 64-byte alignment for tensor offsets inside a shm slot (cache-line; also
# satisfies every numpy dtype's alignment requirement).
_ALIGN = 64


def _solo_refcount() -> int:
    """Calibrate the refcount a pending-list entry shows when the pool is
    its sole owner: one ref from the list, one from the loop variable, one
    from ``getrefcount``'s own argument binding. Computed (not hardcoded)
    so an interpreter that counts differently cannot make the sweep recycle
    a page something still reads."""
    lst = [object()]  # no extra name binding: mirror the sweep loop exactly
    for x in lst:
        return sys.getrefcount(x)
    raise AssertionError("unreachable")


_SOLO_REFS = _solo_refcount()


class BufferPool:
    """Lease-based pool of recycled numpy output buffers.

    ``lease(shape, dtype)`` returns a warm page when one is free (hit) or
    faults a fresh ``np.empty`` (miss). ``release(arr)`` gives the page
    back; it is recycled only once the pool is its sole referent (see the
    module docstring's lease-safety model), so eager release after
    ``device_put`` dispatch is always safe. Arrays the pool never leased
    are ignored by ``release`` — callers can blanket-release a whole batch
    dict without tracking which values were pooled.
    """

    def __init__(
        self,
        max_free_per_key: int = 8,
        max_pending: int = 32,
        registry: Optional[MetricsRegistry] = None,
    ):
        self._lock = threading.Lock()
        self._free: Dict[Tuple, List[np.ndarray]] = {}
        # id(arr) -> weakref. WEAK on purpose: a leased page someone drops
        # without releasing (early generator close, a crashed consumer, a
        # forgotten teardown drain) must degrade to ordinary garbage — a
        # missed recycle — never a permanent leak pinned by the pool. The
        # callback (no pool lock: runs at GC time) retires the entry.
        self._outstanding: Dict[int, weakref.ref] = {}
        self._pending: List[np.ndarray] = []  # released, still referenced
        self.max_free_per_key = max(0, max_free_per_key)
        self.max_pending = max(1, max_pending)
        reg = registry if registry is not None else default_registry()
        self._hits = reg.counter("bufpool_hit_total")
        self._misses = reg.counter("bufpool_miss_total")
        self._evicts = reg.counter("bufpool_evict_total")
        self._in_use = reg.gauge("bufpool_in_use")
        self._pending_gauge = reg.gauge("bufpool_pending")
        self._ragged_leases = reg.counter("bufpool_ragged_leases_total")
        self._ragged_slack = reg.counter("bufpool_ragged_slack_bytes_total")

    @staticmethod
    def _key(shape, dtype) -> Tuple:
        return (tuple(int(s) for s in shape), np.dtype(dtype).str)

    def _stash_locked(self, arr: np.ndarray) -> None:
        key = self._key(arr.shape, arr.dtype)
        free = self._free.setdefault(key, [])
        if len(free) < self.max_free_per_key:
            free.append(arr)
        else:
            self._evicts.inc()  # cap reached: let the page be garbage

    def _sweep_locked(self) -> None:
        still: List[np.ndarray] = []
        for arr in self._pending:
            # One ref each: self._pending, the loop variable, getrefcount's
            # argument — _SOLO_REFS exactly. More means a consumer, a live
            # batch dict, or jax (alias / in-flight transfer) still holds
            # the page: not recyclable yet.
            if sys.getrefcount(arr) <= _SOLO_REFS:
                self._stash_locked(arr)
            else:
                still.append(arr)
        if len(still) > self.max_pending:
            # Bound the deferred set: the overflow pages are dropped from
            # the pool entirely (their external holders keep them alive;
            # they just never recycle).
            self._evicts.inc(len(still) - self.max_pending)
            still = still[-self.max_pending:]
        self._pending = still
        self._pending_gauge.set(len(still))

    def lease(self, shape: Sequence[int], dtype) -> np.ndarray:
        key = self._key(shape, dtype)
        arr: Optional[np.ndarray] = None
        with self._lock:
            self._sweep_locked()
            free = self._free.get(key)
            if free:
                arr = free.pop()
                self._hits.inc()
            else:
                self._misses.inc()
        if arr is None:
            arr = np.empty(tuple(shape), np.dtype(dtype))
        outstanding = self._outstanding
        gauge = self._in_use

        def _dropped(_ref, _key=id(arr)):
            # Lease died unreleased: retire the entry (plain dict pop, no
            # pool lock — this runs from the GC) so the id can be reused.
            outstanding.pop(_key, None)
            gauge.set(len(outstanding))
            if leaktrack.enabled():
                # The leak event itself, caught live: a page dropped
                # without release (LDT1201's witness corroboration).
                leaktrack.track_dropped("pool-page", _key)

        with self._lock:
            outstanding[id(arr)] = weakref.ref(arr, _dropped)
            gauge.set(len(outstanding))
        if leaktrack.enabled():
            # depth 3: past this frame and the hook, to lease()'s caller —
            # the static ownership model's acquire-site join key.
            leaktrack.track_acquire("pool-page", id(arr), depth=3)
        return arr

    def lease_ragged(self, total: int, n_sequences: int,
                     values_dtype) -> RaggedPage:
        """Lease one variable-length column's page pair (see
        :class:`RaggedPage`). The values page is keyed by its **capacity
        bucket** (next power of two ≥ ``total``), not the exact token
        count — without the bucket, every distinct batch token total would
        mint its own free-list key and the pool would never recycle a
        ragged page (the fragmentation the r15 tentpole removes). Both
        pages ride the ordinary lease/release discipline — the LDT1201
        ownership analyzer and the ``LDT_LEAK_SANITIZER`` witness track
        them through the same ``BufferPool.lease`` acquire site."""
        from .token_pack import ragged_capacity

        cap = ragged_capacity(int(total))
        values = self.lease((cap,), values_dtype)
        try:
            offsets = self.lease((int(n_sequences) + 1,), np.int32)
        except BaseException:
            # The pair acquires atomically or not at all: a failed offsets
            # lease must not strand the values page (LDT1201's
            # exception-edge class).
            self.release(values)
            raise
        try:
            # Counted only once BOTH pages are held — a MemoryError'd lease
            # must not inflate the ragged series exactly in the degraded
            # runs where an operator reads them.
            self._ragged_leases.inc()
            self._ragged_slack.inc(
                (cap - int(total)) * np.dtype(values_dtype).itemsize
            )
        except BaseException:
            self.release(values)
            self.release(offsets)
            raise
        return RaggedPage(values, offsets, cap)

    def release(self, arr) -> bool:
        """Return a leased page. ``False`` (and a no-op) for arrays this
        pool does not own — safe to call on every value of a mixed batch.
        A *view* of a leased page (a ragged values page sliced to its real
        token count) releases its base: the refcount sweep still defers
        recycling until every view dies, so this is always safe."""
        if not isinstance(arr, np.ndarray):
            return False
        with self._lock:
            ref = self._outstanding.pop(id(arr), None)
            if ref is None or ref() is not arr:  # foreign (or id reuse race)
                # Walk the view chain: releasing batch["c__values"][:n]
                # must find the pooled base page it windows.
                base = arr.base
                hops = 0
                while isinstance(base, np.ndarray) and hops < 4:
                    ref = self._outstanding.pop(id(base), None)
                    if ref is not None and ref() is base:
                        arr = base
                        break
                    base = base.base
                    hops += 1
                else:
                    return False
                if ref is None or ref() is not arr:
                    return False
            self._in_use.set(len(self._outstanding))
            self._pending.append(arr)
            self._sweep_locked()
        if leaktrack.enabled():
            leaktrack.track_release("pool-page", id(arr))
        return True

    def release_batch(self, batch) -> int:
        """Release every pooled value of a ``{name: array}`` batch dict.
        Returns how many were pool-owned."""
        if not isinstance(batch, dict):
            return 0
        return sum(self.release(v) for v in list(batch.values()))

    def set_budget(self, max_free_per_key: int) -> int:
        """Autotune actuator (tune/): resize the recycled-page budget, live.
        Growing lets more warm pages survive between batches (the hit-rate
        lever); shrinking trims every free list to the new cap immediately
        (counted as evictions) — outstanding leases are untouched, so no
        in-flight batch ever loses its page."""
        cap = max(0, int(max_free_per_key))
        with self._lock:
            self.max_free_per_key = cap
            for key, free in self._free.items():
                if len(free) > cap:
                    self._evicts.inc(len(free) - cap)
                    del free[cap:]
        return cap

    def tunables(self):
        """Autotune registration surface: the per-(shape, dtype) free-page
        budget."""
        from ..tune.tunable import Tunable

        return [Tunable(
            "bufpool_pages",
            lambda: self.max_free_per_key,
            self.set_budget,
            lo=2, hi=64,
            doc="recycled pages kept warm per (shape, dtype) key",
        )]

    def sweep(self) -> None:
        """Run one pending→free sweep now. The sweep normally rides every
        ``lease``/``release``; the placement plane's release-at-dispatch
        discipline means the LAST batches of an epoch can sit on the
        pending list until jax drops its transfer references — a steady
        state the next lease clears, but teardown paths and leak asserts
        (tests, the CI smoke) call this to observe 'everything recycled'
        without having to lease again."""
        with self._lock:
            self._sweep_locked()

    def stats(self) -> dict:
        with self._lock:
            return {
                "outstanding": len(self._outstanding),
                "pending": len(self._pending),
                "free": sum(len(v) for v in self._free.values()),
            }


_DEFAULT_POOL: Optional[BufferPool] = None
_DEFAULT_POOL_LOCK = threading.Lock()


def default_buffer_pool() -> BufferPool:
    """The process-wide pool every layer shares (decoder output pages,
    wire-receive pages, shm copy-out pages) — one pool so a page freed by
    one stage warms the next."""
    global _DEFAULT_POOL
    with _DEFAULT_POOL_LOCK:
        if _DEFAULT_POOL is None:
            _DEFAULT_POOL = BufferPool()
        return _DEFAULT_POOL


# -- shared-memory ring -----------------------------------------------------


def shm_available() -> bool:
    """Can this platform back a shm ring? (POSIX shared memory present and
    writable — containers occasionally mount /dev/shm noexec/ro.)"""
    try:
        from multiprocessing import shared_memory

        seg = shared_memory.SharedMemory(create=True, size=16)
        try:
            seg.unlink()
        finally:
            seg.close()
        return True
    except (ImportError, OSError):
        return False


def _slot_name(session: str, slot: int) -> str:
    return f"ldtshm_{session}_{slot}"


def _round_slot_size(nbytes: int) -> int:
    """Slot capacity for a batch of ``nbytes``: 25% headroom rounded up to
    4 KiB pages, so steady-state jitter in batch size (ragged label widths,
    contrastive text columns) doesn't resize every other batch."""
    padded = nbytes + nbytes // 4
    return max(4096, (padded + 4095) // 4096 * 4096)


def _plan_layout(batch: dict) -> Optional[Tuple[list, int]]:
    """``(tensor_metas, total_bytes)`` for writing ``batch`` into one slot;
    ``None`` when the batch isn't a pure dict of numpy arrays (the caller
    then falls back to the pickle transport)."""
    metas = []
    offset = 0
    for name, arr in batch.items():
        if not isinstance(arr, np.ndarray):
            return None
        offset = (offset + _ALIGN - 1) // _ALIGN * _ALIGN
        metas.append([name, arr.dtype.str, list(arr.shape), offset])
        offset += arr.nbytes
    return metas, offset


class ShmSlotWriter:
    """Worker-process half of the ring: acquire a free slot token, size the
    slot's segment to the batch, copy the tensors in, and return a small
    picklable descriptor. Falls back (returns ``None``) when no slot frees
    up within the acquire timeout — liveness is never hostage to a lost
    token."""

    def __init__(self, session: str, free_q, acquire_timeout_s: float = 10.0):
        self.session = session
        self._free_q = free_q
        self.acquire_timeout_s = acquire_timeout_s
        # slot -> (SharedMemory, size) as last seen by THIS process.
        self._segments: Dict[int, Tuple[object, int]] = {}

    def _acquire(self):
        import queue as _queue

        deadline = time.monotonic() + self.acquire_timeout_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            try:
                tok = self._free_q.get(timeout=min(0.25, remaining))
            except _queue.Empty:
                continue
            return tok  # (slot, gen, size) or None = shutdown poison

    def _ensure(self, slot: int, gen: int, size: int, needed: int):
        """Attach (or create/resize) the slot's segment with capacity for
        ``needed`` bytes. Returns ``(seg, gen, size)``."""
        from multiprocessing import shared_memory

        name = _slot_name(self.session, slot)
        cached = self._segments.get(slot)
        if needed > size:
            # Resize = unlink + recreate under the same name. Only the
            # token holder touches a slot, so no other process can be
            # mid-write; readers detect staleness by the size change
            # (sizes strictly grow).
            if size > 0:
                if cached is not None and cached[1] == size:
                    old = cached[0]
                else:
                    if cached is not None:
                        cached[0].close()
                    old = shared_memory.SharedMemory(name=name)
                try:
                    old.unlink()
                except FileNotFoundError:
                    pass  # earlier failed resize already removed it
                finally:
                    old.close()
                self._segments.pop(slot, None)
            size = _round_slot_size(needed)
            gen += 1
            seg = self._create(name, size)
            self._segments[slot] = (seg, size)
            return seg, gen, size
        if cached is not None and cached[1] == size:
            return cached[0], gen, size
        if cached is not None:
            cached[0].close()
        if size == 0:
            # A (slot, gen, 0) token after a failed write: the segment may
            # or may not exist — _create below reconciles either way.
            size = _round_slot_size(needed)
            gen += 1
            seg = self._create(name, size)
            self._segments[slot] = (seg, size)
            return seg, gen, size
        seg = shared_memory.SharedMemory(name=name)
        self._segments[slot] = (seg, size)
        return seg, gen, size

    @staticmethod
    def _create(name: str, size: int):
        """Create a segment, reconciling a leftover from a failed earlier
        write (same name, unknown size): unlink it and retry once."""
        from multiprocessing import shared_memory

        try:
            return shared_memory.SharedMemory(name=name, create=True,
                                              size=size)
        except FileExistsError:
            stale = shared_memory.SharedMemory(name=name)
            try:
                stale.unlink()
            finally:
                stale.close()
            return shared_memory.SharedMemory(name=name, create=True,
                                              size=size)

    def write_batch(self, batch: dict) -> Optional[dict]:
        plan = _plan_layout(batch)
        if plan is None:
            return None
        metas, total = plan
        t0 = time.monotonic_ns()
        tok = self._acquire()
        if tok is None:  # timeout or shutdown poison: pickle fallback
            return None
        # Unpack the token FIRST (pure tuple destructuring, cannot raise):
        # from here down the requeue in the except arm owns the slot, so
        # no statement between acquire and the try can strand it (LDT1201).
        slot, gen, size = tok
        wait_ms = (time.monotonic_ns() - t0) / 1e6
        try:
            seg, gen, size = self._ensure(slot, gen, size, total)
            resized = size != tok[2]
            for name, dtype_str, shape, offset in metas:
                dst = np.ndarray(
                    tuple(shape), np.dtype(dtype_str),
                    buffer=seg.buf, offset=offset,
                )
                np.copyto(dst, batch[name])
        except BaseException as exc:
            # Requeue a RESET token (size 0), not the one we were handed:
            # _ensure may have already unlinked the slot's old segment, so
            # the stale (slot, gen, size) would poison every later writer
            # with FileNotFoundError. Size 0 makes the next holder create
            # fresh (reconciling any leftover segment).
            self._segments.pop(slot, None)
            self._free_q.put((slot, gen + 1, 0))
            if isinstance(exc, OSError):
                # E.g. ENOSPC on an undersized /dev/shm (64 MB docker
                # default vs ~48 MB slots): degrade to the pickle
                # transport for this batch instead of killing the epoch —
                # the documented fallback policy.
                return None
            raise
        return {
            "slot": slot, "gen": gen, "size": size, "total": total,
            "wait_ms": round(wait_ms, 3), "resized": resized,
            "tensors": metas,
        }

    def close(self) -> None:
        for seg, _ in self._segments.values():
            try:
                seg.close()
            except (OSError, BufferError):  # BufferError: copy in flight
                pass
        self._segments.clear()


class ShmRing:
    """Parent/consumer half of the ring: owns the slot-token queue and the
    segments' lifecycle. ``read_batch`` maps a descriptor's slot, copies
    the tensors out (into ``BufferPool`` pages when given), and returns the
    token to the free queue — the consumer ack that lets a worker reuse the
    slot."""

    def __init__(
        self,
        nslots: int,
        ctx,
        acquire_timeout_s: float = 10.0,
        registry: Optional[MetricsRegistry] = None,
    ):
        if nslots < 1:
            raise ValueError("ShmRing needs nslots >= 1")
        self.session = uuid.uuid4().hex[:12]
        self.nslots = nslots
        self.acquire_timeout_s = acquire_timeout_s
        # Bounded: at most nslots tokens circulate; the headroom absorbs
        # shutdown poison pills without ever blocking.
        self._free_q = ctx.Queue(maxsize=nslots + 64)
        for slot in range(nslots):
            self._free_q.put((slot, 0, 0))  # size 0 = not yet created
        self._segments: Dict[int, Tuple[object, int]] = {}
        self._closed = False
        self._lock = threading.Lock()
        reg = registry if registry is not None else default_registry()
        self._batches = reg.counter("shm_batches_total")
        self._bytes = reg.counter("shm_bytes_total")
        self._resizes = reg.counter("shm_slot_resizes_total")
        self._fallbacks = reg.counter("shm_fallback_total")
        self._wait_hist = reg.histogram("shm_slot_wait_ms")

    def writer_args(self) -> tuple:
        """The picklable bits a worker needs to build its
        :class:`ShmSlotWriter` (rides ``ProcessPoolExecutor`` initargs —
        legal because initargs travel as spawn-time ``Process`` arguments,
        the one context where an ``mp.Queue`` may be pickled)."""
        return (self.session, self._free_q, self.acquire_timeout_s)

    def _attach(self, slot: int, size: int):
        from multiprocessing import shared_memory

        cached = self._segments.get(slot)
        if cached is not None and cached[1] == size:
            return cached[0]
        if cached is not None:
            cached[0].close()
            self._segments.pop(slot, None)
        seg = shared_memory.SharedMemory(name=_slot_name(self.session, slot))
        self._segments[slot] = (seg, size)
        return seg

    def read_batch(
        self, desc: dict, buffer_pool: Optional[BufferPool] = None
    ) -> dict:
        """Descriptor → ``{name: np.ndarray}`` (freshly owned arrays; the
        slot is released back to the ring before returning)."""
        if self._closed:
            raise RuntimeError("ShmRing is closed")
        slot, gen, size = desc["slot"], desc["gen"], desc["size"]
        out: Dict[str, np.ndarray] = {}
        try:
            # Lock only the attach-cache lookup: the slot's CONTENT is
            # exclusively ours while we hold its token, and serialising
            # the multi-MB copies would bottleneck multi-client servers
            # on one reader thread's memcpy. The attach lives INSIDE the
            # requeue-protected try: a vanished segment (worker died
            # mid-epoch, FileNotFoundError here) must return the token
            # too, not just copy failures.
            with self._lock:
                seg = self._attach(slot, size)
            for name, dtype_str, shape, offset in desc["tensors"]:
                shape = tuple(shape)
                src = np.ndarray(
                    shape, np.dtype(dtype_str), buffer=seg.buf, offset=offset
                )
                if buffer_pool is not None:
                    dst = buffer_pool.lease(shape, dtype_str)
                else:
                    dst = np.empty(shape, np.dtype(dtype_str))
                # Park ownership in `out` BEFORE the copy: if copyto raises
                # (a torn/stale descriptor), the except arm below can
                # release every page it leased so far, dst included.
                out[name] = dst
                np.copyto(dst, src)
        except BaseException:
            # A failed copy-out must not strand resources: return the
            # leased pages to the pool and — critically — the slot token
            # to the ring (a lost token shrinks the ring FOREVER; the
            # writer side already requeues a reset token on its own
            # failures, this is the reader-side mirror).
            if buffer_pool is not None:
                for arr in out.values():
                    buffer_pool.release(arr)
            self._free_q.put((slot, gen, size))
            if leaktrack.enabled():
                leaktrack.track_release("shm-token",
                                        (self.session, slot, gen))
            raise
        self._free_q.put((slot, gen, size))
        if leaktrack.enabled():
            leaktrack.track_release("shm-token", (self.session, slot, gen))
        self._batches.inc()
        self._bytes.inc(desc["total"])
        if desc.get("resized"):
            self._resizes.inc()
        self._wait_hist.observe(desc.get("wait_ms", 0.0))
        return out

    def release_token(self, desc: dict) -> None:
        """Return a descriptor's slot without reading it (teardown path for
        completed-but-unconsumed futures)."""
        if self._closed:
            return
        self._free_q.put((desc["slot"], desc["gen"], desc["size"]))
        if leaktrack.enabled():
            leaktrack.track_release(
                "shm-token", (self.session, desc["slot"], desc["gen"])
            )

    def count_fallback(self) -> None:
        self._fallbacks.inc()

    def poison(self, n: int) -> None:
        """Wake ``n`` workers potentially blocked on slot acquisition so
        executor shutdown can join them."""
        import queue as _queue

        for _ in range(n):
            try:
                self._free_q.put_nowait(None)
            except _queue.Full:
                break

    def cleanup(self) -> None:
        """Unlink every slot segment (whichever process created it — names
        are deterministic) and close the token queue. Idempotent; ignores
        already-gone segments, so it is safe after worker crashes."""
        from multiprocessing import shared_memory

        with self._lock:
            if self._closed:
                return
            self._closed = True
            for seg, _ in self._segments.values():
                try:
                    seg.close()
                except (OSError, BufferError):  # BufferError: copy in flight
                    pass
            self._segments.clear()
            for slot in range(self.nslots):
                try:
                    seg = shared_memory.SharedMemory(
                        name=_slot_name(self.session, slot)
                    )
                except FileNotFoundError:
                    continue
                except OSError:
                    continue
                try:
                    seg.unlink()  # unregisters: balances the create-time register
                finally:
                    seg.close()
            try:
                self._free_q.close()
                self._free_q.cancel_join_thread()
            except (OSError, AttributeError):
                pass
