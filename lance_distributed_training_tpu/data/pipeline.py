"""Input pipeline: read plan → decode → prefetch → host batch.

This is the north-star component (SURVEY.md §7.3). It replaces, in one class,
the reference's:

* ``LanceDataset(path, to_tensor_fn, batch_size, sampler)`` + single-process
  ``DataLoader`` (iterable path, ``/root/reference/lance_iterable.py:53-59,
  71-72`` — where ``num_workers`` is forced to 0 under DDP, so decode blocks
  the training process, ``:75-77``),
* ``SafeLanceDataset`` + ``DistributedSampler`` + ``get_safe_loader``
  multi-worker loading (map-style path, ``lance_map_style.py:54-69``).

TPU-native design: a background producer thread walks this process's read
plan, fans decode out over a thread pool, and fills a bounded queue of HOST
batches; placement to the device mesh is owned by the shared **placement
plane** (:mod:`.placement`) — the trainer wraps every pipeline in a
``PlacedLoader`` whose dedicated thread slices per local device, dispatches
async H2D, and double-buffers device-resident global batches, so the DMA
for step N+1 overlaps the device compute of step N. That overlap — not a
faster kernel — is what drives loader-stall below the 2% BASELINE target.
A pipeline yields host numpy batches and never touches a device.

Thread & queue policy (enforced by ``ldt check`` LDT201/LDT202): producer
threads are ``daemon=True`` (a wedged decode must never block interpreter
exit — a plain ThreadPoolExecutor would, via its atexit join), queues are
always bounded (``prefetch``, clamped >= 1) so decode can't run away from a
slow consumer, and teardown uses drain-then-join: pop until the producer's
blocked ``put()`` can observe the stop flag, then ``join`` with a timeout.
``service/server.py`` and ``service/client.py`` follow the same discipline.

**Resume-cursor contract** (r8 — implemented by all five loaders:
``DataPipeline``, ``MapStylePipeline``, ``FolderDataPipeline``,
``RemoteLoader``, ``FleetLoader``, and passed through ``PlacedLoader``):

* ``state_dict() -> {"step": n, ...}`` — ``n`` is the number of batches
  HANDED TO the consumer this epoch (the count increments immediately
  before each yield, so while the trainer runs its step on batch ``i`` the
  cursor already reads ``i + 1`` — exactly the next batch a restart must
  serve). Loaders that own an epoch also report ``"epoch"``.
* ``load_state_dict({"step": n, ...})`` — position the loader so its next
  iteration yields batch ``n`` of the (deterministically rebuilt) plan.
  Because plans are pure functions of (dataset, sampler, batch, shard,
  seed, epoch), the resumed tail is bit-identical to the uninterrupted
  run's (``samplers.slice_plan``).

The cursor is *position only*: checkpoints persist it next to the model
state (``utils/checkpoint.py``) and the trainer rebuilds the loader from
config before loading it.
"""

from __future__ import annotations

import queue
import threading
import time
from functools import partial
from typing import Callable, Iterator, Optional, Sequence

import numpy as np
import pyarrow as pa

from ..obs.costs import cost_context
from ..obs.lineage import make_lineage, observe_local_lineage
from ..obs.registry import default_registry
from ..obs.spans import span
from ..tune.tunable import AdjustableQueue, Tunable, _LiveQueues
from .cache import item_fingerprint
from .format import Dataset
from .samplers import (
    ReadRange,
    distributed_index_batches,
    slice_plan,
)

__all__ = ["DataPipeline", "MapStylePipeline", "make_train_pipeline", "make_map_style_pipeline", "make_eval_pipeline"]

_SENTINEL = object()


def _range_read(
    dataset: Dataset,
    ranges: Sequence[ReadRange],
    columns: Optional[Sequence[str]] = None,
) -> pa.Table:
    """Streaming read: concatenate the step's row-ranges (iterable path).
    ``columns`` projects at the fragment reader (the Lance scanner's column
    selection — zero-copy, skips unused columns entirely)."""
    tables = [
        dataset.read_range(r.fragment, r.start, r.stop, columns=columns)
        for r in ranges
    ]
    return pa.concat_tables(tables) if len(tables) > 1 else tables[0]


def _take_read(
    dataset: Dataset,
    indices: np.ndarray,
    columns: Optional[Sequence[str]] = None,
) -> pa.Table:
    """Random-access read: global-index gather (map-style path)."""
    return dataset.take(indices, columns=columns)


def _with_columns(read_fn: Callable, columns) -> Callable:
    """Bind a column projection into a read_fn (no-op when columns is None)."""
    if columns is None:
        return read_fn
    return partial(read_fn, columns=list(columns))


class DataPipeline:
    """Iterate host batches for THIS process's shard of the data.

    Since r16 this class is the runtime engine beneath a
    :class:`~.graph.LoaderGraph` assembly (``LanceSource → Decode →
    Cache → ... → InProcess``) — prefer composing the graph.

    Parameters
    ----------
    dataset: the columnar store.
    plan: one work item per step — row-ranges (iterable) or index arrays
        (map-style), interpreted by ``read_fn``.
    decode_fn: Table → dict of host numpy arrays (the ``to_tensor_fn`` /
        ``collate_fn`` plugin point, ``/root/reference/README.md:28,60``).
    prefetch: queue depth of decoded batches kept ahead of the consumer.
    producers: number of producer threads decoding plan items concurrently
        (results still yielded in plan order). With one producer there is no
        decode overlap *across* batches: the serial per-batch work (Arrow
        range read, label conversion, output-buffer faulting) gates the
        native decoder's thread pool. Two producers keep the pool saturated
        while the other thread runs the serial sections.
    workers: optional :class:`~.workers.WorkerPool` — read+decode runs in N
        worker processes instead of the producer thread (the reference's
        ``get_safe_loader``/``num_workers`` path,
        ``/root/reference/lance_map_style.py:60-69``).
    scheduler: optional :class:`~.schedule.DecodeScheduler` — worker-pool
        dispatch reorders predicted-heaviest-first within its lookahead
        window (straggler-aware scheduling); yield order stays plan
        order, so the stream is bit-identical. Ignored without
        ``workers`` (in-process decode has no dispatch to reorder).
    """

    def __init__(
        self,
        dataset: Dataset,
        plan: Sequence,
        decode_fn: Callable[[pa.Table], dict[str, np.ndarray]],
        prefetch: int = 2,
        read_fn: Callable[[Dataset, object], pa.Table] = _range_read,
        workers=None,
        producers: int = 1,
        buffer_pool=None,
        plan_cache=None,
        scheduler=None,
    ):
        self.dataset = dataset
        self.plan = list(plan)
        self.decode_fn = decode_fn
        self.prefetch = max(1, prefetch)
        self.read_fn = read_fn
        self.workers = workers
        self.scheduler = scheduler
        self.producers = max(1, producers)
        # Batch-cache plane (data/cache.py): a PlanCache binding of the
        # process BatchCache, consulted AT the decode boundary — a hit
        # skips the fragment read AND the decode entirely and returns a
        # byte-identical batch in fresh pool-leased pages (released by the
        # consumer exactly like a decoded batch); a miss decodes and fills.
        # None (the default, and the --no_batch_cache arm) is the exact
        # pre-r13 path: no probe, no copy, nothing.
        self.plan_cache = plan_cache
        # Buffer plane (data/buffers.py): the pool the decoder leased its
        # output pages from (and the WorkerPool its copy-out pages). This
        # pipeline owns the RELEASE side: leases go back after the yield
        # returns (the placement plane downstream has dispatched the H2D
        # copy by then; the pool's refcount guard protects aliased or
        # in-flight buffers). Falls back to the decoder's own pool so
        # direct constructions recycle too.
        self.buffer_pool = (
            buffer_pool if buffer_pool is not None
            else getattr(decode_fn, "buffer_pool", None)
        )
        # Telemetry: batches are stamped at creation (obs.lineage) and the
        # consumer closes the loop into pipeline_decode_ms /
        # pipeline_batch_age_ms histograms on the process registry.
        self.registry = default_registry()
        # Resume cursor (module docstring contract): _start_step positions
        # the next iteration; _yielded counts batches handed out, absolute
        # within the plan (seq/lineage stamps stay absolute too, so resumed
        # telemetry lines up with the uninterrupted run's).
        self._start_step = 0
        self._yielded = 0
        # Autotune surface (tune/): the live prefetch queues of the current
        # iteration, so set_prefetch() can move the bound mid-epoch.
        self._live = _LiveQueues()

    def set_prefetch(self, depth: int) -> int:
        """Autotune actuator: move the prefetch bound, live. Takes effect
        immediately on the current iteration's queue(s) (growing wakes a
        blocked producer; shrinking lets the backlog drain — batches are
        never dropped or reordered) and persists for later iterations."""
        depth = max(1, int(depth))
        self.prefetch = depth  # ldt: ignore[LDT1002] -- atomic int swap; readers take any recent value
        self._live.resize_total(depth)
        return depth

    def tunables(self):
        """Autotune registration surface (tune/): the prefetch depth,
        plus whatever the decode hook itself exposes (the coefficient-page
        chunk granularity for the device-decode decoder)."""
        out = [Tunable(
            "prefetch", lambda: self.prefetch, self.set_prefetch,
            lo=1, hi=16,
            doc="decoded host batches buffered ahead of the consumer",
        )]
        decoder = getattr(self.decode_fn, "tunables", None)
        if decoder is not None:
            out.extend(decoder())
        if self.scheduler is not None:
            out.extend(self.scheduler.tunables())
        return out

    def state_dict(self) -> dict:
        return {"step": int(self._yielded)}

    def load_state_dict(self, state: dict) -> None:
        step = int(state.get("step", 0))
        if step < 0:
            raise ValueError(f"negative resume cursor: {step}")
        self._start_step = step
        self._yielded = step

    def _release_host(self, batch) -> None:
        if self.buffer_pool is not None:
            self.buffer_pool.release_batch(batch)

    def _release_drained(self, item) -> None:
        """Teardown drains discard queued (lineage, batch) items — return
        their pool leases so an early-terminated iteration (exception,
        abandoned bench/test loop) recycles instead of relying on GC."""
        if (
            self.buffer_pool is not None
            and isinstance(item, tuple) and len(item) == 2
        ):
            self.buffer_pool.release_batch(item[1])

    def __len__(self) -> int:
        return len(self.plan)

    def _decode_item(self, item) -> dict:
        """The decode boundary, cache-aware: a batch-cache hit returns a
        byte-identical copy in fresh pool pages (no read, no decode); a
        miss runs read→decode and fills the cache. The no-cache path is
        exactly one ``None`` check."""
        cache = self.plan_cache
        if cache is not None:
            hit = cache.get(item, pool=self.buffer_pool)
            if hit is not None:
                return hit
        out = self.decode_fn(self.read_fn(self.dataset, item))
        if cache is not None:
            cache.put(item, out)
        return out

    def _worker_imap(self, items):
        """The pool dispatch seam: straggler-aware when a scheduler is
        attached (dispatch reordered, yield order unchanged — results
        still arrive in plan order either way)."""
        if self.scheduler is not None:
            return self.scheduler.imap(self.workers, items)
        return self.workers.imap(items)

    def _produce(self, q: "queue.Queue", stop: threading.Event,
                 plan: Sequence, base: int) -> None:
        """``plan`` is the resume-sliced tail; ``base`` keeps seq/lineage
        stamps absolute within the full plan."""
        try:
            if self.workers is not None:
                cache = self.plan_cache
                if cache is not None:
                    # Probe once, decode only the misses in the pool: the
                    # miss list keeps imap's plan-order contract, so result
                    # k of the iterator IS the k-th probed miss. A probed
                    # hit evicted before its fetch decodes inline (rare —
                    # a concurrent budget shrink), never off the iterator:
                    # consuming a worker result for a skipped item would
                    # shift every later batch one step (silent reorder).
                    probed = [cache.contains(item) for item in plan]
                    it = self._worker_imap(
                        [i for i, hit in zip(plan, probed) if not hit]
                    )
                else:
                    probed = None
                    it = self._worker_imap(plan)
                for off, item in enumerate(plan):
                    seq = base + off
                    if stop.is_set():
                        return
                    t0 = time.monotonic_ns()
                    with span("pipeline.decode", batch_seq=seq):
                        if probed is not None and probed[off]:
                            out = cache.get(item, pool=self.buffer_pool)
                            if out is None:  # evicted since the probe
                                out = self.decode_fn(
                                    self.read_fn(self.dataset, item)
                                )
                                cache.put(item, out)
                        else:
                            out = next(it)
                            if cache is not None:
                                # This miss never went through get():
                                # count it, or a cold cache under workers
                                # would report a 100% hit rate.
                                cache.note_miss()
                                cache.put(item, out)
                    # Worker-pool path: the producer only waits on results,
                    # so this is the pipelined arrival gap, not decode CPU.
                    decode_ms = (time.monotonic_ns() - t0) / 1e6
                    with span("pipeline.wait_out", batch_seq=seq):
                        q.put((make_lineage(seq, decode_ms), out))
            else:
                for off, item in enumerate(plan):
                    seq = base + off
                    if stop.is_set():
                        return
                    t0 = time.monotonic_ns()
                    # In-process decode runs on THIS thread, so the cost
                    # scope catches the decoder's note_cost() calls
                    # (entropy_ms, token_len) — the local-loader twin of
                    # the server's per-item ledger record.
                    with cost_context(item_fingerprint(item),
                                      step=seq) as cost, \
                         span("pipeline.decode", batch_seq=seq):
                        out = self._decode_item(item)
                        decode_ms = (time.monotonic_ns() - t0) / 1e6
                        cost.note(
                            decode_ms=round(decode_ms, 3),
                            bytes=sum(
                                getattr(v, "nbytes", 0)
                                for v in out.values()
                            ),
                        )
                    with span("pipeline.wait_out", batch_seq=seq):
                        q.put((make_lineage(seq, decode_ms), out))
            q.put(_SENTINEL)
        except BaseException as exc:  # surface worker errors to the consumer
            q.put(exc)

    def __iter__(self) -> Iterator[dict]:
        if self.workers is None and self.producers > 1:
            yield from self._iter_multi_producer()
            return
        if self.workers is not None and self.producers > 1:
            import warnings

            warnings.warn(
                "producers>1 has no effect with a WorkerPool: worker "
                "processes already decode in parallel (and H2D lives in "
                "the placement plane). Drop num_workers to use producer "
                "threads instead.",
                stacklevel=2,
            )
        if self.workers is not None and (
            getattr(self.read_fn, "func", None) in (_range_read, _take_read)
        ):
            # Projection was bound into read_fn, but worker-pool reads bypass
            # read_fn entirely — they project with the POOL's columns. Warn
            # when the two disagree (trainer passes the same list to both).
            bound = self.read_fn.keywords.get("columns")
            pool_cols = getattr(self.workers, "columns", None)
            if bound != pool_cols:
                import warnings

                warnings.warn(
                    f"pipeline columns {bound} differ from the WorkerPool's "
                    f"{pool_cols}; reads run inside the pool, so pass the "
                    "same columns= to WorkerPool(...) for the projection to "
                    "apply.",
                    stacklevel=2,
                )
        q: "queue.Queue" = AdjustableQueue(self.prefetch)
        self._live.install([q])
        stop = threading.Event()
        base = self._start_step
        self._yielded = base
        producer = threading.Thread(
            target=self._produce,
            args=(q, stop, slice_plan(self.plan, base), base),
            daemon=True, name="ldt-producer",
        )
        producer.start()
        try:
            while True:
                item = q.get()
                if item is _SENTINEL:
                    return
                if isinstance(item, BaseException):
                    raise item
                lineage, batch = item
                # Close the loop: creation→pickup age (prefetch-queue dwell
                # + any consumer lag) and the stamped decode duration.
                observe_local_lineage(self.registry, lineage)
                # Cursor advances as the batch is handed out: mid-step the
                # count already names the NEXT batch to serve (contract in
                # the module docstring).
                self._yielded += 1
                yield batch
                # The yield returned, the consumer had its turn — release;
                # any reference it kept defers recycling, not safety.
                self._release_host(batch)
        finally:
            stop.set()
            self._live.clear()
            # Drain so the producer's blocked put() can observe the stop flag
            # (releasing drained batches' pool leases as they go by).
            while producer.is_alive():
                try:
                    self._release_drained(q.get_nowait())
                except queue.Empty:
                    producer.join(timeout=0.1)

    def _iter_multi_producer(self) -> Iterator[dict]:
        """Ordered fan-out: ``producers`` daemon threads decode concurrently,
        thread ``k`` handling plan items ``k, k+N, …`` into its own bounded
        queue; the consumer round-robins the queues, so batches come out in
        plan order (sharded global-batch assembly stays deterministic) with
        total buffered depth ≈ ``max(prefetch, producers)``. Daemon threads +
        the drain in ``finally`` mean a hung decode can never block
        interpreter exit (plain ``ThreadPoolExecutor`` workers would — its
        atexit hook joins them)."""
        n = self.producers
        per = max(1, -(-max(self.prefetch, n) // n))
        queues = [AdjustableQueue(per) for _ in range(n)]
        self._live.install(queues)
        stop = threading.Event()
        base = self._start_step
        self._yielded = base
        plan = slice_plan(self.plan, base)

        def produce(k: int) -> None:
            try:
                for j, item in enumerate(plan[k::n]):
                    seq = base + k + j * n
                    if stop.is_set():
                        return
                    t0 = time.monotonic_ns()
                    with span("pipeline.decode", batch_seq=seq, producer=k):
                        out = self._decode_item(item)
                    decode_ms = (time.monotonic_ns() - t0) / 1e6
                    with span("pipeline.wait_out", batch_seq=seq,
                              producer=k):
                        queues[k].put((make_lineage(seq, decode_ms), out))
                queues[k].put(_SENTINEL)
            except BaseException as exc:  # surface errors to the consumer
                queues[k].put(exc)

        threads = [
            threading.Thread(
                target=produce, args=(k,), daemon=True, name=f"ldt-producer-{k}"
            )
            for k in range(n)
        ]
        for t in threads:
            t.start()
        try:
            active = [True] * n
            done = 0
            i = 0
            while done < n:
                k = i % n
                i += 1
                if not active[k]:
                    continue
                item = queues[k].get()
                if item is _SENTINEL:
                    active[k] = False
                    done += 1
                    continue
                if isinstance(item, BaseException):
                    raise item
                lineage, batch = item
                observe_local_lineage(self.registry, lineage)
                self._yielded += 1
                yield batch
                # Release after the consumer's turn.
                self._release_host(batch)
        finally:
            stop.set()
            self._live.clear()
            # Drain so blocked put()s can observe the stop flag (releasing
            # drained batches' pool leases).
            while any(t.is_alive() for t in threads):
                for q in queues:
                    try:
                        item = q.get_nowait()
                    except queue.Empty:
                        continue
                    self._release_drained(item)
                for t in threads:
                    t.join(timeout=0.05)


def make_train_pipeline(
    dataset: Dataset,
    sampler_type: str,
    batch_size: int,
    process_index: int,
    process_count: int,
    decode_fn: Callable,
    prefetch: int = 2,
    check_deadlock: bool = True,
    workers=None,
    producers: int = 1,
    shuffle: bool = False,
    seed: int = 0,
    epoch: int = 0,
    columns: Optional[Sequence[str]] = None,
    buffer_pool=None,
    batch_cache=None,
    schedule=None,
) -> "LoaderGraph":
    """Iterable-style pipeline — parity with ``get_sampler``+``get_dataset``+
    ``get_loader`` (``/root/reference/lance_iterable.py:53-72,86-88``).

    ``batch_size`` is the PER-PROCESS batch (global batch = ``batch_size ×
    process_count`` assembled by sharding). With ``check_deadlock`` the full
    cross-process plan set is validated for the equal-step-count invariant
    before any training starts — the static guard against the reference's
    documented fragment-imbalance deadlock (``README.md:140-157``).

    Since r16 a thin :class:`~.graph.LoaderGraph` assembly: plan
    construction lives in :class:`~.graph.LanceSource`, the cache binding
    in the graph's decode-boundary compile — compiled eagerly here so
    construction-time errors (empty plan, non-DP-aware sampler) surface
    exactly where they always did.
    """
    from .graph import (
        Buffers,
        Cache,
        Decode,
        InProcess,
        LanceSource,
        LoaderGraph,
        Pool,
        Prefetch,
    )

    graph = LoaderGraph(
        LanceSource(dataset, sampler_type, batch_size, process_index,
                    process_count, shuffle=shuffle, seed=seed, epoch=epoch,
                    check_deadlock=check_deadlock),
        Decode(decode_fn, columns=columns, schedule=schedule),
        Cache(batch_cache),
        Pool(workers),
        Buffers(buffer_pool),
        Prefetch(prefetch, producers=producers),
        InProcess(),
    )
    graph.compile()
    return graph


def make_eval_pipeline(
    read_fn: Callable[[np.ndarray], pa.Table],
    num_rows: int,
    global_batch: int,
    process_index: int,
    process_count: int,
    decode_fn: Callable,
    *,
    prefetch: int = 2,
    producers: int = 1,
    index_pool: Optional[np.ndarray] = None,
    buffer_pool=None,
    batch_cache=None,
    dataset_fingerprint: Optional[str] = None,
) -> "LoaderGraph":
    """Full-coverage eval loader: every row exactly once, ONE compiled shape.

    Train loaders either drop the ragged tail (batch plans) or keep it ragged
    and pay one extra XLA compile per eval shape (``full_scan_plan``). Here
    the tail is padded back to a full global batch by wrap-around rows and
    each yielded batch carries ``_weight`` ∈ {0,1}^[B] marking the pads;
    ``make_eval_step`` weights the per-example metric with it, so eval covers
    100% of rows at a single static batch shape (the reference's eval simply
    iterates a DataLoader, ``modelling/classification.py:20-32`` — ragged
    tails are free under eager torch, not under jit).

    ``read_fn`` maps an index array to an Arrow table — ``Dataset.take`` for
    the columnar arm, the file-reading path for the folder arm — so both
    storage arms share this loader. Decode runs on producer threads (eval is
    a single pass; no worker-pool protocol needed).

    Since r16 a thin :class:`~.graph.LoaderGraph` assembly over
    :class:`~.graph.EvalSource`; the caller-supplied ``dataset_fingerprint``
    (computed ONCE at Dataset construction / FolderDataPipeline init, never
    per eval rebuild) rides the Cache node, and the ``eval=1`` scope keeps
    eval entries (they carry ``_weight``) disjoint from train entries over
    the same rows.
    """
    from .graph import (
        Buffers,
        Cache,
        Decode,
        EvalSource,
        InProcess,
        LoaderGraph,
        Prefetch,
    )

    graph = LoaderGraph(
        EvalSource(read_fn, num_rows, global_batch, process_index,
                   process_count, index_pool=index_pool),
        Decode(decode_fn),
        Cache(batch_cache, dataset_fingerprint=dataset_fingerprint),
        Buffers(buffer_pool),
        Prefetch(prefetch, producers=producers),
        InProcess(),
    )
    graph.compile()
    return graph


class MapStylePipeline:
    """Random-access pipeline: permuted indices → ``take`` → decode.

    Parity with ``SafeLanceDataset`` + ``DistributedSampler`` +
    ``get_safe_loader`` (``/root/reference/lance_map_style.py:54-69``);
    ``set_epoch`` reshuffles like ``DistributedSampler.set_epoch``
    (``lance_map_style.py:85-86``).

    Since r16 this class is the runtime engine beneath a
    :class:`~.graph.LoaderGraph` assembly (``MapStyleSource → Decode →
    ... → InProcess``) — prefer composing the graph.
    """

    def __init__(
        self,
        dataset: Dataset,
        batch_size: int,
        process_index: int,
        process_count: int,
        decode_fn: Callable,
        *,
        shuffle: bool = True,
        seed: int = 0,
        epoch: int = 0,
        drop_last: bool = True,
        prefetch: int = 2,
        workers=None,
        producers: int = 1,
        columns: Optional[Sequence[str]] = None,
        index_pool: Optional[np.ndarray] = None,
        buffer_pool=None,
        batch_cache=None,
        scheduler=None,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.process_index = process_index
        self.process_count = process_count
        self.decode_fn = decode_fn
        self.shuffle = shuffle
        self.seed = seed
        self.epoch = epoch
        self.drop_last = drop_last
        self.prefetch = prefetch
        self.workers = workers
        self.scheduler = scheduler
        self.producers = producers
        self.buffer_pool = buffer_pool
        self.batch_cache = batch_cache
        self.columns = list(columns) if columns is not None else None
        # Optional row-filter pool (Dataset.filter_indices): shard/permute
        # POSITIONS in the pool, then map back to global rows — every process
        # derives the same pool, so the equal-step invariant holds unchanged.
        self.index_pool = (
            np.asarray(index_pool, dtype=np.int64)
            if index_pool is not None
            else None
        )
        self._start_step = 0
        self._yielded = 0
        # The per-epoch inner DataPipeline currently iterating, so
        # set_prefetch reaches its live queue (None between epochs).
        self._live_pipe: Optional[DataPipeline] = None

    def set_prefetch(self, depth: int) -> int:
        """Autotune actuator — mirrors :meth:`DataPipeline.set_prefetch`,
        forwarded to the epoch's live inner pipeline when one is up."""
        depth = max(1, int(depth))
        self.prefetch = depth  # ldt: ignore[LDT1002] -- atomic int swap; readers take any recent value
        pipe = self._live_pipe
        if pipe is not None:
            pipe.set_prefetch(depth)
        return depth

    def tunables(self):
        out = [Tunable(
            "prefetch", lambda: self.prefetch, self.set_prefetch,
            lo=1, hi=16,
            doc="decoded host batches buffered ahead of the consumer",
        )]
        decoder = getattr(self.decode_fn, "tunables", None)
        if decoder is not None:
            out.extend(decoder())
        if self.scheduler is not None:
            out.extend(self.scheduler.tunables())
        return out

    def set_epoch(self, epoch: int) -> None:
        if epoch != self.epoch:
            self.epoch = epoch
            # A new epoch's plan starts at its own step 0; a stale cursor
            # must not slice it.
            self._start_step = 0
            self._yielded = 0

    def state_dict(self) -> dict:
        """Resume cursor (contract: module docstring) — the per-epoch
        index-batch plan is a pure function of (dataset, shard, seed,
        epoch), so (epoch, step) fully names the position."""
        return {"epoch": int(self.epoch), "step": int(self._yielded)}

    def load_state_dict(self, state: dict) -> None:
        if "epoch" in state:
            self.epoch = int(state["epoch"])
        step = int(state.get("step", 0))
        if step < 0:
            raise ValueError(f"negative resume cursor: {step}")
        self._start_step = step
        self._yielded = step

    def _index_batches(self) -> list[np.ndarray]:
        pool = self.index_pool
        n = self.dataset.count_rows() if pool is None else len(pool)
        batches = distributed_index_batches(
            n,
            self.batch_size,
            self.process_index,
            self.process_count,
            shuffle=self.shuffle,
            seed=self.seed,
            epoch=self.epoch,
            drop_last=self.drop_last,
        )
        if pool is not None:
            batches = [pool[b] for b in batches]
        return batches

    def __len__(self) -> int:
        return len(self._index_batches())

    def _plan_cache(self):
        """Per-epoch cache binding. Map-style epochs reshuffle at ROW
        level, so epoch e's index batches genuinely differ from epoch
        0's — the item-content keys make that an automatic (honest) miss,
        while unshuffled configs and repeated evals over the same pool
        hit. The dataset fingerprint was computed once at Dataset
        construction; reused here every epoch."""
        if self.batch_cache is None:
            return None
        from .cache import PlanCache, decode_fingerprint, plan_fingerprint

        return PlanCache(
            self.batch_cache,
            self.dataset.fingerprint(),
            lambda: plan_fingerprint(
                decode=decode_fingerprint(self.decode_fn),
                columns=self.columns,
            ),
        )

    def __iter__(self) -> Iterator[dict]:
        pipe = DataPipeline(
            self.dataset,
            self._index_batches(),
            self.decode_fn,
            self.prefetch,
            read_fn=_with_columns(_take_read, self.columns),
            workers=self.workers,
            producers=self.producers,
            buffer_pool=self.buffer_pool,
            plan_cache=self._plan_cache(),
            scheduler=self.scheduler,
        )
        # The cursor lives HERE (this is the consumer-facing loader); the
        # inner single-shot pipeline just starts at the same offset.
        pipe.load_state_dict({"step": self._start_step})
        self._yielded = self._start_step
        self._live_pipe = pipe  # ldt: ignore[LDT1002] -- handle publish; set_prefetch tolerates either epoch's pipe
        try:
            for batch in pipe:
                self._yielded += 1
                yield batch
        finally:
            self._live_pipe = None


def make_map_style_pipeline(dataset: Dataset, *args, **kwargs) -> "LoaderGraph":
    """Map-style loader as a :class:`~.graph.LoaderGraph` assembly —
    accepts exactly :class:`MapStylePipeline`'s signature and streams
    bit-identically to a direct construction."""
    from .graph import (
        Buffers,
        Cache,
        Decode,
        InProcess,
        LoaderGraph,
        MapStyleSource,
        Pool,
        Prefetch,
    )
    import inspect

    bound = inspect.signature(MapStylePipeline.__init__).bind(
        None, dataset, *args, **kwargs
    )
    bound.apply_defaults()
    a = bound.arguments
    graph = LoaderGraph(
        MapStyleSource(dataset, a["batch_size"], a["process_index"],
                       a["process_count"], shuffle=a["shuffle"],
                       seed=a["seed"], epoch=a["epoch"],
                       drop_last=a["drop_last"],
                       index_pool=a["index_pool"]),
        Decode(a["decode_fn"], columns=a["columns"],
               schedule=a["scheduler"]),
        Cache(a["batch_cache"]),
        Pool(a["workers"]),
        Buffers(a["buffer_pool"]),
        Prefetch(a["prefetch"], producers=a["producers"]),
        InProcess(),
    )
    graph.compile()
    return graph
