"""File-based control arm — the ``torch_version/`` equivalent.

The reference keeps a parallel set of torchvision drivers reading
``ImageFolder``/``Food101`` straight from files, "deliberately
near-isomorphic" to the Lance drivers so wandb comparisons isolate the data
layer (``/root/reference/README.md:286-290``; ``torch_version/iter_style.py``,
``torch_version/map_style.py``). Here the control arm is a *pipeline*, not a
driver fork: :class:`FolderDataPipeline` yields the same batch dicts as the
columnar pipelines and plugs into the same ``train()``, so
columnar-vs-files is a one-flag A/B (``--data_format folder``).
"""

from __future__ import annotations

from typing import Callable, Iterator, Optional

import numpy as np

from .authoring import _folder_samples
from .samplers import distributed_index_batches, sharded_batch_plan

__all__ = ["FolderDataPipeline", "read_sample_batch"]


def read_sample_batch(samples, idx_batch: np.ndarray):
    """Read files ``samples[i] for i in idx_batch`` into the columnar batch
    schema ``{image: binary, label: int64}`` — the shared file-side read used
    by both the train pipeline and the full-coverage eval loader."""
    import pyarrow as pa

    payloads, labels = [], []
    for i in idx_batch:
        path, label = samples[int(i)]
        with open(path, "rb") as f:
            payloads.append(f.read())
        labels.append(label)
    return pa.table(
        {"image": pa.array(payloads, pa.binary()),
         "label": pa.array(labels, pa.int64())}
    )


class FolderDataPipeline:
    """Distributed file-reading pipeline over an image-folder tree.

    Both torchvision twins, selected by ``loader_style``:

    - ``"map"``: ``DistributedSampler``-equivalent per-index sharding with
      per-epoch reshuffle, mirroring ``torch_version/map_style.py:59-61``.
    - ``"iterable"``: sequential file-walk semantics mirroring
      ``torch_version/iter_style.py:17-50`` — contiguous batches of the
      walk-ordered file list dealt round-robin across processes (the same
      batch-range plan as the columnar iterable arm, so the columnar-vs-files
      A/B isolates storage, not sampling); ``shuffle`` permutes batch ORDER
      only, rows within a batch keep walk order.

    Either way the decode hook receives ``{image: list[bytes], label:
    np.ndarray}`` shaped like a columnar read, so the SAME decoder classes
    work on both arms.

    Since r16 this class is the runtime engine beneath a
    :class:`~.graph.LoaderGraph` assembly (``FolderSource → Decode → ... →
    InProcess``) — prefer composing the graph.
    """

    def __init__(
        self,
        root: str,
        batch_size: int,
        process_index: int,
        process_count: int,
        decode_fn: Callable,
        *,
        loader_style: str = "map",
        shuffle: bool = True,
        seed: int = 0,
        epoch: int = 0,
        drop_last: bool = True,
        prefetch: int = 2,
        workers=None,
        producers: int = 1,
        buffer_pool=None,
        batch_cache=None,
        dataset_fingerprint=None,
        scheduler=None,
    ):
        self.samples, self.classes = _folder_samples(root)
        if not self.samples:
            raise ValueError(f"no images under {root}")
        # Content identity of the walk-ordered corpus: hashed at most ONCE
        # per pipeline and reused for every epoch's batch-cache keys (each
        # __iter__ builds a fresh inner pipeline; re-hashing per epoch was
        # the fingerprint-churn bug the r13 satellite fixed) — and lazily,
        # so cacheless runs over million-file corpora never pay the
        # full-tree stat+hash at all (see dataset_fingerprint). A caller
        # that already computed it (the trainer does, once per RUN, and
        # rebuilds this pipeline per epoch) injects it here.
        self._dataset_fingerprint: Optional[str] = (
            str(dataset_fingerprint)
            if dataset_fingerprint is not None else None
        )
        if loader_style not in ("map", "iterable"):
            raise ValueError(
                f"loader_style must be 'map' or 'iterable', got {loader_style!r}"
            )
        self.loader_style = loader_style
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
        self._start_step = 0
        self._yielded = 0

    def set_epoch(self, epoch: int) -> None:
        if epoch != self.epoch:
            self.epoch = epoch
            self._start_step = 0
            self._yielded = 0

    def state_dict(self) -> dict:
        """Resume cursor (contract: ``data/pipeline.py``) — the per-epoch
        index plan is a pure function of (walk-ordered file list, shard,
        seed, epoch), so (epoch, step) fully names the position."""
        return {"epoch": int(self.epoch), "step": int(self._yielded)}

    def load_state_dict(self, state: dict) -> None:
        if "epoch" in state:
            self.epoch = int(state["epoch"])
        step = int(state.get("step", 0))
        if step < 0:
            raise ValueError(f"negative resume cursor: {step}")
        self._start_step = step
        self._yielded = step

    @property
    def num_classes(self) -> int:
        return len(self.classes)

    @property
    def dataset_fingerprint(self) -> str:
        """Corpus content identity (``cache.folder_fingerprint``),
        computed on first use and cached for the pipeline's lifetime."""
        if self._dataset_fingerprint is None:
            from .cache import folder_fingerprint

            self._dataset_fingerprint = folder_fingerprint(self.samples)
        return self._dataset_fingerprint

    def _index_batches(self) -> list[np.ndarray]:
        if self.loader_style == "iterable":
            plan = sharded_batch_plan(
                [len(self.samples)],
                self.batch_size,
                self.process_index,
                self.process_count,
                shuffle=self.shuffle,
                seed=self.seed,
                epoch=self.epoch,
            )
            return [
                np.concatenate([np.arange(r.start, r.stop) for r in ranges])
                for ranges in plan
            ]
        return distributed_index_batches(
            len(self.samples),
            self.batch_size,
            self.process_index,
            self.process_count,
            shuffle=self.shuffle,
            seed=self.seed,
            epoch=self.epoch,
            drop_last=self.drop_last,
        )

    def __len__(self) -> int:
        return len(self._index_batches())

    def _read(self, idx_batch: np.ndarray):
        return read_sample_batch(self.samples, idx_batch)

    def _plan_cache(self):
        """Per-epoch cache binding over the construction-time fingerprint.
        Iterable-style epochs shuffle batch ORDER only, so their index
        batches replay identical content every epoch — all hits from
        epoch 2 regardless of the permutation; map-style row reshuffles
        miss honestly (item-content keys)."""
        if self.batch_cache is None:
            return None
        from .cache import PlanCache, decode_fingerprint, plan_fingerprint

        return PlanCache(
            self.batch_cache,
            self.dataset_fingerprint,
            lambda: plan_fingerprint(
                decode=decode_fingerprint(self.decode_fn)
            ),
        )

    def __iter__(self) -> Iterator[dict]:
        from .pipeline import DataPipeline

        pipe = DataPipeline(
            dataset=None,  # read_fn closes over self.samples instead
            plan=self._index_batches(),
            decode_fn=self.decode_fn,
            prefetch=self.prefetch,
            read_fn=lambda _ds, idx: self._read(idx),
            workers=self.workers,
            producers=self.producers,
            buffer_pool=self.buffer_pool,
            plan_cache=self._plan_cache(),
            scheduler=self.scheduler,
        )
        pipe.load_state_dict({"step": self._start_step})
        self._yielded = self._start_step
        for batch in pipe:
            self._yielded += 1
            yield batch
