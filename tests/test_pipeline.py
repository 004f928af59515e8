"""Input-pipeline tests: decode correctness, prefetch, sharding, map-style."""

import numpy as np
import pyarrow as pa
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from lance_distributed_training_tpu.data import (
    DataPipeline,
    ImageClassificationDecoder,
    MapStylePipeline,
    PlacementPlane,
    make_train_pipeline,
    numeric_decoder,
    write_dataset,
)
from lance_distributed_training_tpu.parallel import get_mesh


def test_decoder_shapes_and_dtypes(image_table):
    decode = ImageClassificationDecoder(image_size=64)
    out = decode(image_table.slice(0, 16))
    assert out["image"].shape == (16, 64, 64, 3)
    assert out["image"].dtype == np.uint8
    assert out["label"].shape == (16,) and out["label"].dtype == np.int32
    assert out["label"].tolist() == image_table.column("label").to_pylist()[:16]


def test_iterable_pipeline_host_batches(image_dataset):
    pipe = make_train_pipeline(
        image_dataset, "batch", 32, 0, 1,
        ImageClassificationDecoder(image_size=32),
    )
    batches = list(pipe)
    assert len(batches) == len(pipe) == 240 // 32
    assert all(b["image"].shape == (32, 32, 32, 3) for b in batches)


def test_two_process_batches_disjoint(image_dataset):
    # Global-batch reassembly invariant: the two processes' label streams
    # together cover exactly the dealt batches, no overlap.
    decode = ImageClassificationDecoder(image_size=32)
    seen = []
    for p in range(2):
        pipe = make_train_pipeline(image_dataset, "batch", 16, p, 2, decode)
        seen.append([tuple(b["label"].tolist()) for b in pipe])
    assert len(seen[0]) == len(seen[1])
    assert not (set(seen[0]) & set(seen[1]))


def test_pipeline_device_put_sharded(image_dataset):
    mesh = get_mesh()
    assert len(jax.devices()) == 8  # conftest forced 8 CPU devices
    pipe = PlacementPlane(mesh).wrap(make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        ImageClassificationDecoder(image_size=32),
    ))
    batch = next(iter(pipe))
    assert isinstance(batch["image"], jax.Array)
    assert batch["image"].sharding.spec == P("data")
    # 16 rows over 8 devices -> shard of 2 per device.
    assert batch["image"].addressable_shards[0].data.shape[0] == 2


def test_pipeline_propagates_decode_error(image_dataset):
    def bad_decode(table):
        raise RuntimeError("boom in worker")

    pipe = make_train_pipeline(image_dataset, "batch", 16, 0, 1, bad_decode)
    with pytest.raises(RuntimeError, match="boom in worker"):
        list(pipe)


def test_pipeline_early_stop_no_hang(image_dataset):
    pipe = make_train_pipeline(
        image_dataset, "batch", 16, 0, 1,
        ImageClassificationDecoder(image_size=32), prefetch=1,
    )
    it = iter(pipe)
    next(it)
    it.close()  # generator close must not deadlock the producer


def test_map_style_reshuffles_by_epoch(image_dataset):
    decode = ImageClassificationDecoder(image_size=32)
    pipe = MapStylePipeline(image_dataset, 24, 0, 1, decode, seed=1)
    e0 = [b["label"].tolist() for b in pipe]
    pipe.set_epoch(1)
    e1 = [b["label"].tolist() for b in pipe]
    assert sorted(sum(e0, [])) == sorted(sum(e1, []))  # same multiset
    assert e0 != e1  # different order


def test_map_style_two_process_cover_all(image_dataset):
    decode = ImageClassificationDecoder(image_size=32)
    labels = []
    for p in range(2):
        pipe = MapStylePipeline(
            image_dataset, 24, p, 2, decode, shuffle=False, drop_last=False
        )
        for b in pipe:
            labels.extend(b["label"].tolist())
    assert len(labels) == 240
    assert sorted(labels) == sorted(image_dataset.take(
        np.arange(240)).column("label").to_pylist())


def test_numeric_decoder_fixed_size_list(tmp_path):
    tokens = pa.array(
        [list(range(i, i + 8)) for i in range(50)], pa.list_(pa.int32(), 8)
    )
    table = pa.table({"tokens": tokens, "label": pa.array(range(50), pa.int64())})
    ds = write_dataset(table, tmp_path / "txt", max_rows_per_file=20)
    pipe = make_train_pipeline(ds, "batch", 10, 0, 1, numeric_decoder)
    b = next(iter(pipe))
    assert b["tokens"].shape == (10, 8)
    assert b["tokens"][3].tolist() == list(range(3, 11))


def test_fragment_sampler_through_pipeline(image_dataset):
    # fragment plan over [100,100,40] with pad: both procs get equal steps.
    decode = ImageClassificationDecoder(image_size=32)
    pipes = [
        make_train_pipeline(image_dataset, "fragment", 20, p, 2, decode)
        for p in range(2)
    ]
    s0, s1 = (sum(1 for _ in p) for p in pipes)
    assert s0 == s1 == max(len(p) for p in pipes)


def test_multi_producer_preserves_order(image_dataset):
    decode = ImageClassificationDecoder(image_size=32)
    ref = [
        b["label"].tolist()
        for b in make_train_pipeline(image_dataset, "batch", 16, 0, 1, decode)
    ]
    got = [
        b["label"].tolist()
        for b in make_train_pipeline(
            image_dataset, "batch", 16, 0, 1, decode, producers=3
        )
    ]
    assert got == ref


def test_multi_producer_propagates_error(image_dataset):
    def bad_decode(table):
        raise RuntimeError("decode exploded")

    pipe = make_train_pipeline(
        image_dataset, "batch", 16, 0, 1, bad_decode, producers=2
    )
    with pytest.raises(RuntimeError, match="decode exploded"):
        list(pipe)


def test_full_scan_multiprocess_refused(image_dataset):
    # FullScanSampler is "not DP-aware" (reference README.md:126,130-138);
    # stitching identical per-process scans into a "global" batch silently
    # duplicates data, so the pipeline must refuse.
    with pytest.raises(ValueError, match="not DP-aware"):
        make_train_pipeline(
            image_dataset, "full", 16, 0, 2,
            ImageClassificationDecoder(image_size=32),
        )


def test_iterable_shuffle_reorders_batches(image_dataset):
    decode = ImageClassificationDecoder(image_size=32)

    def labels(epoch):
        pipe = make_train_pipeline(
            image_dataset, "batch", 16, 0, 1, decode,
            shuffle=True, seed=7, epoch=epoch,
        )
        return [tuple(b["label"].tolist()) for b in pipe]

    e0, e0_again, e1 = labels(0), labels(0), labels(1)
    assert e0 == e0_again  # deterministic per epoch
    assert e0 != e1  # reshuffled across epochs
    assert sorted(e0) == sorted(e1)  # same batches, new order


def test_column_projection_iterable(tmp_path, image_table):
    # Extra column in the schema must never reach the decoder when the
    # pipeline projects (Lance scanner column selection).
    extra = image_table.append_column(
        "weight", pa.array(np.arange(240, dtype=np.float64))
    )
    ds = write_dataset(extra, tmp_path / "wide", mode="create",
                       max_rows_per_file=100)
    seen_schemas = []

    def probe_decode(table):
        seen_schemas.append(table.column_names)
        return {"n": np.asarray([table.num_rows])}

    pipe = make_train_pipeline(
        ds, "batch", 32, 0, 1, probe_decode, columns=["image", "label"]
    )
    assert len(list(pipe)) == 240 // 32
    assert all(names == ["image", "label"] for names in seen_schemas)


def test_column_projection_map_style(tmp_path, image_table):
    extra = image_table.append_column(
        "weight", pa.array(np.arange(240, dtype=np.float64))
    )
    ds = write_dataset(extra, tmp_path / "wide2", mode="create",
                       max_rows_per_file=100)
    decode = ImageClassificationDecoder(image_size=32)
    assert decode.required_columns == ["image", "label"]
    pipe = MapStylePipeline(ds, 16, 0, 1, decode,
                            columns=decode.required_columns)
    batch = next(iter(pipe))
    assert set(batch) == {"image", "label"}
    assert batch["image"].shape == (16, 32, 32, 3)


def test_eval_pipeline_full_coverage(image_dataset):
    """make_eval_pipeline: 100% of rows at a single compiled shape — the
    weighted multiset of labels equals the dataset's, pads carry weight 0."""
    import numpy as np

    from lance_distributed_training_tpu.data import make_eval_pipeline

    def decode(table):
        return {"label": np.asarray(table.column("label").to_numpy())}

    pipe = make_eval_pipeline(
        lambda idx: image_dataset.take(idx), image_dataset.count_rows(),
        64, 0, 1, decode,
    )
    assert len(pipe) == 4  # ceil(240/64)
    real = []
    for batch in pipe:
        assert batch["label"].shape == (64,)  # single static shape
        assert batch["_weight"].shape == (64,)
        real.extend(batch["label"][batch["_weight"] == 1.0].tolist())
    all_labels = image_dataset.take(
        np.arange(image_dataset.count_rows())
    ).column("label").to_pylist()
    assert sorted(real) == sorted(all_labels)
