"""Ragged token plane (r15): planner, decoder, kernel, pool, wire, tune.

Covers the end-to-end contract: variable-length pages from Arrow to
device, deterministic FFD packing, bit-identical packed streams across
repeats and resume, protocol-v4 negotiation (and the v3 padded fallback),
and the padding-waste observability the autotuner acts on.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pyarrow as pa
import pytest

from lance_distributed_training_tpu.data.authoring import (
    create_variable_length_token_dataset,
)
from lance_distributed_training_tpu.data.buffers import BufferPool
from lance_distributed_training_tpu.data.format import Dataset
from lance_distributed_training_tpu.data.pipeline import make_train_pipeline
from lance_distributed_training_tpu.data.token_pack import (
    OFFSETS_SUFFIX,
    PACK_META_KEY,
    PACK_MODE_BUCKET,
    PACK_MODE_FFD,
    PACK_SLOT_KEY,
    PACK_START_KEY,
    VALUES_SUFFIX,
    TokenDecoder,
    TokenPackConfig,
    TokenPackPlanner,
    is_ragged_batch,
    is_ragged_key,
    length_bucket,
    ragged_capacity,
)
from lance_distributed_training_tpu.obs.registry import MetricsRegistry

pytestmark = pytest.mark.fast


def _ragged_table(lengths, vocab=100, seed=0, dtype=np.int32):
    rng = np.random.default_rng(seed)
    ids = [rng.integers(2, vocab, int(L), dtype=dtype) for L in lengths]
    return pa.table({"input_ids": pa.array(ids, pa.list_(pa.int32()))}), ids


def _digest(batch) -> str:
    h = hashlib.sha256()
    for k in sorted(batch):
        arr = np.asarray(batch[k])
        h.update(k.encode())
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


# -- planner -----------------------------------------------------------------


def test_planner_deterministic_and_disjoint():
    lengths = [7, 31, 2, 31, 15, 1, 64, 9, 9, 3]
    planner = TokenPackPlanner(TokenPackConfig(pack_len=64, rows_multiple=2))
    a = planner.plan(lengths)
    b = planner.plan(lengths)
    assert np.array_equal(a.slot, b.slot)
    assert np.array_equal(a.start, b.start)
    assert (a.rows, a.pack_len) == (b.rows, b.pack_len)
    # No two runs overlap, every run fits its slot.
    cells = set()
    for i, L in enumerate(lengths):
        L = min(L, a.pack_len)
        assert 0 <= a.slot[i] < a.rows
        assert a.start[i] + L <= a.pack_len
        for c in range(L):
            key = (int(a.slot[i]), int(a.start[i]) + c)
            assert key not in cells
            cells.add(key)
    assert a.rows % 2 == 0  # rows_multiple honoured
    assert a.payload_tokens == sum(min(L, a.pack_len) for L in lengths)


def test_planner_truncates_and_counts():
    planner = TokenPackPlanner(TokenPackConfig(pack_len=16, rows_multiple=1))
    plan = planner.plan([40, 3])
    assert plan.pack_len == 16
    assert plan.truncated_tokens == 24
    assert plan.payload_tokens == 16 + 3


def test_planner_bucket_mode_preserves_rows():
    planner = TokenPackPlanner(TokenPackConfig(pack_len=128))
    plan = planner.plan_bucket([5, 60, 17])
    assert list(plan.slot) == [0, 1, 2]
    assert list(plan.start) == [0, 0, 0]
    assert plan.rows == 3
    assert plan.pack_len == length_bucket(60, hi=128) == 64


def test_planner_length_bucket_ladder():
    planner = TokenPackPlanner(
        TokenPackConfig(pack_len=256, len_bucket_lo=32)
    )
    assert planner.plan([4, 9]).pack_len == 32  # floor
    assert planner.plan([40]).pack_len == 64
    assert planner.plan([500]).pack_len == 256  # capped at pack_len


def test_capacity_bucketing():
    assert ragged_capacity(1) == 256
    assert ragged_capacity(257) == 512
    assert ragged_capacity(512) == 512
    assert ragged_capacity(513) == 1024


def test_planner_tunables_declare_bounds():
    planner = TokenPackPlanner(TokenPackConfig(pack_len=128))
    knobs = {t.name: t for t in planner.tunables()}
    assert set(knobs) == {"pack_len", "pack_rows_quantum"}
    for t in knobs.values():
        assert t.lo < t.hi
    # Actuation moves the config (and the fingerprint with it).
    before = planner.fingerprint()
    knobs["pack_rows_quantum"].set(2)
    assert planner.config.rows_multiple == 2
    assert planner.fingerprint() != before


# -- decoder -----------------------------------------------------------------


def test_decoder_pack_emits_convention():
    lengths = [5, 12, 3, 30]
    table, ids = _ragged_table(lengths)
    dec = TokenDecoder(mode="pack", seq_len=32,
                       planner=TokenPackPlanner(TokenPackConfig(pack_len=32)))
    out = dec(table)
    assert is_ragged_batch(out)
    assert set(out) == {
        "input_ids" + VALUES_SUFFIX, "input_ids" + OFFSETS_SUFFIX,
        PACK_SLOT_KEY, PACK_START_KEY, PACK_META_KEY,
    }
    values = out["input_ids" + VALUES_SUFFIX]
    offsets = out["input_ids" + OFFSETS_SUFFIX]
    assert values.shape[0] == ragged_capacity(sum(lengths))
    assert list(offsets) == list(np.cumsum([0] + lengths))
    for i, seq in enumerate(ids):
        lo, hi = int(offsets[i]), int(offsets[i + 1])
        assert np.array_equal(values[lo:hi], seq)
    assert (values[int(offsets[-1]):] == 0).all()  # deterministic tail
    assert out[PACK_META_KEY][3] == PACK_MODE_FFD


def test_decoder_pack_repeat_is_bit_identical():
    table, _ = _ragged_table([9, 2, 17, 40, 6], seed=3)
    dec = TokenDecoder(mode="pack", seq_len=64)
    assert _digest(dec(table)) == _digest(dec(table))


def test_decoder_drops_variable_attention_mask():
    lengths = [4, 7]
    rng = np.random.default_rng(0)
    ids = [rng.integers(2, 50, L, dtype=np.int32) for L in lengths]
    table = pa.table({
        "input_ids": pa.array(ids, pa.list_(pa.int32())),
        "attention_mask": pa.array(
            [np.ones(L, np.int8) for L in lengths], pa.list_(pa.int8())
        ),
    })
    out = TokenDecoder(mode="pack", seq_len=16)(table)
    # The device-side mask supersedes the stored all-ones column.
    assert "attention_mask" + VALUES_SUFFIX not in out
    assert "input_ids" + VALUES_SUFFIX in out


def test_decoder_pack_rejects_fixed_row_columns():
    table = pa.table({
        "input_ids": pa.array([[1, 2], [3]], pa.list_(pa.int32())),
        "label": pa.array([0, 1], pa.int64()),
    })
    with pytest.raises(ValueError, match="bucket mode"):
        TokenDecoder(mode="pack", seq_len=8)(table)


def test_decoder_bucket_mode_keeps_rows():
    table = pa.table({
        "input_ids": pa.array([[1, 2], [3, 4, 5]], pa.list_(pa.int32())),
        "label": pa.array([7, 9], pa.int64()),
    })
    out = TokenDecoder(mode="bucket", seq_len=64)(table)
    assert out[PACK_META_KEY][3] == PACK_MODE_BUCKET
    assert list(out[PACK_SLOT_KEY]) == [0, 1]
    assert np.array_equal(out["label"], [7, 9])


def test_decoder_padded_control_arm():
    lengths = [3, 8, 1]
    table, ids = _ragged_table(lengths, seed=1)
    out = TokenDecoder(mode="pad", seq_len=16)(table)
    assert out["input_ids"].shape == (3, 16)
    assert out["attention_mask"].shape == (3, 16)
    for i, seq in enumerate(ids):
        assert np.array_equal(out["input_ids"][i, : len(seq)], seq)
        assert (out["input_ids"][i, len(seq):] == 0).all()
        assert out["attention_mask"][i].sum() == len(seq)


def test_decoder_fixed_schema_passthrough_zero_copy(tmp_path):
    table = pa.table({
        "input_ids": pa.array([[1, 2, 3], [4, 5, 6]],
                              pa.list_(pa.int32(), 3)),
    })
    reg = MetricsRegistry()
    out = TokenDecoder(mode="pack", seq_len=8)(table)
    assert out["input_ids"].shape == (2, 3)
    # The zero-copy view windows the Arrow buffer (a view has a base).
    assert out["input_ids"].base is not None


def test_decoder_cache_fingerprint_scopes_pack_knobs():
    a = TokenDecoder(mode="pack", seq_len=64,
                     planner=TokenPackPlanner(
                         TokenPackConfig(pack_len=64, rows_multiple=8)))
    b = TokenDecoder(mode="pack", seq_len=64,
                     planner=TokenPackPlanner(
                         TokenPackConfig(pack_len=64, rows_multiple=4)))
    c = TokenDecoder(mode="pad", seq_len=64)
    assert a.cache_fingerprint() != b.cache_fingerprint()
    assert a.cache_fingerprint() != c.cache_fingerprint()


def test_decoder_picklable_for_workers():
    import pickle

    dec = TokenDecoder(mode="pack", seq_len=32, buffer_pool=BufferPool())
    clone = pickle.loads(pickle.dumps(dec))
    assert clone.buffer_pool is None
    table, _ = _ragged_table([4, 9])
    assert _digest(clone(table)) == _digest(
        TokenDecoder(mode="pack", seq_len=32)(table)
    )


# -- waste accounting --------------------------------------------------------


def test_waste_counters_padded_vs_packed():
    reg = MetricsRegistry()
    import lance_distributed_training_tpu.data.token_pack as tp

    lengths = [4] * 15 + [60]  # long tail: padded waste is large
    table, _ = _ragged_table(lengths, seed=5)
    orig = tp._pack_metrics
    counters = [
        reg.counter(n) for n in (
            "pack_payload_tokens_total", "pack_grid_tokens_total",
            "pack_sequences_total", "pack_truncated_tokens_total",
            "pack_batches_total",
        )
    ]
    tp._pack_metrics = lambda: tuple(counters)
    try:
        TokenDecoder(mode="pad", seq_len=64)(table)
        snap = reg.snapshot()
        padded_waste = 1 - (
            snap["pack_payload_tokens_total"] / snap["pack_grid_tokens_total"]
        )
        reg2 = MetricsRegistry()
        counters2 = [
            reg2.counter(n) for n in (
                "pack_payload_tokens_total", "pack_grid_tokens_total",
                "pack_sequences_total", "pack_truncated_tokens_total",
                "pack_batches_total",
            )
        ]
        tp._pack_metrics = lambda: tuple(counters2)
        TokenDecoder(
            mode="pack", seq_len=64,
            planner=TokenPackPlanner(
                TokenPackConfig(pack_len=64, rows_multiple=1)
            ),
        )(table)
        snap2 = reg2.snapshot()
        packed_waste = 1 - (
            snap2["pack_payload_tokens_total"]
            / snap2["pack_grid_tokens_total"]
        )
    finally:
        tp._pack_metrics = orig
    assert padded_waste > 0.8  # 4-token rows padded to 64
    assert packed_waste < padded_waste - 0.3  # the 30-point cut, in-miniature


# -- device kernel -----------------------------------------------------------


def test_pack_kernel_round_trip_and_determinism():
    from lance_distributed_training_tpu.ops.token_device import (
        make_pack_transform,
        unpack_token_batch,
    )

    lengths = [5, 12, 3, 30, 1, 22]
    table, ids = _ragged_table(lengths, seed=7)
    dec = TokenDecoder(mode="pack", seq_len=32,
                       planner=TokenPackPlanner(
                           TokenPackConfig(pack_len=32, rows_multiple=1)))
    batch = dec(table)
    tx = make_pack_transform()
    out = tx(batch)
    assert set(out) == {"input_ids", "attention_mask", "segment_ids",
                        "position_ids"}
    grid = np.asarray(out["input_ids"])
    seg = np.asarray(out["segment_ids"])
    pos = np.asarray(out["position_ids"])
    slot = batch[PACK_SLOT_KEY]
    start = batch[PACK_START_KEY]
    for i, seq in enumerate(ids):
        row, st = int(slot[i]), int(start[i])
        assert np.array_equal(grid[row, st:st + len(seq)], seq)
        assert (seg[row, st:st + len(seq)] == i + 1).all()
        assert np.array_equal(pos[row, st:st + len(seq)],
                              np.arange(len(seq)))
    # Dead cells carry segment 0 and the mask mirrors liveness.
    assert np.array_equal(np.asarray(out["attention_mask"]), (seg > 0))
    # Bit-determinism across repeated kernel runs.
    out2 = tx(dec(table))
    assert _digest({k: np.asarray(v) for k, v in out.items()}) == _digest(
        {k: np.asarray(v) for k, v in out2.items()}
    )
    # Unpack inverts the scatter exactly.
    back = np.asarray(unpack_token_batch(
        out["input_ids"], batch["input_ids" + OFFSETS_SUFFIX], slot, start,
        capacity=int(batch["input_ids" + VALUES_SUFFIX].shape[0]),
    ))
    assert np.array_equal(back, batch["input_ids" + VALUES_SUFFIX])


def test_pack_transform_passthrough_for_padded_batches():
    from lance_distributed_training_tpu.ops.token_device import (
        make_pack_transform,
    )

    tx = make_pack_transform()
    batch = {"input_ids": np.zeros((4, 8), np.int32)}
    assert tx(batch) is batch


def test_pack_transform_bucket_mode_omits_segments():
    from lance_distributed_training_tpu.ops.token_device import (
        make_pack_transform,
    )

    table = pa.table({
        "input_ids": pa.array([[1, 2], [3, 4, 5]], pa.list_(pa.int32())),
        "label": pa.array([7, 9], pa.int64()),
    })
    out = make_pack_transform()(TokenDecoder(mode="bucket", seq_len=64)(table))
    assert "segment_ids" not in out and "position_ids" not in out
    assert np.asarray(out["input_ids"]).shape[0] == 2
    assert np.array_equal(np.asarray(out["label"]), [7, 9])


def test_segment_attention_mask():
    from lance_distributed_training_tpu.ops.flash import (
        segment_attention_mask,
    )

    seg = np.array([[1, 1, 2, 0]], np.int32)
    mask = np.asarray(segment_attention_mask(seg))[0, 0]
    expect = np.array([
        [1, 1, 0, 0],
        [1, 1, 0, 0],
        [0, 0, 1, 0],
        [0, 0, 0, 0],
    ], bool)
    assert np.array_equal(mask, expect)


# -- buffer plane ------------------------------------------------------------


def test_lease_ragged_buckets_and_recycles():
    pool = BufferPool()
    page = pool.lease_ragged(300, 4, np.int32)
    assert page.capacity == 512
    assert page.values.shape == (512,)
    assert page.offsets.shape == (5,)
    pool.release(page.values)
    pool.release(page.offsets)
    pool.sweep()
    # A nearby total lands in the SAME bucket: the page recycles.
    again = pool.lease_ragged(400, 4, np.int32)
    assert again.values.shape == (512,)
    assert pool.stats()["outstanding"] == 2
    pool.release_batch({"v": again.values, "o": again.offsets})
    assert pool.stats()["outstanding"] == 0


def test_release_walks_view_base():
    pool = BufferPool()
    page = pool.lease((64,), np.int32)
    view = page[:10]
    assert pool.release(view) is True  # releases the base page
    assert pool.stats()["outstanding"] == 0
    # While the view lives, the sweep defers recycling.
    pool.sweep()
    assert pool.stats()["pending"] == 1
    del view, page
    pool.sweep()
    assert pool.stats()["free"] == 1


def test_ragged_keys_and_placement_convention():
    assert is_ragged_key("input_ids" + VALUES_SUFFIX)
    assert is_ragged_key("input_ids" + OFFSETS_SUFFIX)
    assert is_ragged_key(PACK_SLOT_KEY) and is_ragged_key(PACK_START_KEY)
    assert not is_ragged_key("input_ids")
    from lance_distributed_training_tpu.data.token_pack import (
        is_host_meta_key,
    )

    assert is_host_meta_key(PACK_META_KEY)
    assert not is_host_meta_key("_weight")


def test_placement_passes_host_meta_and_replicates_ragged():
    import jax

    from lance_distributed_training_tpu.data.placement import PlacementPlane
    from lance_distributed_training_tpu.parallel.mesh import (
        get_mesh,
        make_global_batch,
    )

    mesh = get_mesh(jax.devices())
    table, _ = _ragged_table([4, 9, 2, 5])
    batch = TokenDecoder(mode="pack", seq_len=32)(table)
    plane = PlacementPlane(mesh)
    placed = plane.place_batch(batch)
    assert isinstance(placed[PACK_META_KEY], np.ndarray)  # host passthrough
    values = placed["input_ids" + VALUES_SUFFIX]
    assert not isinstance(values, np.ndarray)  # device-resident
    assert np.array_equal(
        np.asarray(values), batch["input_ids" + VALUES_SUFFIX]
    )
    # make_global_batch (the reference function) agrees bit-for-bit.
    global_batch = make_global_batch(batch, mesh)
    for k in batch:
        assert np.array_equal(np.asarray(placed[k]),
                              np.asarray(global_batch[k])), k


# -- pipeline: determinism + resume ------------------------------------------


def _variable_dataset(tmp_path, rows=96, seed=0):
    return create_variable_length_token_dataset(
        str(tmp_path / f"toks{seed}"), rows=rows, vocab_size=100,
        max_len=48, mean_len=10.0, seed=seed,
    )


def _packed_pipeline(ds, start_step=0):
    dec = TokenDecoder(mode="pack", seq_len=48,
                       planner=TokenPackPlanner(
                           TokenPackConfig(pack_len=48, rows_multiple=2)))
    pipe = make_train_pipeline(ds, "batch", 16, 0, 1, dec)
    if start_step:
        pipe.load_state_dict({"step": start_step})
    return pipe


def test_packed_stream_bit_identical_and_resumable(tmp_path):
    ds = _variable_dataset(tmp_path)
    full = [_digest(b) for b in _packed_pipeline(ds)]
    assert len(full) >= 4
    again = [_digest(b) for b in _packed_pipeline(ds)]
    assert full == again
    # Resume mid-epoch: the tail replays bit-identically from the cursor.
    pipe = _packed_pipeline(ds)
    it = iter(pipe)
    head = [_digest(next(it)) for _ in range(2)]
    cursor = pipe.state_dict()
    it.close()
    assert cursor["step"] == 2
    tail = [_digest(b) for b in _packed_pipeline(ds, start_step=2)]
    assert head + tail == full


def test_packed_batches_cache_warm_hit_bit_identical(tmp_path):
    from lance_distributed_training_tpu.data.cache import BatchCache

    ds = _variable_dataset(tmp_path, seed=2)
    cache = BatchCache(cache_dir=str(tmp_path / "cache"),
                       ram_budget_mb=64, disk_budget_mb=64)
    try:
        dec = TokenDecoder(mode="pack", seq_len=48)
        cold = [
            _digest(b) for b in make_train_pipeline(
                ds, "batch", 16, 0, 1, dec, batch_cache=cache
            )
        ]
        warm = [
            _digest(b) for b in make_train_pipeline(
                ds, "batch", 16, 0, 1, dec, batch_cache=cache
            )
        ]
        assert cold == warm
    finally:
        cache.close()


# -- wire: v4 negotiation ---------------------------------------------------


def test_ragged_batch_wire_round_trip():
    from lance_distributed_training_tpu.service import protocol as P

    table, _ = _ragged_table([4, 9, 2])
    batch = TokenDecoder(mode="pack", seq_len=32)(table)
    payload = P.encode_batch(7, batch)
    step, out = P.decode_batch(payload)
    assert step == 7
    assert _digest(out) == _digest(batch)


def test_ragged_meta_validation_rejects_drift():
    import json

    from lance_distributed_training_tpu.service import protocol as P

    table, _ = _ragged_table([4, 9, 2])
    batch = TokenDecoder(mode="pack", seq_len=32)(table)
    payload = bytearray(P.encode_batch(7, batch))
    (meta_len,) = P._META_LEN.unpack_from(payload, 0)
    meta = json.loads(bytes(payload[4:4 + meta_len]))
    assert "ragged" in meta and "input_ids" in meta["ragged"]
    meta["ragged"]["input_ids"] = int(meta["ragged"]["input_ids"]) + 1
    tampered = json.dumps(meta).encode()
    # Re-frame with the tampered meta (pad to preserve framing lengths is
    # unnecessary: rebuild the payload from parts).
    body = bytes(payload[4 + meta_len:])
    new_payload = P._META_LEN.pack(len(tampered)) + tampered + body
    with pytest.raises(P.ProtocolError, match="capacity bucket"):
        P.decode_batch(new_payload)


def test_service_negotiates_packed_and_padded_streams(tmp_path):
    from lance_distributed_training_tpu.service.client import RemoteLoader
    from lance_distributed_training_tpu.service.server import (
        DataService,
        ServeConfig,
    )

    ds = _variable_dataset(tmp_path, seed=3)
    svc = DataService(ServeConfig(
        dataset_path=str(tmp_path / "toks3"), host="127.0.0.1", port=0,
        task_type="masked_lm", seq_len=48, token_pack=True,
        buffer_pool=False,
    )).start()
    try:
        addr = f"127.0.0.1:{svc.port}"
        packed = [
            _digest(b) for b in RemoteLoader(
                addr, 16, 0, 1, task_type="masked_lm", token_pack=True,
            )
        ]
        local_packed = [
            _digest(b) for b in make_train_pipeline(
                Dataset(str(tmp_path / "toks3")), "batch", 16, 0, 1,
                TokenDecoder(mode="pack", seq_len=48),
            )
        ]
        assert packed == local_packed
        # A client that does NOT request packing negotiates the padded
        # stream — bit-identical to a local padded pipeline (the v3-peer
        # compatibility contract; v3 peers cannot send token_pack at all).
        padded = [
            _digest(b) for b in RemoteLoader(
                addr, 16, 0, 1, task_type="masked_lm",
            )
        ]
        local_padded = [
            _digest(b) for b in make_train_pipeline(
                Dataset(str(tmp_path / "toks3")), "batch", 16, 0, 1,
                TokenDecoder(mode="pad", seq_len=48),
            )
        ]
        assert padded == local_padded
        assert packed != padded
    finally:
        svc.stop()


def test_packing_client_rejected_by_padded_server(tmp_path):
    from lance_distributed_training_tpu.service import protocol as P
    from lance_distributed_training_tpu.service.client import RemoteLoader
    from lance_distributed_training_tpu.service.server import (
        DataService,
        ServeConfig,
    )

    _variable_dataset(tmp_path, seed=4)
    svc = DataService(ServeConfig(
        dataset_path=str(tmp_path / "toks4"), host="127.0.0.1", port=0,
        task_type="masked_lm", seq_len=48, buffer_pool=False,
    )).start()
    try:
        loader = RemoteLoader(
            f"127.0.0.1:{svc.port}", 16, 0, 1, task_type="masked_lm",
            token_pack=True, connect_retries=1,
        )
        with pytest.raises(P.ProtocolError, match="token_pack"):
            list(loader)
    finally:
        svc.stop()


def test_seq_len_skew_rejected_at_connect(tmp_path):
    from lance_distributed_training_tpu.service import protocol as P
    from lance_distributed_training_tpu.service.client import RemoteLoader
    from lance_distributed_training_tpu.service.server import (
        DataService,
        ServeConfig,
    )

    _variable_dataset(tmp_path, seed=6)
    svc = DataService(ServeConfig(
        dataset_path=str(tmp_path / "toks6"), host="127.0.0.1", port=0,
        task_type="masked_lm", seq_len=48, buffer_pool=False,
    )).start()
    try:
        loader = RemoteLoader(
            f"127.0.0.1:{svc.port}", 16, 0, 1, task_type="masked_lm",
            seq_len=32, connect_retries=1,
        )
        with pytest.raises(P.ProtocolError, match="seq_len"):
            list(loader)
        # A matching declaration streams fine.
        ok = RemoteLoader(
            f"127.0.0.1:{svc.port}", 16, 0, 1, task_type="masked_lm",
            seq_len=48, connect_retries=1,
        )
        assert len(list(ok)) > 0
    finally:
        svc.stop()


def test_padded_arm_rejects_mismatched_siblings():
    rng = np.random.default_rng(0)
    table = pa.table({
        "input_ids": pa.array(
            [rng.integers(2, 50, 4, dtype=np.int32),
             rng.integers(2, 50, 7, dtype=np.int32)], pa.list_(pa.int32())
        ),
        "extra_feats": pa.array(
            [rng.integers(2, 50, 3, dtype=np.int32),
             rng.integers(2, 50, 9, dtype=np.int32)], pa.list_(pa.int32())
        ),
    })
    with pytest.raises(ValueError, match="different row lengths"):
        TokenDecoder(mode="pad", seq_len=16)(table)


def test_hello_carries_token_pack_and_gate_constant():
    from lance_distributed_training_tpu.service import protocol as P

    assert P.PROTOCOL_VERSION >= P.TOKEN_PACK_MIN_VERSION == 4
    h = P.hello(batch_size=8, process_index=0, process_count=1,
                token_pack=True)
    assert h["token_pack"] is True
    assert P.hello_malformed(dict(h, token_pack="yes")) is not None
    assert P.hello_malformed(h) is None


# -- autotune ----------------------------------------------------------------


def test_derive_window_pack_signals():
    from lance_distributed_training_tpu.tune.controller import derive_window

    w = derive_window({
        "pack_payload_tokens_total": 700.0,
        "pack_grid_tokens_total": 1000.0,
        "pack_new_shapes_total": 2.0,
    })
    assert w["pad_waste_pct"] == pytest.approx(30.0)
    assert w["pack_occupancy"] == pytest.approx(0.7)
    assert w["pack_new_shapes"] == 2.0
    assert "pad_waste_pct" not in derive_window({})


def test_policy_pack_rung_trades_waste_and_recompiles():
    from lance_distributed_training_tpu.tune.policy import HillClimbPolicy

    knobs = {"pack_rows_quantum": 8}
    bounds = {"pack_rows_quantum": (1, 64)}
    calm = {"steps": 10.0, "stall_pct": 10.0}
    # High waste, calm pipeline → tighten the quantum.
    policy = HillClimbPolicy()
    decisions = policy.decide(dict(calm, pad_waste_pct=55.0), knobs, bounds)
    assert decisions and decisions[0].knob == "pack_rows_quantum"
    assert decisions[0].target == 4
    assert decisions[0].reason == "pad_waste_bound"
    # Recompile churn → coarsen (takes priority over waste).
    policy = HillClimbPolicy()
    decisions = policy.decide(
        dict(calm, pad_waste_pct=55.0, pack_new_shapes=5.0), knobs, bounds
    )
    assert decisions[0].reason == "recompile_bound"
    assert decisions[0].target > 8
    # Stalled pipelines keep capacity priority: no pack move while the
    # loader starves.
    policy = HillClimbPolicy()
    decisions = policy.decide(
        {"steps": 10.0, "stall_pct": 80.0, "pad_waste_pct": 55.0},
        dict(knobs, prefetch=2), dict(bounds, prefetch=(1, 16)),
    )
    assert decisions and decisions[0].knob != "pack_rows_quantum"


# -- trainer config ----------------------------------------------------------


def test_trainer_rejects_bad_token_pack_combos(tmp_path):
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    with pytest.raises(ValueError, match="text task"):
        train(TrainConfig(dataset_path=str(tmp_path / "nope"),
                          task_type="classification", token_pack=True))
    with pytest.raises(ValueError, match="seq_parallelism"):
        train(TrainConfig(dataset_path=str(tmp_path / "nope"),
                          task_type="masked_lm", token_pack=True,
                          seq_parallelism=2))


def test_eval_decoder_is_always_padded():
    from lance_distributed_training_tpu.trainer import (
        TrainConfig,
        _decoder_for,
    )

    config = TrainConfig(dataset_path="unused", task_type="masked_lm",
                         token_pack=True, seq_len=48, buffer_pool=False)
    train_dec = _decoder_for(config)
    eval_dec = _decoder_for(config, for_eval=True)
    assert train_dec.mode == "pack"
    assert eval_dec.mode == "pad"


# -- authoring ---------------------------------------------------------------


def test_variable_corpus_deterministic_and_long_tailed(tmp_path):
    a = create_variable_length_token_dataset(
        str(tmp_path / "a"), rows=200, vocab_size=50, max_len=64,
        mean_len=12.0, seed=9,
    )
    b = create_variable_length_token_dataset(
        str(tmp_path / "b"), rows=200, vocab_size=50, max_len=64,
        mean_len=12.0, seed=9,
    )
    ta = a.take(np.arange(200))
    tb = b.take(np.arange(200))
    assert ta.equals(tb)
    col = ta.column("input_ids").combine_chunks()
    assert pa.types.is_list(col.type)
    lengths = np.diff(col.offsets.to_numpy(zero_copy_only=False))
    assert lengths.min() >= 1 and lengths.max() <= 64
    # Long tail: the mean sits far below the max.
    assert lengths.mean() < 25
