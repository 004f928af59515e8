"""Unified loader graph (data/graph.py, r16).

The contract under test: a ``LoaderGraph`` assembly is BIT-IDENTICAL to
the legacy engine it compiles to — same per-step digests, same resume
cursor — across every loader shape × plane combination (batch cache,
device decode, token pack), so the graph is the one composition layer
and the five engines are its compile targets, never parallel APIs.
"""

import io
import pathlib

import numpy as np
import pytest

from lance_distributed_training_tpu.data.cache import BatchCache
from lance_distributed_training_tpu.data.decode import (
    ImageClassificationDecoder,
)
from lance_distributed_training_tpu.data.folder import FolderDataPipeline
from lance_distributed_training_tpu.data.graph import (
    Buffers,
    Cache,
    Decode,
    EvalSource,
    FleetTransport,
    FolderSource,
    InProcess,
    LanceSource,
    LoaderGraph,
    MapStyleSource,
    Place,
    Pool,
    Prefetch,
    ServiceTransport,
    canonical_graphs,
)
from lance_distributed_training_tpu.data.pipeline import (
    DataPipeline,
    MapStylePipeline,
    make_eval_pipeline,
    make_train_pipeline,
)
from lance_distributed_training_tpu.data.samplers import make_plan
from lance_distributed_training_tpu.obs.registry import MetricsRegistry
from lance_distributed_training_tpu.utils.chaos import batch_digest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _decoder(pool=None):
    return ImageClassificationDecoder(image_size=32, buffer_pool=pool)


def _digests(loader):
    return [batch_digest(b) for b in loader]


def _cache(tmp_path, name="cache"):
    return BatchCache(cache_dir=str(tmp_path / name), ram_budget_mb=8,
                      disk_budget_mb=64, registry=MetricsRegistry())


def _consume(graph, k):
    """Pull k batches off a fresh iterator, return their digests + the
    graph-root cursor afterwards."""
    it = iter(graph)
    head = [batch_digest(next(it)) for _ in range(k)]
    cursor = graph.state_dict()
    close = getattr(it, "close", None)
    if close:
        close()
    return head, cursor


# -- topology validation -----------------------------------------------------


def test_graph_requires_exactly_one_source():
    with pytest.raises(ValueError, match="exactly one Source"):
        LoaderGraph(Decode(lambda t: t), InProcess())
    with pytest.raises(ValueError, match="duplicate 'source'"):
        LoaderGraph(MapStyleSource(None, 8, 0, 1),
                    FolderSource(None, 8, 0, 1), Decode(lambda t: t))


def test_graph_rejects_duplicate_kind_and_non_node():
    with pytest.raises(ValueError, match="duplicate 'prefetch'"):
        LoaderGraph(MapStyleSource(None, 8, 0, 1), Decode(lambda t: t),
                    Prefetch(2), Prefetch(4))
    with pytest.raises(TypeError, match="not a graph node"):
        LoaderGraph(MapStyleSource(None, 8, 0, 1), "prefetch=2")


def test_remote_transport_requires_lance_source():
    with pytest.raises(ValueError, match="must be a LanceSource"):
        LoaderGraph(MapStyleSource(None, 8, 0, 1),
                    ServiceTransport("h:1"))


def test_remote_transport_rejects_inprocess_decode_fn():
    with pytest.raises(ValueError, match="declaration-only"):
        LoaderGraph(LanceSource(None, "batch", 8, 0, 1),
                    Decode(lambda t: t), ServiceTransport("h:1"))


def test_remote_transport_rejects_cache_and_pool_payload(tmp_path):
    cache = _cache(tmp_path)
    try:
        with pytest.raises(ValueError, match="DataService owns"):
            LoaderGraph(LanceSource(None, "batch", 8, 0, 1),
                        Cache(cache), FleetTransport("h:1"))
    finally:
        cache.close()
    with pytest.raises(ValueError, match="DataService owns"):
        LoaderGraph(LanceSource(None, "batch", 8, 0, 1),
                    Pool(workers=object()), ServiceTransport("h:1"))
    # Empty seam nodes are fine: the topology documents where the planes
    # WOULD plug in even when the payload lives server-side.
    LoaderGraph(LanceSource(None, "batch", 8, 0, 1), Cache(), Pool(),
                ServiceTransport("h:1"))


def test_inprocess_requires_decode_fn():
    with pytest.raises(ValueError, match="Decode node with a decode_fn"):
        LoaderGraph(MapStyleSource(None, 8, 0, 1), InProcess())
    with pytest.raises(ValueError, match="Decode node with a decode_fn"):
        LoaderGraph(MapStyleSource(None, 8, 0, 1), Decode(image_size=32))


def test_eval_source_rejects_worker_pool():
    with pytest.raises(ValueError, match="drop the Pool node"):
        LoaderGraph(EvalSource(lambda idx: idx, 64, 8, 0, 1),
                    Decode(lambda t: t), Pool(workers=object()))


def test_spec_only_sources_cannot_compile():
    with pytest.raises(ValueError, match="spec-only LanceSource"):
        LoaderGraph(LanceSource(None, "batch", 8, 0, 1),
                    Decode(lambda t: t)).compile()
    with pytest.raises(ValueError, match="spec-only FolderSource"):
        LoaderGraph(FolderSource(None, 8, 0, 1),
                    Decode(lambda t: t)).compile()
    with pytest.raises(ValueError, match="spec-only EvalSource"):
        LoaderGraph(EvalSource(None, 64, 8, 0, 1),
                    Decode(lambda t: t)).compile()


def test_place_without_plane_fails_at_compile(image_dataset):
    graph = LoaderGraph(LanceSource(image_dataset, "batch", 16, 0, 1),
                        Decode(_decoder()), Place())
    with pytest.raises(ValueError, match="Place node has no plane"):
        graph.compile()


def test_full_sampler_refusal_matches_legacy(image_dataset):
    """The not-DP-aware refusal moved INTO LanceSource — same message,
    same construction-time surfacing via the factory."""
    graph = LoaderGraph(LanceSource(image_dataset, "full", 16, 1, 2),
                        Decode(_decoder()))
    with pytest.raises(ValueError, match="not DP-aware"):
        graph.compile()
    with pytest.raises(ValueError, match="not DP-aware"):
        make_train_pipeline(image_dataset, "full", 16, 1, 2, _decoder())


# -- cursor staging (state_dict never compiles) ------------------------------


def test_cursor_reads_never_compile():
    """state_dict/load_state_dict before compile() must not dial sockets
    or open datasets — cursor serialization is a pure read (this is what
    keeps LoaderGraph.state_dict inside LDT1301's content-path purity)."""
    graph = LoaderGraph(
        LanceSource(None, "batch", 16, 0, 1, dataset_fingerprint="fp"),
        Decode(image_size=32),
        ServiceTransport("127.0.0.1:9", connect_retries=1, backoff_s=0.01),
    )
    assert graph.state_dict() == {"step": 0}
    graph.load_state_dict({"step": 3})
    assert graph.state_dict() == {"step": 3}
    assert graph._runtime is None  # nothing compiled, nothing dialed
    with pytest.raises(ValueError, match="negative resume cursor"):
        graph.load_state_dict({"step": -1})


def test_staged_cursor_applied_at_compile(image_dataset):
    def mk():
        return LoaderGraph(LanceSource(image_dataset, "batch", 16, 0, 1),
                           Decode(_decoder()), InProcess())

    full = _digests(mk())
    assert len(full) >= 4
    resumed = mk()
    resumed.load_state_dict({"step": 2})  # staged: not compiled yet
    assert _digests(resumed) == full[2:]
    assert resumed.state_dict() == {"step": len(full)}


# -- describe / cursor ownership ---------------------------------------------


def test_canonical_graphs_describe_without_compiling():
    graphs = canonical_graphs()
    assert set(graphs) == {"train-iterable", "train-map-style",
                           "train-folder", "service", "fleet"}
    owners = {}
    for name, g in graphs.items():
        desc = g.describe()
        assert g._runtime is None  # describe() never compiles
        assert [d["kind"] for d in desc["nodes"]][0] == "source"
        owners[name] = desc["cursor_owner"]
        assert sum(d["cursor"] for d in desc["nodes"]) == 1
    assert owners == {
        "train-iterable": "Place",          # placement plane owns consumed
        "train-map-style": "MapStyleSource",
        "train-folder": "FolderSource",
        "service": "ServiceTransport",
        "fleet": "FleetTransport",
    }
    fleet = graphs["fleet"].describe()
    assert "FleetTransport" in fleet["tunable_nodes"]


# -- parity matrix: in-process shapes ----------------------------------------


@pytest.mark.parametrize("cache_on", [False, True])
def test_parity_lance_iterable(image_dataset, tmp_path, cache_on):
    """Explicit graph vs the raw engine (make_plan + DataPipeline): same
    digests, and the resume tail round-trips across both paths."""
    cache = _cache(tmp_path) if cache_on else None

    def graph(resume=0):
        g = LoaderGraph(LanceSource(image_dataset, "batch", 16, 0, 1),
                        Decode(_decoder()), Cache(cache), InProcess())
        if resume:
            g.load_state_dict({"step": resume})
        return g

    try:
        plan = make_plan("batch", image_dataset.fragment_rows(), 16, 0, 1,
                         shuffle=False, seed=0, epoch=0)
        legacy = DataPipeline(image_dataset, plan, _decoder(), 2)
        full = _digests(legacy)
        assert len(full) >= 4
        assert _digests(graph()) == full
        if cache_on:
            assert _digests(graph()) == full  # warm epoch: pure hits
        head, cursor = _consume(graph(), 2)
        assert head == full[:2] and cursor == {"step": 2}
        assert _digests(graph(resume=2)) == full[2:]
        legacy_resumed = DataPipeline(image_dataset, plan, _decoder(), 2)
        legacy_resumed.load_state_dict(cursor)
        assert _digests(legacy_resumed) == full[2:]
    finally:
        if cache:
            cache.close()


@pytest.mark.parametrize("cache_on", [False, True])
def test_parity_map_style(image_dataset, tmp_path, cache_on):
    cache = _cache(tmp_path) if cache_on else None

    def graph(resume=0):
        g = LoaderGraph(
            MapStyleSource(image_dataset, 16, 0, 1, seed=7),
            Decode(_decoder(), columns=["image", "label"]),
            Cache(cache), InProcess(),
        )
        if resume:
            g.load_state_dict({"step": resume})
        return g

    try:
        legacy = MapStylePipeline(image_dataset, 16, 0, 1, _decoder(),
                                  seed=7,
                                  columns=["image", "label"],
                                  batch_cache=cache)
        full = _digests(legacy)
        assert len(full) >= 4
        assert _digests(graph()) == full
        head, cursor = _consume(graph(), 2)
        assert head == full[:2] and cursor["step"] == 2
        assert _digests(graph(resume=2)) == full[2:]
        # set_epoch reshuffles identically through both paths
        reshuffled = MapStylePipeline(image_dataset, 16, 0, 1, _decoder(),
                                      seed=7,
                                      columns=["image", "label"])
        reshuffled.set_epoch(3)
        g2 = graph()
        g2.set_epoch(3)
        assert _digests(g2) == _digests(reshuffled) != full
    finally:
        if cache:
            cache.close()


@pytest.fixture()
def image_folder(tmp_path):
    """root/<class>/<img>.jpg tree, 3 classes x 10 images."""
    from PIL import Image

    rng = np.random.default_rng(0)
    root = tmp_path / "folder"
    for cls in ["apple", "banana", "cherry"]:
        d = root / cls
        d.mkdir(parents=True)
        for i in range(10):
            arr = (rng.random((48, 48, 3)) * 255).astype(np.uint8)
            Image.fromarray(arr).save(d / f"{i}.jpg", quality=90)
    return str(root)


@pytest.mark.parametrize("cache_on", [False, True])
def test_parity_folder(image_folder, tmp_path, cache_on):
    cache = _cache(tmp_path) if cache_on else None

    def graph(resume=0):
        g = LoaderGraph(FolderSource(image_folder, 10, 0, 1, seed=3),
                        Decode(_decoder()), Cache(cache), InProcess())
        if resume:
            g.load_state_dict({"step": resume})
        return g

    try:
        legacy = FolderDataPipeline(image_folder, 10, 0, 1, _decoder(),
                                    seed=3, batch_cache=cache)
        full = _digests(legacy)
        assert len(full) == 3
        assert _digests(graph()) == full
        assert graph().num_classes == 3  # engine surface delegates
        head, cursor = _consume(graph(), 1)
        assert head == full[:1] and cursor["step"] == 1
        assert _digests(graph(resume=1)) == full[1:]
    finally:
        if cache:
            cache.close()


@pytest.mark.parametrize("cache_on", [False, True])
def test_parity_eval(image_dataset, tmp_path, cache_on):
    """EvalSource composition vs the legacy factory: padded-tail plan,
    _weight channel, and the eval=1 cache scope all match."""
    cache = _cache(tmp_path) if cache_on else None
    fp = image_dataset.fingerprint()

    def read(idx):
        return image_dataset.take(idx, columns=["image", "label"])

    def graph():
        return LoaderGraph(
            EvalSource(read, image_dataset.count_rows(), 32, 0, 1),
            Decode(_decoder()),
            Cache(cache, dataset_fingerprint=fp),
        )

    try:
        legacy = make_eval_pipeline(read, image_dataset.count_rows(), 32,
                                    0, 1, _decoder(), batch_cache=cache,
                                    dataset_fingerprint=fp)
        full = _digests(legacy)
        assert len(full) == len(graph())
        assert _digests(graph()) == full
        if cache_on:
            assert _digests(graph()) == full
    finally:
        if cache:
            cache.close()


# -- parity matrix: modality planes ------------------------------------------


@pytest.mark.parametrize("cache_on", [False, True])
def test_parity_device_decode(image_dataset, tmp_path, cache_on):
    """device_decode plane through the graph path: coefficient pages stay
    bit-identical to the legacy engine, warm epochs included."""
    from lance_distributed_training_tpu.native import native_available

    if not native_available():
        pytest.skip("native coefficient extractor unavailable")
    from lance_distributed_training_tpu.data.device_decode import (
        CoeffImageDecoder,
    )

    cache = _cache(tmp_path) if cache_on else None

    def dec():
        return CoeffImageDecoder(image_size=32)

    def graph():
        return LoaderGraph(LanceSource(image_dataset, "batch", 16, 0, 1),
                           Decode(dec()), Cache(cache), InProcess())

    try:
        plan = make_plan("batch", image_dataset.fragment_rows(), 16, 0, 1,
                         shuffle=False, seed=0, epoch=0)
        full = _digests(DataPipeline(image_dataset, plan, dec(), 2))
        assert _digests(graph()) == full
        if cache_on:
            assert _digests(graph()) == full
    finally:
        if cache:
            cache.close()


@pytest.mark.parametrize("cache_on", [False, True])
def test_parity_token_pack(tmp_path, cache_on):
    """token_pack plane through the graph path: deterministic FFD packing
    digests match the legacy engine, resume included."""
    from lance_distributed_training_tpu.data.authoring import (
        create_variable_length_token_dataset,
    )
    from lance_distributed_training_tpu.data.token_pack import (
        TokenDecoder,
        TokenPackConfig,
        TokenPackPlanner,
    )

    ds = create_variable_length_token_dataset(
        str(tmp_path / "toks"), rows=96, vocab_size=100, max_len=48,
        mean_len=10.0, seed=0,
    )
    cache = _cache(tmp_path) if cache_on else None

    def dec():
        return TokenDecoder(mode="pack", seq_len=48,
                            planner=TokenPackPlanner(
                                TokenPackConfig(pack_len=48,
                                                rows_multiple=2)))

    def graph(resume=0):
        g = LoaderGraph(LanceSource(ds, "batch", 16, 0, 1), Decode(dec()),
                        Cache(cache), InProcess())
        if resume:
            g.load_state_dict({"step": resume})
        return g

    try:
        plan = make_plan("batch", ds.fragment_rows(), 16, 0, 1,
                         shuffle=False, seed=0, epoch=0)
        full = _digests(DataPipeline(ds, plan, dec(), 2))
        assert len(full) >= 4
        assert _digests(graph()) == full
        assert _digests(graph(resume=2)) == full[2:]
        if cache_on:
            assert _digests(graph()) == full
    finally:
        if cache:
            cache.close()


# -- parity matrix: remote transports ----------------------------------------


def test_parity_service_transport(image_dataset, tmp_path):
    """ServiceTransport graph vs legacy RemoteLoader: same stream, same
    resume tail, server-side cache inherited by both paths."""
    from lance_distributed_training_tpu.service import (
        DataService,
        RemoteLoader,
        ServeConfig,
    )

    svc = DataService(ServeConfig(
        dataset_path=image_dataset.uri, host="127.0.0.1", port=0,
        image_size=32, batch_cache=True,
        cache_dir=str(tmp_path / "svc-cache"),
    )).start()
    try:
        addr = f"127.0.0.1:{svc.port}"
        fp = image_dataset.fingerprint()

        def legacy():
            return RemoteLoader(addr, 16, 0, 1, image_size=32,
                                dataset_fingerprint=fp,
                                connect_retries=2, backoff_s=0.01)

        def graph(resume=0):
            g = LoaderGraph(
                LanceSource(None, "batch", 16, 0, 1,
                            dataset_fingerprint=fp),
                Decode(image_size=32),
                ServiceTransport(addr, connect_retries=2, backoff_s=0.01),
            )
            if resume:
                g.load_state_dict({"step": resume})
            return g

        full = _digests(legacy())
        assert len(full) >= 4
        assert _digests(graph()) == full  # second epoch: cache hits too
        head, cursor = _consume(graph(), 2)
        assert head == full[:2] and cursor["step"] == 2
        assert _digests(graph(resume=2)) == full[2:]
        resumed = legacy()
        resumed.load_state_dict(cursor)
        assert _digests(resumed) == full[2:]
    finally:
        svc.stop()


def test_parity_fleet_transport(image_dataset, tmp_path):
    from lance_distributed_training_tpu.fleet.balancer import FleetLoader
    from lance_distributed_training_tpu.fleet.coordinator import (
        Coordinator,
        CoordinatorConfig,
    )
    from lance_distributed_training_tpu.service import (
        DataService,
        ServeConfig,
    )

    coord = Coordinator(CoordinatorConfig(
        host="127.0.0.1", port=0,
        heartbeat_interval_s=0.1, lease_ttl_s=0.6,
    )).start()
    servers = []
    try:
        for i in range(2):
            svc = DataService(ServeConfig(
                dataset_path=image_dataset.uri, host="127.0.0.1", port=0,
                image_size=32, queue_depth=2,
                coordinator_addr=f"127.0.0.1:{coord.port}",
            )).start()
            assert svc.fleet_agent.registered.wait(5)
            servers.append(svc)
        addr = f"127.0.0.1:{coord.port}"
        fp = image_dataset.fingerprint()
        opts = dict(connect_retries=2, resolve_retries=3, backoff_s=0.05)

        legacy = FleetLoader(addr, 16, 0, 1, image_size=32,
                             dataset_fingerprint=fp, **opts)
        full = _digests(legacy)
        assert len(full) >= 4
        graph = LoaderGraph(
            LanceSource(None, "batch", 16, 0, 1, dataset_fingerprint=fp),
            Decode(image_size=32),
            FleetTransport(addr, **opts),
        )
        assert _digests(graph) == full
        assert graph.state_dict()["step"] == len(full)
    finally:
        for s in servers:
            s.stop()
        coord.stop()


# -- factory surface (the legacy entry points stay graph-backed) -------------


def test_factories_return_graphs_with_unchanged_contract(image_dataset):
    pipe = make_train_pipeline(image_dataset, "batch", 16, 0, 1,
                               _decoder())
    assert isinstance(pipe, LoaderGraph)
    assert pipe.state_dict() == {"step": 0}
    assert [t.name for t in pipe.tunables()] == ["prefetch"]
    assert pipe.set_prefetch(3) == 3
    assert len(pipe) == image_dataset.count_rows() // 16
    assert pipe.cursor_owner() == "LanceSource"
    # engine-only surface falls through (num_classes is covered by the
    # folder parity test); unknown names still raise AttributeError
    with pytest.raises(AttributeError):
        pipe.not_a_loader_attribute


def test_engine_surface_reaches_through_place_wrap(image_folder):
    """The trainer's folder arm reads loader.num_classes AFTER the Place
    node wraps the engine in a PlacedLoader — the graph must fall back to
    the engine beneath the wrap for engine-only surface."""
    from lance_distributed_training_tpu.obs.registry import MetricsRegistry
    from lance_distributed_training_tpu.parallel.mesh import get_mesh
    from lance_distributed_training_tpu.data.placement import (
        PlacementPlane,
    )

    plane = PlacementPlane(get_mesh(), registry=MetricsRegistry())
    graph = LoaderGraph(FolderSource(image_folder, 10, 0, 1),
                        Decode(_decoder()), Place(plane))
    assert graph.num_classes == 3  # through the PlacedLoader wrap
    assert graph.cursor_owner() == "Place"
    # the Place-owned cursor contract itself stays on the wrapper
    assert graph.state_dict()["step"] == 0
    with pytest.raises(AttributeError):
        graph.not_a_loader_attribute


# -- ldt graph --loader ------------------------------------------------------


def test_graph_loader_text_smoke():
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(["--root", str(REPO_ROOT), "--loader"], out=out)
    assert rc == 0
    text = out.getvalue()
    assert "loader graph model (data/graph.py): 5 canonical shapes" in text
    for shape in ("train-iterable", "train-map-style", "train-folder",
                  "service", "fleet"):
        assert f"loader {shape}:" in text
    assert "[cursor owner" in text
    assert "tunables: stripe_width" in text
    assert "server-side" in text  # remote Decode is declaration-only


def test_graph_loader_dot_smoke():
    from lance_distributed_training_tpu.analysis import graph_main

    out = io.StringIO()
    rc = graph_main(["--root", str(REPO_ROOT), "--loader", "--dot"],
                    out=out)
    assert rc == 0
    dot = out.getvalue()
    assert dot.count("{") == dot.count("}")
    assert 'subgraph "cluster_loader_train_iterable"' in dot
    assert 'subgraph "cluster_loader_fleet"' in dot
    assert "peripheries=2" in dot  # cursor owners are double-boxed
