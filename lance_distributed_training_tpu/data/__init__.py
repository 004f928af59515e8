"""Data subsystem: columnar storage, sampler plans, decode, input pipeline."""

from .format import Dataset, Fragment, write_dataset  # noqa: F401
from .samplers import (  # noqa: F401
    ReadRange,
    full_scan_plan,
    sharded_batch_plan,
    sharded_fragment_plan,
    distributed_indices,
    assert_equal_step_counts,
    make_plan,
)
from .decode import (  # noqa: F401
    ImageClassificationDecoder,
    ImageTextDecoder,
    decode_tensor_image,
    numeric_decoder,
)
from .pipeline import (  # noqa: F401
    DataPipeline,
    MapStylePipeline,
    make_eval_pipeline,
    make_train_pipeline,
    make_map_style_pipeline,
)
from .authoring import (  # noqa: F401
    create_dataset_from_image_folder,
    create_food101_datasets,
    create_synthetic_classification_dataset,
    create_synthetic_image_folder,
    create_synthetic_image_text_dataset,
    create_text_token_dataset,
    ingest_on_process_zero,
)
from .cache import (  # noqa: F401
    BatchCache,
    DeviceReplayCache,
    PlanCache,
    decode_fingerprint,
    folder_fingerprint,
    item_fingerprint,
    plan_fingerprint,
)
from .filters import parse_predicate, predicate_mask  # noqa: F401
from .folder import FolderDataPipeline  # noqa: F401
from .graph import (  # noqa: F401
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
)
from .placement import PlacedLoader, PlacementPlane  # noqa: F401
from .workers import WorkerPool, columnar_spec, folder_spec  # noqa: F401
