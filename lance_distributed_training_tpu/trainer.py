"""One mesh-aware trainer — replaces the reference's four driver scripts.

The reference duplicates a near-identical DDP loop across
``lance_iterable.py:74-132``, ``lance_map_style.py:46-126``,
``torch_version/iter_style.py:80-145`` and ``torch_version/map_style.py:85-149``
(SURVEY.md §1: "four parallel driver scripts, not one framework entry
point"). Here there is ONE ``train()`` with a pluggable input pipeline
(loader style × sampler × data format are config, not scripts) and a
pluggable :class:`~.models.tasks.Task` (classification / masked-LM /
contrastive).

TPU-native loop design vs. the reference hot loop (SURVEY.md §3.4):

* gradient sync: no DDP wrapper — the step is jitted with a replicated state
  sharding and a ``P('data')`` batch sharding; XLA inserts the gradient
  all-reduce (psum) over ICI,
* input prep (normalize/augment/MLM-masking) runs on device fused into the
  step (:mod:`.ops.image`, :mod:`.models.tasks`), not per-row on host,
* no per-step ``loss.item()`` D2H sync (``lance_iterable.py:115``): the loss
  stays on device in a running accumulator and is fetched once per epoch,
* loader-stall is measured explicitly (BASELINE metric) by timing
  ``next(loader)`` against the device step.
"""

from __future__ import annotations

import dataclasses
import os
import statistics
import threading
import time
from collections import deque
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state

from .data.format import Dataset
from .models.tasks import Task, get_task
from .obs.registry import default_registry
from .obs.spans import default_tracer, end_phase, watch_xla_compiles
from .obs.spans import phase as obs_phase
from .obs.spans import span as obs_span
from .ops.flash import splash_tilings_built
from .parallel.mesh import (
    batch_sharding,
    get_mesh,
    maybe_initialize_distributed,
    process_topology,
    replicated_sharding,
)
from .utils import compiletrack
from .utils.metrics import MetricLogger, StepTimer

__all__ = [
    "TrainConfig",
    "TrainState",
    "train",
    "create_train_state",
    "create_sharded_train_state",
    "make_optimizer",
    "lr_schedule_fn",
    "make_train_step",
    "make_eval_step",
    "evaluate",
]


class TrainState(train_state.TrainState):
    """TrainState + the model's non-trainable collection that a training
    step updates: batch-norm statistics, or the selection bias of sigmoid
    routers (None for stateless models)."""

    batch_stats: Any = None


@dataclasses.dataclass
class TrainConfig:
    """Flag-for-flag parity with the reference CLI
    (``/root/reference/lance_iterable.py:136-146``) plus TPU/task knobs."""

    dataset_path: str
    val_dataset_path: Optional[str] = None  # held-out split for eval_every /
    # eval_at_end (the reference's Food101 split='test' val loader,
    # torch_version/map_style.py:57); default: eval over the train loader
    val_fraction: float = 0.0  # >0: carve a seeded held-out fraction of the
    # train dataset as the val split (torch random_split equivalent;
    # torch_version/map_style.py:57's train/val separation without a second
    # dataset). Map-style columnar path; composes with --filter (the split
    # happens inside the filtered pool). Mutually exclusive with
    # val_dataset_path.
    task_type: str = "classification"
    num_classes: int = 101
    sampler_type: str = "batch"  # batch | fragment | full (lance_iterable.py:61-69)
    loader_style: str = "iterable"  # iterable | map  (the two reference paths)
    filter: Optional[str] = None  # row predicate ("label < 50"), resolved to
    # an index pool once; map-style columnar path only (see data/filters.py)
    data_format: str = "columnar"  # columnar | folder (the torch_version/ control arm)
    batch_size: int = 512  # GLOBAL batch (reference default, lance_iterable.py:141)
    epochs: int = 10
    max_steps: int = 0  # >0: stop after N train (micro) steps regardless of
    # epochs — compile checks, smoke runs, fixed-step benchmarking. Counted
    # like total_steps/warmup_steps in data steps: under grad_accum an
    # optimizer update lands every grad_accum of these.
    lr: float = 0.05
    momentum: float = 0.9
    # -- optimizer/schedule knobs beyond the reference's fixed-lr SGD
    # (lance_iterable.py:98) --
    optimizer: str = "sgd"  # sgd | adamw
    weight_decay: float = 0.0
    lr_schedule: str = "constant"  # constant | cosine (optional linear warmup)
    warmup_steps: int = 0
    total_steps: Optional[int] = None  # schedule horizon; None = derived from
    # dataset size × epochs at train() time
    grad_clip: float = 0.0  # >0: clip gradients by global norm
    grad_accum: int = 1  # >1: accumulate N micro-steps per optimizer update
    num_workers: int = 0  # >0: decode in N worker processes (get_safe_loader parity)
    shm_workers: bool = True  # worker-pool batches cross the IPC boundary
    # through shared-memory ring slots (data/buffers.py) instead of being
    # pickled — descriptor-only returns, one copy out of the mapped pages.
    # False = legacy pickle transport (the A/B control arm; also the
    # automatic fallback where POSIX shm is unavailable).
    buffer_pool: bool = True  # recycle decode / wire-receive pages through
    # the process BufferPool: decode writes into warm leased pages and the
    # loader returns them after device_put dispatch (bufpool_* metrics on
    # /metrics). False = fault a fresh allocation per batch (pre-r6).
    device_decode: bool = False  # split the JPEG hot loop at the entropy
    # boundary: the host does only the sequential Huffman/entropy decode
    # and ships half-decoded coefficient pages (data/device_decode.py)
    # through the placement ring; dequant + 8x8 IDCT + chroma upsample +
    # YCbCr->RGB + resize run as a pure jitted device kernel
    # (ops/jpeg_device.py, integer-exact, bit-deterministic) applied as a
    # timed transform stage ahead of the train step, where XLA overlaps it
    # with the step like any other device work. Classification only;
    # raises when the native coefficient extractor is unavailable. False
    # (--no_device_decode) = the exact r11 host decode path, the A/B
    # control arm.
    token_pack: bool = False  # ragged token plane (text tasks,
    # data/token_pack.py + ops/token_device.py): variable-length sequences
    # ride pool/wire/cache as values+offsets pages with a deterministic
    # FFD pack plan, and one pure jitted kernel scatters them into packed
    # (rows, pack_len) slabs with segment/position ids ahead of the step —
    # the padding the fixed-shape path burns on every short sequence
    # becomes a measured quantity (pad_waste_pct on /metrics) the
    # autotuner can trade against recompile count. masked_lm/causal_lm
    # pack multiple sequences per row (segment-masked attention,
    # per-segment positions); contrastive buckets one caption per slot so
    # row i stays paired with image i. Eval always streams the padded arm
    # (per-sequence metrics need row alignment). False (--no_token_pack) =
    # the exact r14 padded control arm.
    pack_len: int = 0  # packed slot-length cap; 0 = seq_len. A bounded
    # Tunable (with pack_rows_multiple) when the autotuner is on.
    pack_rows_multiple: int = 8  # packed row-count rounding quantum:
    # smaller = less padding waste, more distinct compiled shapes
    data_service_addr: Optional[str] = None  # host:port of a running
    # `ldt serve-data` DataService: decode runs on that host's fleet and this
    # process streams plan-ordered device-ready batches (RemoteLoader) —
    # identical batches to local training on the same seed. Iterable columnar
    # path only; decode knobs (task_type/image_size) must match server-side.
    coordinator_addr: Optional[str] = None  # host:port of a running
    # `ldt coordinator`: like data_service_addr, but the FleetLoader
    # resolves N data servers from the coordinator, stripes this shard's
    # plan across them, and fails over (re-stripe at the resume cursor) on
    # server loss — same bit-identical batch contract, elastic capacity.
    # Mutually exclusive with data_service_addr; NOT the jax multi-host
    # rendezvous (that is coordinator_address, below).
    job_id: Optional[str] = None  # v6 job plane: this run's tenancy on a
    # shared DataService/fleet — per-job resume cursor, fairness weight and
    # admission on the server side. None = the implicit "default" job
    # (downgrade-safe against pre-v6 servers; an explicit id refuses them).
    job_priority: Optional[str] = None  # priority class for job_id
    # ("inference" | "training" | "bulk"); None = server default (training).
    no_ddp: bool = False  # single-device escape hatch (lance_iterable.py:145)
    no_wandb: bool = False  # lance_iterable.py:146
    model_name: Optional[str] = None  # default per task (resnet50 / bert_base / clip)
    pretrained: Optional[str] = None  # path to a torch.save'd torchvision
    # ResNet state_dict: backbone weights + BN stats import into the Flax
    # model (models/pretrained.py); the head stays fresh unless its shape
    # matches — the reference's transfer-learning task shape
    # (modelling/classification.py:6-10). Classification/ResNet only.
    image_size: int = 224
    seq_len: int = 128  # masked_lm / contrastive text length
    vocab_size: Optional[int] = None  # None = the model's own default
    num_layers: int = 0  # >0: this many layers of a transformer preset in
    # place of its own depth (one chip's share of a published model, at
    # every published width); 0 keeps the preset's
    expert_share: Optional[str] = None  # "rank/ranks": the experts of each
    # dropless layer that this rank of an expert-parallel job holds (the
    # router stays whole); with vocab_size as the vocabulary's slice, the
    # share of a stated deployment that this chip runs. None: all of them
    layer_span: Optional[str] = None  # "first:end": the published layers
    # [first, end) that this pipeline stage holds of a preset whose layers
    # differ by kind; None: all of them
    prefetch: int = 2
    producer_threads: int = 4  # decode-producer threads
    placement_depth: int = 2  # device-resident batches the placement ring
    # (data/placement.py: one thread slices each host batch per local
    # device and dispatches async H2D, so next(loader) returns an
    # already-transferred array) keeps ahead of the step; 2 double-buffers
    # (one consumed, one in flight), more pins extra HBM for little added
    # overlap
    autotune: bool = True  # closed-loop pipeline autotuning (tune/): a
    # background controller snapshots windowed obs/ deltas each interval,
    # attributes the bottleneck, and actuates live knobs — decode worker
    # count, prefetch depth, buffer-pool budget, placement ring depth,
    # fleet stripe width — within their declared bounds. Capacity only:
    # the batch stream stays bit-identical in value and order through any
    # decision. False (--no_autotune) = the exact fixed-knob pipeline of
    # r8 and earlier (no controller thread, no Tunable ever constructed).
    autotune_interval_s: float = 1.0  # controller tick period; decisions
    # additionally sit out a policy cooldown between actuations
    data_echo: int = 1  # >1: run N train steps per host batch ("data
    # echoing", Choi et al. 2019) — each echo re-draws the on-device
    # augmentation / MLM masking rng, so echoes are not exact repeats. When
    # the host pipeline (decode / H2D) is the bottleneck, throughput scales
    # ~N× at a modest statistical cost; when the device is the bottleneck it
    # changes nothing. Composes with device_cache (echo shapes epoch 0; the
    # cache stores each batch once).
    device_cache: bool = False  # HBM-resident dataset: keep epoch-0 batches
    # on device and replay them in later epochs — no host decode, no H2D.
    # Correct for every task here because augmentation / MLM masking run ON
    # DEVICE inside the jitted step (fresh randomness each epoch); the cache
    # holds raw uint8/token batches. Epoch shuffle degrades to batch-order
    # permutation (membership frozen at epoch 0).
    device_cache_gb: float = 8.0  # projected-size guard: fall back to the
    # streaming path (with a warning) when the dataset won't fit
    batch_cache: bool = False  # epoch-coherent decoded-batch cache
    # (data/cache.py): a tiered RAM/disk plane consulted at every local
    # loader's decode boundary — epoch >= 2 (and a restarted run, via the
    # disk tier) streams byte-identical cached batches instead of
    # re-reading fragments and re-running decode. Content-keyed (dataset
    # fingerprint + decode config + plan item), so the stream is
    # bit-identical to the uncached run by construction. Host tier of the
    # same idea device_cache implements in HBM; the two compose (the
    # batch cache feeds the fill epoch). False (--no_batch_cache) = the
    # exact r12 path: no probe, no spill dir, nothing.
    cache_ram_budget_mb: int = 512  # RAM ring budget (BufferPool-leased
    # pages; LRU eviction spills to disk, then releases the leases) — a
    # bounded Tunable the autotuner can actuate
    cache_disk_budget_mb: int = 2048  # local-disk spill budget (atomic,
    # sha256-verified segment files; oldest evicted over budget) — Tunable
    cache_dir: Optional[str] = None  # spill directory; default
    # ~/.cache/<pkg>/batch-cache (stable across restarts on purpose:
    # that is what makes a resumed job's first epoch decode-free)
    compile_cache: bool = True  # persistent XLA compile cache on accelerator
    # backends, at JAX_COMPILATION_CACHE_DIR when set, else
    # <checkout>/.jax_cache — see maybe_enable_compile_cache.
    shuffle: bool = False  # iterable path: epoch batch-order reshuffle
    # (beyond the reference — Lance samplers replay the same order every
    # epoch; map-style shuffles regardless, as DistributedSampler does)
    augment: bool = True
    eval_at_end: bool = True  # rank-0 eval over train loader (lance_iterable.py:125-127)
    eval_every: int = 0  # map-style: val every N epochs (lance_map_style.py:109-112)
    seed: int = 0
    run_name: Optional[str] = None
    metrics_port: Optional[int] = None  # same contract as ServeConfig:
    # None = exporter off, 0 = ephemeral (bound port in the progress log),
    # >0 fixed. Process 0 serves /metrics (Prometheus text: trainer_*
    # step/loader histograms, svc_* RemoteLoader counters, lineage_*
    # per-batch latency attribution) and /healthz for the run's lifetime.
    metrics_host: str = "127.0.0.1"  # exporter bind address; non-loopback
    # is an explicit opt-in (unauthenticated endpoint)
    log_every: int = 50
    log_grad_norm: bool = False  # per-step micro-batch global gradient norm
    # in the progress lines (divergence telemetry; a few fused reductions;
    # under grad_accum the optimizer clips the accumulated mean, not this)
    # -- parallelism beyond the reference's DP-only scope (SURVEY.md §2.3) --
    model_parallelism: int = 1  # tensor-parallel degree ('model' mesh axis)
    seq_parallelism: int = 1  # context-parallel degree ('seq' axis, ring attn)
    remat: bool = False  # rematerialize transformer blocks (long-context)
    flash_attention: bool = False  # force the Pallas attention kernel (on a
    # TPU the kernel or an error, dense elsewhere); without it get_task
    # binds the kernel where shapes and mesh allow (ops/flash.py)
    num_experts: int = 0  # >0: switch-MoE transformer blocks (expert parallel)
    moe_every: int = 2  # MoE on every Nth block
    pipeline_parallelism: int = 1  # GPipe stages over a 'pipe' mesh axis
    pp_microbatches: int = 4  # microbatches per pipeline round
    fsdp: bool = False  # ZeRO-3-style: fully shard params + optimizer state
    # over the 'data' axis; XLA inserts the per-layer all-gathers
    zero_opt: int = 0  # ZeRO gradient/optimizer sharding over the 'data'
    # axis, params replicated. 1 (or legacy True): shard the optimizer
    # MOMENTS only — the SPMD partitioner reduce-scatters gradients into
    # each replica's opt-state shard and all-gathers just the updated
    # params, so optimizer memory scales 1/N with the mesh at no per-layer
    # forward/backward gathers. 2: ZeRO-2 — additionally shard the
    # gradient-accumulation buffer (optax.MultiSteps acc_grads, the
    # persistent gradient state under --grad_accum) and constrain the
    # step's gradients to the same layout (parallel/sharding.py
    # grad_partition_specs), so the backward's gradient never materialises
    # fully replicated. Value-preserving re-layouts both — the loss
    # trajectory matches the unsharded run (pinned by a slow parity
    # test). Mutually exclusive with fsdp (which already shards both).
    # -- aux subsystems the reference lacks (SURVEY.md §5) --
    checkpoint_dir: Optional[str] = None  # orbax save/restore root
    checkpoint_every: int = 1  # save every N epochs
    checkpoint_every_steps: int = 0  # >0: ALSO save every N data steps —
    # step-granular, crash-consistent checkpoints carrying the data-plane
    # cursor (loader state_dict + host rng + counters), so a SIGKILLed run
    # restarts mid-epoch at the exact next batch with a bit-identical
    # stream. Counted in absolute data steps across restarts; with
    # data_echo > 1 saves land at host-batch boundaries. Epoch-boundary
    # saves (checkpoint_every) continue independently.
    resume: bool = True  # restore the latest checkpoint if one exists
    profile_dir: Optional[str] = None  # jax.profiler trace of early steps
    # -- multi-host rendezvous (torchrun MASTER_ADDR/RANK/WORLD_SIZE parity) --
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None


def _task_from_config(config: TrainConfig, mesh=None) -> Task:
    attention_fn = None
    if config.flash_attention and (
        config.seq_parallelism > 1 or config.pipeline_parallelism > 1
    ):
        raise ValueError(
            "flash_attention cannot combine with seq_parallelism or "
            "pipeline_parallelism (they select their own attention path)"
        )
    if config.seq_parallelism > 1:
        if config.task_type != "masked_lm":
            raise ValueError(
                "seq_parallelism>1 requires a sequence model (masked_lm)"
            )
        if config.seq_len % config.seq_parallelism:
            raise ValueError(
                f"seq_len {config.seq_len} not divisible by "
                f"seq_parallelism {config.seq_parallelism}"
            )
        from .parallel.ring_attention import make_ring_attention

        attention_fn = make_ring_attention(mesh)
    elif config.pipeline_parallelism > 1:
        if config.task_type != "masked_lm":
            raise ValueError(
                "pipeline_parallelism>1 requires a sequence model (masked_lm)"
            )
    elif config.flash_attention:
        if config.task_type not in ("masked_lm", "causal_lm"):
            raise ValueError("flash_attention requires a sequence model")
        from .ops.flash import make_flash_attention

        # causal_lm binds the kernel's fused autoregressive masking (also
        # skips the fully-masked upper blocks).
        attention_fn = make_flash_attention(
            causal=config.task_type == "causal_lm", mesh=mesh
        )
    return get_task(
        config.task_type,
        num_classes=config.num_classes,
        model_name=config.model_name,
        image_size=config.image_size,
        seq_len=config.seq_len,
        vocab_size=config.vocab_size,
        num_layers=config.num_layers,
        expert_share=config.expert_share,
        layer_span=config.layer_span,
        augment=config.augment,
        attention_fn=attention_fn,
        remat=config.remat,
        num_experts=config.num_experts,
        moe_every=config.moe_every,
        pipeline_parallelism=config.pipeline_parallelism,
        pp_microbatches=config.pp_microbatches,
        mesh=mesh,
    )


def _kernel_paths(task: Task, config: TrainConfig) -> dict:
    """The first log line's word for the form each of the model's kernels
    runs at ``seq_len``, by the kernel's name (``Task.kernels``: the mixers'
    own answer, which asks the test each call makes): ``attention=``,
    ``scan=``, ``delta=``, ``ssd=``, ``conv=``, ``norm=``; and ``yarn=`` where
    some held layer's rotary turn runs YaRN's table (Laguna's full layers)."""
    plain = {"attention": "ring" if config.seq_parallelism > 1 else "dense",
             "conv": "plain", "norm": "plain"}
    paths = {name: "fused kernel" if fused else plain.get(name, "chunked")
             for name, fused in sorted(task.kernels.items())}
    yarn = getattr(task.model, "yarn", ())
    if yarn:
        paths["yarn"] = ("factor {:g} over {} positions, beta {:g}/{:g}, "
                         "cos and sin x {:.4f}").format(*yarn)
    return paths


def lr_schedule_fn(config: TrainConfig, total_steps: Optional[int] = None):
    """The learning-rate schedule from the config knobs: a float (constant)
    or an ``optax`` schedule callable over OPTIMIZER updates (data steps are
    converted under ``grad_accum`` — see :func:`make_optimizer`). Shared by
    the optimizer build and the per-step lr logging."""
    horizon = total_steps or config.total_steps
    accum = max(config.grad_accum, 1)
    if config.lr_schedule == "constant":
        if config.warmup_steps > 0:
            # Linear warmup, then constant — warmup_steps must never be a
            # silent no-op just because no decay schedule was chosen.
            return optax.linear_schedule(
                0.0, config.lr, max(-(-config.warmup_steps // accum), 1)
            )
        return config.lr
    if config.lr_schedule == "cosine":
        if not horizon:
            raise ValueError("cosine schedule needs total_steps")
        horizon = max(-(-horizon // accum), 1)
        warmup = -(-config.warmup_steps // accum)
        if warmup > 0:
            return optax.warmup_cosine_decay_schedule(
                0.0, config.lr, warmup, max(horizon, warmup + 1)
            )
        return optax.cosine_decay_schedule(config.lr, horizon)
    raise ValueError(f"Invalid lr_schedule: {config.lr_schedule}")


def make_optimizer(config: TrainConfig, total_steps: Optional[int] = None):
    """Optax chain from the config knobs.

    The reference trains with a single fixed-lr SGD
    (``/root/reference/lance_iterable.py:98``); that stays the default. Beyond
    it: AdamW (decoupled weight decay), SGD + classic L2 weight decay (the
    decay term rides the momentum buffer, torch ``SGD(weight_decay=)``
    semantics), cosine decay with linear warmup, global-norm gradient
    clipping, and gradient accumulation (``optax.MultiSteps`` — N
    micro-batches per parameter update, the memory-for-batch-size trade that
    needs no loader change).

    ``total_steps`` / ``warmup_steps`` are counted in *data* (micro) steps;
    with ``grad_accum > 1`` they are converted to optimizer updates here,
    since ``MultiSteps`` advances the inner schedule once per accumulation
    window — otherwise the schedule would traverse only 1/N of its horizon.
    """
    lr = lr_schedule_fn(config, total_steps)
    parts = []
    if config.grad_clip > 0:
        parts.append(optax.clip_by_global_norm(config.grad_clip))
    if config.optimizer == "sgd":
        if config.weight_decay > 0:
            parts.append(optax.add_decayed_weights(config.weight_decay))
        parts.append(optax.sgd(lr, momentum=config.momentum))
    elif config.optimizer == "adamw":
        parts.append(optax.adamw(lr, weight_decay=config.weight_decay))
    else:
        raise ValueError(f"Invalid optimizer: {config.optimizer}")
    tx = parts[0] if len(parts) == 1 else optax.chain(*parts)
    if config.grad_accum > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=config.grad_accum)
    return tx


def create_train_state(rng: jax.Array, task: Task, config: TrainConfig,
                       total_steps: Optional[int] = None) -> TrainState:
    variables = task.init_variables(rng)
    tx = make_optimizer(config, total_steps)
    return TrainState.create(
        apply_fn=None,
        params=variables["params"],
        batch_stats=variables.get("batch_stats"),
        tx=tx,
    )


def create_sharded_train_state(
    rng: jax.Array, task: Task, config: TrainConfig, mesh, rules=(),
    *, fsdp_axis: Optional[str] = None, zero_axis: Optional[str] = None,
    zero_level: int = 1, total_steps: Optional[int] = None,
):
    """Initialize the TrainState *directly sharded* over the mesh.

    Init runs under jit with ``out_shardings`` derived from the partition
    rules, so each device materialises only its parameter shard — no host
    round-trip, no full replica anywhere (how a model larger than one chip's
    HBM gets initialized). With ``fsdp_axis``, rule-unmatched leaves (params
    AND their optimizer state) fully shard over that axis instead of
    replicating; with ``zero_axis``, only the optimizer state does (ZeRO-1 —
    each device initializes just its momentum/moment shard). Returns
    ``(state, sharding_pytree)``.
    """
    from .parallel.sharding import state_shardings

    # One tx instance shared by the eval_shape pass and the jitted init —
    # TrainState's static metadata (tx, apply_fn) must be identical in the
    # out_shardings prefix tree and the actual output.
    tx = make_optimizer(config, total_steps)

    def _create(r):
        variables = task.init_variables(r)
        return TrainState.create(
            apply_fn=None,
            params=variables["params"],
            batch_stats=variables.get("batch_stats"),
            tx=tx,
        )

    abstract = jax.eval_shape(_create, rng)
    shardings = state_shardings(abstract, mesh, rules, fsdp_axis=fsdp_axis,
                                zero_axis=zero_axis, zero_level=zero_level)
    return jax.jit(_create, out_shardings=shardings)(rng), shardings


def _variables(state: TrainState) -> dict:
    v = {"params": state.params}
    if state.batch_stats is not None:
        v["batch_stats"] = state.batch_stats
    return v


def make_train_step(task: Task, mesh, *, donate: bool = True,
                    state_sharding=None, batch_spec=None,
                    grad_norm: bool = False, stats: bool = False,
                    grad_sharding=None):
    """Build the jitted sharded train step.

    Pure DP (the reference's scope): state replicated (``P()``), every batch
    leaf sharded ``P('data')`` on its leading dim; under those in-shardings
    XLA turns the per-shard gradients into a mean via an all-reduce over ICI —
    the compiled equivalent of DDP's bucketed NCCL all-reduce
    (``/root/reference/lance_iterable.py:93-97``; ``README.md:185``).

    Beyond DP: pass ``state_sharding`` (a NamedSharding pytree from
    :func:`~.parallel.sharding.state_shardings`) to tensor-parallel-shard
    params + optimizer state over the ``'model'`` axis, and ``batch_spec``
    (e.g. ``P('data', 'seq')``) to lay token batches out for context
    parallelism. The SPMD partitioner derives every collective from these
    annotations — no communication code here.

    The step returns ``(state, loss)``, then the gradient norm under
    ``grad_norm``, then under ``stats`` the dictionary of scalars that
    ``task.stats`` reports of the step (the expert layer's load, the
    masked-LM head's fill; empty for a task with none). What comes back is
    the caller's choice and never the task's.
    """

    def step(state: TrainState, batch, rng):
        def loss_of(params):
            variables = dict(_variables(state), params=params)
            # Scopes are metadata only: they name every instruction's
            # op_name (jvp(forward), transpose(jvp(forward)), optimizer) so
            # a device trace splits the step by phase and by flax module.
            with jax.named_scope("forward"):
                outputs, new_state = task.forward(variables, batch, True, rng)
            with jax.named_scope("loss"):
                loss = task.loss(outputs, batch)
            reported = (task.stats(outputs)
                        if stats and task.stats is not None else {})
            return loss, (new_state, reported)

        (loss, (new_model_state, reported)), grads = jax.value_and_grad(
            loss_of, has_aux=True
        )(state.params)
        if grad_sharding is not None:
            # ZeRO-2's in-flight half: pin the gradients to the moment/
            # accumulator layout (grad_partition_specs), so the SPMD
            # partitioner lowers the data-axis gradient mean to
            # reduce-scatter + shard-local optimizer update + param
            # all-gather instead of a full all-reduce per device. A pure
            # re-layout — gradient VALUES are unchanged.
            grads = jax.lax.with_sharding_constraint(grads, grad_sharding)
        with jax.named_scope("optimizer"):
            state = state.apply_gradients(grads=grads)
            if (new_model_state is not None
                    and "batch_stats" in new_model_state):
                state = state.replace(
                    batch_stats=new_model_state["batch_stats"])
        if grad_norm:
            # Global norm of THIS micro-batch's gradient (a few extra sum-
            # reductions XLA fuses into the backward) — divergence telemetry
            # (--log_grad_norm). With grad_accum > 1 the optimizer clips the
            # accumulated MEAN inside MultiSteps (smoother than this), which
            # is not observable from here.
            extras = (optax.global_norm(grads),)
        else:
            extras = ()
        if stats:
            # What the task reports of the step: scalars beside the loss,
            # read at log points, never a sync.
            extras += (reported,)
        return (state, loss) + extras

    repl = replicated_sharding(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    if batch_spec is not None:
        from jax.sharding import NamedSharding

        data = NamedSharding(mesh, batch_spec)
    else:
        data = batch_sharding(mesh)
    out_sh = (state_sh, repl) + (repl,) * (int(grad_norm) + int(stats))
    jitted = jax.jit(
        step,
        in_shardings=(state_sh, data, repl),
        out_shardings=out_sh,
        donate_argnums=(0,) if donate else (),
    )
    if compiletrack.enabled():
        # Compile-witness funnel (LDT1703's evidence half): count distinct
        # trace signatures per step def site — steady state must show zero
        # post-warmup compiles, and scripts/ci.sh gates on exactly that.
        jitted = compiletrack.wrap_jit(jitted, step)
    return jitted


def make_eval_step(task: Task, mesh, *, state_sharding=None, batch_spec=None):
    """Returns ``step(state, batch) -> (metric_sum, example_count)``.

    A batch carrying ``_weight`` (the full-coverage eval loader's pad mask,
    ``make_eval_pipeline``) contributes ``(metric·w).sum(), w.sum()`` so
    wrap-around pad rows count zero; otherwise the count is the static batch
    size. Two jitted variants — the weight array is rank-1 regardless of the
    task's batch rank, so it takes its own ``P('data')`` sharding rather
    than the batch-wide spec."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = replicated_sharding(mesh)
    state_sh = state_sharding if state_sharding is not None else repl
    if batch_spec is not None:
        data = NamedSharding(mesh, batch_spec)
    else:
        data = batch_sharding(mesh)
    wsharding = NamedSharding(mesh, P("data"))

    def _metric(state: TrainState, batch):
        outputs, _ = task.forward(_variables(state), batch, False, None)
        return task.metric(outputs, batch)

    def _plain(state: TrainState, batch):
        m = _metric(state, batch)
        return m.sum(), jnp.asarray(m.shape[0], jnp.float32)

    def _weighted(state: TrainState, batch, w):
        m = _metric(state, batch)
        return (m * w).sum(), w.sum()

    plain = jax.jit(_plain, in_shardings=(state_sh, data),
                    out_shardings=repl)
    weighted = jax.jit(_weighted, in_shardings=(state_sh, data, wsharding),
                       out_shardings=repl)
    if compiletrack.enabled():
        plain = compiletrack.wrap_jit(plain, _plain)
        weighted = compiletrack.wrap_jit(weighted, _weighted)

    def step(state: TrainState, batch):
        batch = dict(batch)
        w = batch.pop("_weight", None)
        if w is None:
            return plain(state, batch)
        return weighted(state, batch, w)

    return step


def evaluate(state, loader, eval_step) -> float:
    """Mean per-example metric over a loader — the ``evaluate`` equivalent
    (``/root/reference/modelling/classification.py:20-32``). The per-batch
    (sum, count) pairs accumulate ON DEVICE (async dispatch); the only host
    sync is the final ``float()`` — unlike the reference's per-step
    ``.item()`` (``lance_iterable.py:115``) this never serialises eval on
    D2H. Pad rows from the full-coverage eval loader carry weight 0 in both
    the sum and the count."""
    num = None
    den = None
    batches = 0
    for batch in loader:
        part, count = eval_step(state, batch)
        num = part if num is None else num + part
        den = count if den is None else den + count
        batches += 1
        if batches % 32 == 0:
            # Bound dispatch depth: each in-flight eval step pins its batch
            # on device; one scalar fetch per 32 batches caps that without
            # serialising every step as the reference's .item() did.
            if compiletrack.enabled():
                compiletrack.track_transfer(
                    "d2h", getattr(num, "nbytes", 0) or 0)
            _ = float(num)  # ldt: ignore[LDT1704] -- deliberate dispatch-depth drain: one scalar fetch per 32 eval batches caps in-flight memory
    if den is None:
        return 0.0
    if compiletrack.enabled():
        compiletrack.track_transfer("d2h", getattr(den, "nbytes", 0) or 0)
    total = float(den)  # ldt: ignore[LDT1704] -- the eval-end fetch: the one place the mean leaves the device
    return float(num) / total if total else 0.0  # ldt: ignore[LDT1704] -- same eval-end fetch; num is already drained one line up


def _loader_buffer_pool(config: TrainConfig):
    """The process BufferPool when the knob is on — shared by the decoder
    (lease side) and every pipeline (release side), so pages recycle across
    batches instead of faulting fresh per step."""
    if not config.buffer_pool:
        return None
    from .data.buffers import default_buffer_pool

    return default_buffer_pool()


_TEXT_TASKS = ("masked_lm", "causal_lm", "contrastive")
# gauges a traced step of a model with expert layers sets to 1 where it runs
# the kernel form: like attention_fused on every log line and in the results
_EXPERT_GAUGES = ("rows_sum_fused", "grouped_products_fused")


def _token_pack_config(config: TrainConfig, mesh=None):
    """The run's :class:`~.data.token_pack.TokenPackConfig`, or ``None``
    when the ragged plane is off. ``mesh`` pins ``rows_align`` to the
    data-axis size so every packed grid's row count divides over the
    devices (the autotuner may move ``rows_multiple`` freely; the align
    floor is immune)."""
    if not config.token_pack:
        return None
    from .data.token_pack import TokenPackConfig

    align = 1
    if mesh is not None:
        align = int(mesh.shape.get("data", 1))
    return TokenPackConfig(
        pack_len=config.pack_len or config.seq_len,
        rows_multiple=config.pack_rows_multiple,
        rows_align=align,
    )


def _decoder_for(config: TrainConfig, *, for_eval: bool = False, mesh=None):
    from .data.decode import decoder_for_task

    text = config.task_type in _TEXT_TASKS
    return decoder_for_task(
        config.task_type, config.image_size,
        buffer_pool=_loader_buffer_pool(config),
        device_decode=config.device_decode,
        # Eval always streams the padded arm: per-sequence metrics (and
        # the full-coverage loader's _weight pads) need row alignment the
        # FFD pack gives up.
        token_pack=None if for_eval else _token_pack_config(config, mesh),
        seq_len=config.seq_len if text else None,
    )


def _make_worker_pool(config: TrainConfig, dataset, mesh=None):
    """Persistent decode-worker pool (``num_workers``/``persistent_workers``
    parity, ``/root/reference/lance_map_style.py:60-69``). None when
    ``num_workers == 0`` — decode then runs on the producer thread + the
    native decoder's own thread pool."""
    if config.num_workers <= 0:
        return None
    from .data.workers import WorkerPool, columnar_spec, folder_spec

    decode = _decoder_for(config, mesh=mesh)
    columns = getattr(decode, "required_columns", None)
    transport = "shm" if config.shm_workers else "pickle"
    pool = _loader_buffer_pool(config)
    if config.data_format == "folder":
        from .data.authoring import _folder_samples

        samples, _ = _folder_samples(config.dataset_path)
        return WorkerPool(folder_spec(samples), decode, config.num_workers,
                          transport=transport, buffer_pool=pool)
    return WorkerPool(
        columnar_spec(config.dataset_path), decode, config.num_workers,
        columns=columns, transport=transport, buffer_pool=pool,
    )


def _make_placement(config: TrainConfig, mesh):
    """The run's :class:`~.data.placement.PlacementPlane`. One plane per
    loader build; the plane shares the process BufferPool with the decode
    side so leases released at transfer dispatch warm the next decode."""
    from .data.placement import PlacementPlane

    return PlacementPlane(
        mesh,
        seq_axis="seq" if config.seq_parallelism > 1 else None,
        depth=config.placement_depth,
        buffer_pool=_loader_buffer_pool(config),
    )


def _build_loader(config: TrainConfig, dataset, mesh, epoch: int = 0,
                  workers=None, index_pool=None, batch_cache=None,
                  folder_fp=None):
    process_index, process_count = process_topology()
    per_process = config.batch_size // process_count
    if per_process * process_count != config.batch_size:
        raise ValueError(
            f"global batch {config.batch_size} not divisible by "
            f"{process_count} processes"
        )
    decode = _decoder_for(config, mesh=mesh)
    # Placement: host batches out of the engines, one placement thread
    # owning H2D.
    plane = _make_placement(config, mesh)

    # Every arm is ONE LoaderGraph assembly (data/graph.py): the source/
    # transport choice is the only thing that varies; decode boundary,
    # cache, buffers, prefetch, and placement compose identically.
    from .data.graph import (
        Buffers,
        Cache,
        Decode,
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

    def _assemble(source, decode_node, *mid):
        graph = LoaderGraph(source, decode_node, *mid,
                            Buffers(_loader_buffer_pool(config)),
                            Place(plane))
        graph.compile()
        return graph

    if config.data_service_addr or config.coordinator_addr:
        # Disaggregated input plane: decode runs in remote DataService
        # processes; this process only streams host batches and places
        # them. The servers build the identical epoch Plan (same
        # LanceSource.shard_plans), so batches match local training
        # bit-for-bit on the same seed — whether one server
        # (ServiceTransport) or a coordinated fleet striped across N of
        # them (FleetTransport).
        source = LanceSource(
            None,
            config.sampler_type,
            per_process,
            process_index,
            process_count,
            shuffle=config.shuffle,
            seed=config.seed,
            epoch=epoch,
            # Dataset-identity skew check (r13): when this host can read
            # the dataset too, declare its fingerprint so a server backed
            # by a DIFFERENT copy is rejected at connect time.
            dataset_fingerprint=(
                dataset.fingerprint() if dataset is not None else None
            ),
        )
        decode_node = Decode(
            columns=getattr(decode, "required_columns", None),
            task_type=config.task_type,
            image_size=config.image_size,
            # Text-task decode shape, skew-checked like image_size (a
            # seq_len-64 trainer against a seq_len-128 server would crash
            # mid-epoch on the model's max_len).
            seq_len=(
                config.seq_len if config.task_type in _TEXT_TASKS else None
            ),
            device_decode=config.device_decode,
            token_pack=config.token_pack,
        )
        transport = (
            FleetTransport(config.coordinator_addr,
                           job_id=config.job_id,
                           job_priority=config.job_priority)
            if config.coordinator_addr
            else ServiceTransport(config.data_service_addr,
                                  job_id=config.job_id,
                                  job_priority=config.job_priority)
        )
        loader = _assemble(source, decode_node,
                           Prefetch(config.prefetch), transport)
        if len(loader) == 0:
            raise ValueError(
                "empty plan from data service: dataset smaller than one "
                f"global batch ({config.batch_size})"
            )
        return loader
    if config.filter and config.data_format != "columnar":
        raise ValueError("filter= needs the columnar store (data_format="
                         "'columnar'); folder trees have no row predicates")
    prefetch_node = Prefetch(config.prefetch,
                             producers=config.producer_threads)
    if config.data_format == "folder":
        # Control arm: plain files, no columnar store (torch_version/ twin,
        # reference README.md:286-290).
        source = FolderSource(
            config.dataset_path,
            per_process,
            process_index,
            process_count,
            loader_style=config.loader_style,
            # Map-style always reshuffles (DistributedSampler semantics);
            # the iterable arm's batch-order shuffle is opt-in, matching the
            # columnar iterable path.
            shuffle=True if config.loader_style == "map" else config.shuffle,
            seed=config.seed,
            epoch=epoch,
            dataset_fingerprint=folder_fp,
        )
        loader = _assemble(source, Decode(decode), Cache(batch_cache),
                           Pool(workers), prefetch_node, InProcess())
        if len(loader) == 0:
            raise ValueError("folder smaller than one global batch")
        if (
            config.task_type == "classification"
            and loader.num_classes > config.num_classes
        ):
            raise ValueError(
                f"folder has {loader.num_classes} class directories but "
                f"num_classes={config.num_classes}; out-of-range labels "
                "would be silently clamped by the XLA gather"
            )
        return loader
    columns = getattr(decode, "required_columns", None)
    if config.filter and config.loader_style != "map":
        raise ValueError(
            "filter= needs the map-style loader (the predicate resolves to "
            "an index pool; iterable range plans read contiguous rows); pass "
            "loader_style='map'"
        )
    if config.loader_style == "map":
        if config.filter and index_pool is None:
            # Fallback for direct calls / held-out val datasets; train()
            # resolves the TRAIN pool once and passes it down.
            index_pool = dataset.filter_indices(config.filter)
        if index_pool is not None and len(index_pool) < config.batch_size:
            raise ValueError(
                f"filter {config.filter!r} keeps {len(index_pool)} rows — "
                f"fewer than one global batch ({config.batch_size})"
            )
        source = MapStyleSource(
            dataset,
            per_process,
            process_index,
            process_count,
            seed=config.seed,
            epoch=epoch,
            index_pool=index_pool,
        )
    else:
        source = LanceSource(
            dataset,
            config.sampler_type,
            per_process,
            process_index,
            process_count,
            shuffle=config.shuffle,
            seed=config.seed,
            epoch=epoch,
        )
    loader = _assemble(source, Decode(decode, columns=columns),
                       Cache(batch_cache), Pool(workers), prefetch_node,
                       InProcess())
    if len(loader) == 0:
        raise ValueError(
            "empty plan: dataset smaller than one global batch "
            f"({dataset.count_rows()} rows, global batch {config.batch_size})"
        )
    return loader


def _split_val_pool(config: TrainConfig, dataset, index_pool):
    """Held-out validation fraction: a seeded disjoint split of the
    (possibly filtered) row pool. Deterministic across processes — every
    process derives the same split, preserving the equal-step invariant.
    Returns ``(train_pool, val_pool)``, both sorted global row indices."""
    pool = (
        index_pool
        if index_pool is not None
        else np.arange(dataset.count_rows(), dtype=np.int64)
    )
    if len(pool) < 2 * config.batch_size:
        # Both sides need at least one full global batch (also guards an
        # empty --filter pool before any division below).
        raise ValueError(
            f"val_fraction needs at least two global batches "
            f"(2×{config.batch_size}) in the pool; have {len(pool)} rows"
        )
    n_val = int(len(pool) * config.val_fraction)
    if n_val < config.batch_size:
        # Eval needs at least one full global batch; never silently.
        import warnings

        warnings.warn(
            f"val_fraction {config.val_fraction} yields {n_val} rows — "
            f"raised to one global batch ({config.batch_size} rows = "
            f"{config.batch_size / len(pool):.1%} of the pool)",
            stacklevel=3,
        )
        n_val = config.batch_size
    if len(pool) - n_val < config.batch_size:
        raise ValueError(
            f"val_fraction {config.val_fraction} leaves fewer than one "
            f"global batch ({config.batch_size}) on one side of the "
            f"split ({len(pool)} rows available)"
        )
    perm = np.random.default_rng(config.seed).permutation(len(pool))
    return np.sort(pool[perm[n_val:]]), np.sort(pool[perm[:n_val]])


def _build_eval_loader(config: TrainConfig, dataset, mesh, index_pool=None,
                       batch_cache=None, folder_fp=None):
    """Full-coverage eval loader: every row exactly once per eval, the tail
    batch padded by wrap-around rows carried with ``_weight`` 0.0 — single
    compiled batch shape, equal step counts on every process (r3 verdict:
    batch-sampler eval dropped the tail; full_scan's ragged tail recompiled).
    Training's ``loader_style``/``sampler_type`` don't apply here: eval
    coverage is exact by construction on both storage arms."""
    from .data.pipeline import make_eval_pipeline

    process_index, process_count = process_topology()
    decode = _decoder_for(config, for_eval=True)
    if config.data_format == "folder":
        from .data.authoring import _folder_samples
        from .data.folder import read_sample_batch

        samples, _ = _folder_samples(config.dataset_path)

        def read_fn(idx):
            return read_sample_batch(samples, idx)

        total = len(samples)
        # The run-scoped fingerprint train() computed once; the direct-
        # call fallback (library users) derives it here, still only when
        # a cache is actually bound.
        dataset_fp = folder_fp
        if dataset_fp is None and batch_cache is not None:
            from .data.cache import folder_fingerprint

            dataset_fp = folder_fingerprint(samples)
    else:
        columns = getattr(decode, "required_columns", None)

        def read_fn(idx):
            return dataset.take(idx, columns=columns)

        total = dataset.count_rows()
        # The fingerprint was computed once at Dataset construction —
        # eval rebuilds this loader every eval_every epochs and must
        # REUSE it, not re-derive it (the r13 satellite).
        dataset_fp = dataset.fingerprint()
        if config.filter and index_pool is None:
            index_pool = dataset.filter_indices(config.filter)
    loader = make_eval_pipeline(
        read_fn,
        total,
        config.batch_size,
        process_index,
        process_count,
        decode,
        prefetch=config.prefetch,
        producers=config.producer_threads,
        index_pool=index_pool,
        buffer_pool=_loader_buffer_pool(config),
        batch_cache=batch_cache,
        dataset_fingerprint=dataset_fp,
    )
    return _make_placement(config, mesh).wrap(loader)


# The package's parent directory: the repo root of a checkout. A fixed path,
# never a temporary name — a cache that moves between runs never hits.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def maybe_enable_compile_cache(platform: str, *,
                               enabled: bool = True) -> Optional[str]:
    """Persistent XLA compile cache; returns the directory in use, or None.

    Whoever launches the process places the cache: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and nothing
    here sets a directory. Where it is not set, accelerator runs cache under
    ``<checkout>/.jax_cache``. XLA:CPU stays uncached unless the variable
    says otherwise: its persistent cache stores AOT machine code whose
    round-trip is unsound for shard_map collective programs and across
    hosts (see tests/conftest.py).

    The cache's key and directory are settled here, so this is also where
    the process starts to record what it traces, lowers and compiles
    (``obs/spans.watch_xla_compiles``, once a process): a caller that
    compiles before ``train()`` (the benchmark's model check) has those
    programs in the span file too, outside every phase.
    """
    watch_xla_compiles()
    if os.environ.get("LDT_TRACE_PATH"):
        # A traced run reads the step's scopes (forward, optimizer, ...) from
        # the executable's metadata, and JAX leaves metadata out of the
        # cache key: an executable that a build without these scopes wrote
        # to a shared cache would be loaded with ITS names (seen on the
        # v5e, PR 24). Traced runs key by metadata too, so they compile, or
        # find, executables that carry this build's names; untraced runs
        # keep the default key and whatever the cache already holds.
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if not enabled or platform == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR


def _process_age_s() -> Optional[float]:
    """Seconds since the OS started this process: its start time in
    ``/proc/self/stat`` (clock ticks since boot) against the boot clock.
    None where the OS has no such file."""
    try:
        with open("/proc/self/stat") as f:
            # after "pid (comm)", which may hold spaces: state is field 3,
            # starttime field 22
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        return round(time.clock_gettime(time.CLOCK_BOOTTIME)
                     - started / os.sysconf("SC_CLK_TCK"), 3)
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def _cache_census(cache_dir: Optional[str]) -> dict:
    """What the compile cache holds as ``train()`` is entered (one
    ``scandir``), and whether this process keys it by metadata too."""
    entries = size = 0
    if cache_dir:
        try:
            with os.scandir(cache_dir) as found:  # ldt: ignore[LDT003] -- counted and summed: the order never shows
                for entry in found:
                    entries += 1
                    size += entry.stat().st_size
        except OSError:
            pass  # no directory yet: JAX makes it at its first write
    return {
        "cache_dir": cache_dir, "cache_entries": entries,
        "cache_bytes": size,
        "cache_key_metadata": bool(
            jax.config.jax_compilation_cache_include_metadata_in_key),
    }


class _CkptJournal:
    """Checkpoint bookkeeping shared between the step loop and ``train()``'s
    ``finally`` (the emergency-save path). Updated only at completed-step
    boundaries, so whatever it holds always pairs a model state with the
    cursor naming the exact next batch — a signal or exception arriving
    mid-step can never save an inconsistent pair."""

    def __init__(self, resume_global_step: int = 0):
        self.state = None  # latest post-step TrainState (a reference)
        self.rng = None  # the key as of the same boundary
        self.cursor_base: Optional[dict] = None  # loader {"epoch","step"}
        self.abs_step = resume_global_step  # absolute completed data steps
        self.saved_step = resume_global_step  # newest persisted abs_step
        self.preempted = False

    @property
    def dirty(self) -> bool:
        return self.state is not None and self.abs_step > self.saved_step

    def make_cursor(self) -> dict:
        from .utils.checkpoint import pack_rng_key

        cursor = dict(self.cursor_base or {})
        cursor["global_step"] = int(self.abs_step)
        if self.rng is not None:
            cursor["rng"] = pack_rng_key(self.rng)
        return cursor


def train(config: TrainConfig) -> dict:
    """The single training entry point. Returns final metrics.

    From entry to return the calling thread is in exactly one phase
    (``obs/spans.py``): ``startup.*`` up to the first ``train.loader``, the
    loop's flat ``train.*`` phases, ``train.shutdown`` at the end. They tile
    the thread's time, so a trace reader never has to guess what the loop
    was doing in a gap."""
    entry = obs_phase("startup.devices", process_age_s=_process_age_s())
    try:
        return _train(config, entry)
    finally:
        end_phase()


def _train(config: TrainConfig, entry: dict) -> dict:
    if config.val_fraction:
        # Validate the combo BEFORE any dataset I/O so a bad config fails
        # with its own message, not a dataset-open error.
        if not 0.0 < config.val_fraction < 1.0:
            raise ValueError(
                f"val_fraction must be in (0, 1), got {config.val_fraction}"
            )
        if config.val_dataset_path:
            raise ValueError(
                "val_fraction and val_dataset_path are mutually exclusive"
            )
        if config.data_format != "columnar" or config.loader_style != "map":
            raise ValueError(
                "val_fraction needs the map-style columnar path (the split "
                "is an index pool); pass loader_style='map'"
            )
    if config.data_service_addr and config.coordinator_addr:
        raise ValueError(
            "data_service_addr and coordinator_addr are mutually exclusive "
            "(one names a single server, the other a fleet's coordinator)"
        )
    if config.job_id and not (
        config.data_service_addr or config.coordinator_addr
    ):
        raise ValueError(
            "job_id declares tenancy on a shared data service/fleet — it "
            "needs data_service_addr or coordinator_addr (local decode has "
            "no job plane)"
        )
    if config.job_priority and not config.job_id:
        raise ValueError(
            "job_priority needs an explicit job_id (the implicit default "
            "job always runs at the server's default class)"
        )
    if config.fsdp and config.zero_opt:
        raise ValueError(
            "fsdp and zero_opt are mutually exclusive: fsdp (ZeRO-3) "
            "already shards the optimizer state along with the params"
        )
    if int(config.zero_opt) not in (0, 1, 2):
        raise ValueError(
            f"zero_opt must be 0, 1 (shard optimizer state) or 2 (also "
            f"shard gradient accumulation), got {config.zero_opt!r}"
        )
    if config.device_decode and config.task_type != "classification":
        raise ValueError(
            "device_decode splits the JPEG decode loop and currently "
            f"supports task_type='classification' only, got "
            f"{config.task_type!r}"
        )
    if config.token_pack:
        if config.task_type not in _TEXT_TASKS:
            raise ValueError(
                "token_pack packs token columns and needs a text task "
                f"({'/'.join(_TEXT_TASKS)}), got {config.task_type!r}"
            )
        if config.seq_parallelism > 1 or config.pipeline_parallelism > 1:
            raise ValueError(
                "token_pack is incompatible with seq_parallelism/"
                "pipeline_parallelism: packed batches re-enter the data "
                "layout inside the pack transform and carry no static "
                "sequence split"
            )
        if (config.num_processes or 1) > 1:
            raise ValueError(
                "token_pack currently supports single-process training "
                "only: each process's packed row count is data-dependent, "
                "and multi-host global-batch assembly needs identical "
                "per-process shapes"
            )
        if config.data_service_addr or config.coordinator_addr:
            if (jax.local_device_count() if config.no_ddp is False else 1) > 1:
                raise ValueError(
                    "token_pack over a data service cannot yet align "
                    "packed row counts to a multi-device mesh (the "
                    "server's planner does not know this trainer's device "
                    "count) — run single-device (--no_ddp) or decode "
                    "locally until pack alignment rides the HELLO"
                )
    if (
        config.device_decode
        and (config.num_processes or 1) > 1
        and not (config.data_service_addr or config.coordinator_addr)
    ):
        import warnings

        # Known limit: each host's CoeffImageDecoder grows its canonical
        # page grid independently (to ITS shard's largest image), and
        # global-batch assembly needs identical non-batch dims on every
        # process — shards with different max image sizes would crash
        # mid-epoch. Uniform-size corpora are fine; mixed-size multi-host
        # local decode is not yet.
        warnings.warn(
            "device_decode with multi-process LOCAL decode requires every "
            "process's shard to share the same maximum image size (the "
            "canonical coefficient grid must agree across hosts for "
            "global-batch assembly); mixed-size corpora should stream "
            "pixels (--no_device_decode) or move decode behind one data "
            "service until per-dataset grid pinning lands",
            stacklevel=2,
        )
    if config.placement_depth < 1:
        raise ValueError(
            f"placement_depth must be >= 1, got {config.placement_depth}"
        )
    if config.data_service_addr or config.coordinator_addr:
        remote_knob = (
            "data_service_addr" if config.data_service_addr
            else "coordinator_addr"
        )
        if config.data_format != "columnar" or config.loader_style != "iterable":
            raise ValueError(
                f"{remote_knob} needs the iterable columnar path (the "
                "service streams sampler-plan ranges); pass "
                "loader_style='iterable', data_format='columnar'"
            )
        if config.filter or config.val_fraction:
            raise ValueError(
                "filter/val_fraction resolve index pools locally and cannot "
                f"combine with {remote_knob}"
            )
        if config.num_workers > 0:
            import warnings

            warnings.warn(
                "num_workers>0 has no effect with data_service_addr: decode "
                "runs in the remote DataService (size ITS pool with "
                "`ldt serve-data --num_workers N`)",
                stacklevel=2,
            )
    maybe_initialize_distributed(
        config.coordinator_address, config.num_processes, config.process_id
    )
    devices = jax.devices()
    if config.no_ddp:
        devices = devices[:1]
    # places the cache and turns on the jax.trace / jax.lower / xla.compile
    # spans and their counters, if a caller has not already
    entry.update(_cache_census(maybe_enable_compile_cache(
        devices[0].platform, enabled=config.compile_cache)))
    mesh = get_mesh(
        devices,
        model_parallelism=config.model_parallelism,
        seq_parallelism=config.seq_parallelism,
        pipe_parallelism=config.pipeline_parallelism,
    )

    obs_phase("startup.dataset")
    if config.data_format != "columnar":
        dataset = None
    elif config.data_service_addr or config.coordinator_addr:
        # Disaggregated runs: the TPU host may not mount the dataset path at
        # all — train-side reads happen on the service host. Open locally
        # only if present (it unlocks eval + schedule-horizon derivation).
        try:
            dataset = Dataset(config.dataset_path)
        except FileNotFoundError:
            dataset = None
    else:
        dataset = Dataset(config.dataset_path)
    if (
        dataset is None
        and (config.data_service_addr or config.coordinator_addr)
        and (config.eval_at_end or config.eval_every)
        and not config.val_dataset_path
    ):
        raise ValueError(
            "eval needs the dataset readable on this host (eval reads rows "
            f"directly, not through the data service): {config.dataset_path} "
            "is absent — mount it, pass val_dataset_path, or disable eval "
            "(eval_at_end=False, eval_every=0)"
        )
    val_dataset = (
        Dataset(config.val_dataset_path)
        if config.val_dataset_path and config.data_format == "columnar"
        else None
    )
    task = _task_from_config(config, mesh)

    rng = jax.random.key(config.seed)
    rng, init_rng = jax.random.split(rng)
    from .parallel.sharding import batch_partition_spec, rules_for_task

    rules = (
        rules_for_task(task.name, config.model_name)
        if (config.model_parallelism > 1 or config.pipeline_parallelism > 1)
        else ()
    )
    # Row-filter pool: resolved ONCE here (deterministic; per-epoch
    # re-resolution would rescan every fragment at each epoch/eval boundary)
    # and passed down to every train-side loader build.
    index_pool = None
    if (
        config.filter
        and config.data_format == "columnar"
        and config.loader_style == "map"
    ):
        index_pool = dataset.filter_indices(config.filter)
    val_pool = None
    if config.val_fraction > 0:
        index_pool, val_pool = _split_val_pool(config, dataset, index_pool)
    total_steps = config.total_steps
    if total_steps is None and config.lr_schedule != "constant":
        # Schedule horizon: steps/epoch × epochs. rows // batch matches the
        # balanced samplers' drop-last behaviour closely enough for a decay
        # horizon (fragment padding can add a few steps). A --filter pool
        # shrinks the horizon with it.
        if index_pool is not None:
            rows = len(index_pool)
        elif dataset is not None:
            rows = dataset.count_rows()
        elif config.data_service_addr or config.coordinator_addr:
            raise ValueError(
                "lr_schedule needs a horizon, and the dataset is not "
                "readable on this host to derive one — pass total_steps "
                "explicitly with data_service_addr"
            )
        else:
            from .data.authoring import _folder_samples

            rows = len(_folder_samples(config.dataset_path)[0])
        total_steps = (
            max(rows // config.batch_size, 1)
            * config.epochs
            * max(config.data_echo, 1)  # echoes are real optimizer steps
        )
    obs_phase("startup.state")  # init program, then the (lazy) step builders
    state, state_sharding = create_sharded_train_state(
        init_rng, task, config, mesh, rules,
        fsdp_axis="data" if config.fsdp else None,
        zero_axis="data" if config.zero_opt else None,
        zero_level=int(config.zero_opt) or 1,
        total_steps=total_steps,
    )
    if config.pretrained:
        # Transfer learning (the reference's actual training task): replace
        # the randomly initialised backbone with the checkpoint's weights,
        # re-committed at the state's own shardings.
        if config.task_type != "classification":
            raise ValueError(
                "--pretrained imports torchvision ResNet checkpoints; task "
                f"{config.task_type!r} has no importer"
            )
        from .models.pretrained import (
            load_torch_state_dict,
            torchvision_resnet_to_flax,
        )

        imported = torchvision_resnet_to_flax(
            load_torch_state_dict(config.pretrained),
            {"params": state.params, "batch_stats": state.batch_stats},
            config.model_name or "resnet50",
        )
        state = state.replace(
            params=jax.device_put(imported["params"], state_sharding.params),
            batch_stats=jax.device_put(
                imported["batch_stats"], state_sharding.batch_stats
            ),
        )
    batch_spec = (
        batch_partition_spec(2, seq_axis="seq")
        if config.seq_parallelism > 1
        else None
    )

    grad_sharding = None
    if int(config.zero_opt) >= 2:
        from jax.sharding import NamedSharding

        from .parallel.sharding import grad_partition_specs

        grad_sharding = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            grad_partition_specs(state.params, mesh),
        )
    train_step = make_train_step(
        task, mesh, state_sharding=state_sharding, batch_spec=batch_spec,
        grad_norm=config.log_grad_norm, stats=True,
        grad_sharding=grad_sharding,
    )
    eval_step = make_eval_step(
        task, mesh, state_sharding=state_sharding, batch_spec=batch_spec
    )

    n_devices = len(mesh.devices.flatten())
    # Every run names the device it ran on, first in its log and in its
    # result: a number without it cannot be read as a device number.
    device_info = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": n_devices,
    }
    logger = MetricLogger(
        run_name=config.run_name
        or f"DP-{config.loader_style}-{config.sampler_type}-"
           f"{config.model_name or task.name}",
        config=dataclasses.asdict(config),
        enabled=not config.no_wandb,
    )
    timer = StepTimer()
    results: dict = {}
    if "attention" in task.kernels:
        # 1.0 where the model's attention at seq_len runs the fused kernel
        # in every layer that has attention, 0.0 where it runs dense (or
        # ring) attention; absent for a task without attention of its own to
        # choose. A gauge, and an entry of every log line and of the results
        results["attention_fused"] = float(task.kernels["attention"])
        default_registry().gauge("attention_fused").set(
            results["attention_fused"])
    if getattr(task.model, "experts_per_token", 0):
        # 0 until a step is traced whose expert layers sum their rows back
        # by the kernel (ops/rows.py sets it to 1 as it builds one), or
        # multiply their groups by the Pallas grouped matmul (ops/grouped.py)
        for name in _EXPERT_GAUGES:
            results[name] = 0.0
            default_registry().gauge(name).set(0.0)
    total_start = time.perf_counter()
    global_step = 0

    # Checkpoint/resume — preemption recovery the reference delegates to its
    # launcher with nothing to restore (SURVEY.md §5). Checkpoints are
    # step-granular and crash-consistent (utils/checkpoint.py): the newest
    # INTACT step restores model + optimizer state together with the
    # data-plane cursor (epoch, batches consumed, absolute step, host rng),
    # so the resumed stream — and with it the loss trajectory — is
    # bit-identical to the uninterrupted run. Corrupt/partial checkpoints
    # (the previous preemption's torn write) fall back to the step before.
    ckpt = None
    start_epoch = 0
    resume_epoch_step = 0  # batches already consumed within start_epoch
    resume_global_step = 0  # absolute data steps completed before this run
    if config.checkpoint_dir:
        from .utils.checkpoint import CheckpointManager, unpack_rng_key

        obs_phase("startup.restore")
        ckpt = CheckpointManager(config.checkpoint_dir)
        if config.resume:
            restored = ckpt.restore_latest(state)
            if restored is not None:
                state, cursor, ck_step = restored
                if cursor is not None:
                    start_epoch = min(
                        int(cursor.get("epoch", 0)), config.epochs
                    )
                    resume_epoch_step = (
                        int(cursor.get("step", 0))
                        if start_epoch < config.epochs else 0
                    )
                    resume_global_step = int(
                        cursor.get("global_step", ck_step)
                    )
                    packed = cursor.get("rng")
                    if packed is not None:
                        # Exact key restore: the split sequence (and the
                        # on-device augment/masking draws) continues bit-
                        # identically to the uninterrupted run.
                        rng = unpack_rng_key(packed)
                    else:
                        rng = jax.random.fold_in(rng, start_epoch)
                else:
                    # Legacy cursorless checkpoint: the step index is
                    # "epochs completed"; resume at the epoch boundary with
                    # the historical fold-in rng (stream position is intact,
                    # only the masking/augment draw order differs).
                    start_epoch = min(ck_step, config.epochs)
                    resume_global_step = int(state.step)  # ldt: ignore[LDT1704] -- one-off resume-cursor read at startup, before the step loop exists
                    rng = jax.random.fold_in(rng, start_epoch)

    # Preemption handling: SIGTERM (k8s eviction, TPU maintenance) sets a
    # flag the step loop polls — the in-flight step finishes, an emergency
    # checkpoint is awaited, the placement ring drains, and train() returns
    # normally (exit 0). The deterministic chaos harness (utils/chaos.py,
    # LDT_CHAOS env) drives the same paths at an exact step for tests/CI.
    from .utils.chaos import StepTrace, TrainerChaos
    from .utils.signals import PreemptionHandler

    # Parse chaos/trace BEFORE installing the handler: a malformed
    # LDT_CHAOS spec raises by design, and must not leak a hijacked
    # SIGTERM disposition behind it.
    chaos = TrainerChaos.from_env()
    trace = StepTrace.from_env()
    preempt = PreemptionHandler().install()
    if chaos is not None:
        chaos.drain_cb = preempt.request
    journal = _CkptJournal(resume_global_step)

    profiling = False

    # Telemetry scrape surface (--metrics_port): process 0 serves the
    # process-wide registry — StepTimer's trainer_* histograms, any
    # RemoteLoader's svc_*/lineage_* series, pipeline_* batch ages — plus a
    # /healthz liveness body, for the lifetime of the run.
    exporter = None
    slo_tracker = None  # SLO burn-down gauges, started with the exporter
    worker_pool = None
    batch_cache = None
    folder_fp = None  # folder-corpus fingerprint, computed once per run
    tuner = None
    run_exc: Optional[BaseException] = None
    # Exporter, worker pool, cache and autotuner, then the first loader up
    # to its first next(): one phase (the pools take 2-12 ms where none is
    # asked for; a worker pool's spawn shows here).
    obs_phase("startup.loader")
    try:
        # Everything that can fail lives inside the try — a bind failure on
        # the exporter port, the metrics_port log write, or a pool-spawn
        # error must all still run the finally (logger/ckpt close, and the
        # exporter's bound port once started).
        logger.log({**device_info, **_kernel_paths(task, config)},
                   to_wandb=False)
        if config.metrics_port is not None and jax.process_index() == 0:
            from .obs.http import MetricsHTTPServer

            from .obs.slo import SLOTracker

            def _lineage_p99(name: str):
                def probe() -> float:
                    hist = default_registry().get(name)
                    if hist is None:
                        return float("nan")  # no traffic yet: skipped
                    return hist.percentile(99)
                return probe

            slo_tracker = SLOTracker(
                probes={
                    "batch_age_p99_ms": _lineage_p99("lineage_batch_age_ms"),
                    "queue_wait_p99_ms": _lineage_p99(
                        "lineage_queue_wait_ms"
                    ),
                },
            ).start()
            exporter = MetricsHTTPServer(
                default_registry(),
                port=config.metrics_port,  # 0 = ephemeral, as serve-data
                host=config.metrics_host,
                healthz_fn=lambda: {"role": "trainer",
                                    "run_name": config.run_name,
                                    "steps": timer.steps,
                                    "slo": slo_tracker.status()},
            ).start()
            logger.log({"metrics_port": exporter.port}, to_wandb=False)
        if not (config.data_service_addr or config.coordinator_addr):
            worker_pool = _make_worker_pool(config, dataset, mesh)
            if config.batch_cache:
                # Epoch-coherent batch cache (--batch_cache): ONE tiered
                # RAM/disk cache for the whole run — the epoch loop
                # rebuilds loaders, the cache outlives them, which is the
                # entire point (epoch >= 2 hits what epoch 1 filled).
                # Remote arms skip it: the cache lives server-side there
                # (ServeConfig.batch_cache), where the decode boundary is.
                from .data.cache import BatchCache

                batch_cache = BatchCache(
                    cache_dir=config.cache_dir,
                    ram_budget_mb=config.cache_ram_budget_mb,
                    disk_budget_mb=config.cache_disk_budget_mb,
                    buffer_pool=_loader_buffer_pool(config),
                )
                if config.data_format == "folder":
                    # Folder-corpus identity, ONCE per run: the loaders
                    # (train, rebuilt per epoch) and every eval-loader
                    # rebuild reuse this instead of re-walking + re-
                    # hashing the tree — on a million-file corpus that
                    # stat+sha sweep per epoch is the churn the r13
                    # satellite exists to prevent.
                    from .data.authoring import _folder_samples
                    from .data.cache import folder_fingerprint

                    folder_fp = folder_fingerprint(
                        _folder_samples(config.dataset_path)[0]
                    )
        if config.autotune:
            # Closed-loop pipeline autotuning (tune/): one controller for
            # the whole run; the epoch loop re-registers each rebuilt
            # loader's knobs. Reads the process registry the exporter
            # already serves, so autotune_* series ride /metrics for free.
            from .tune import AutoTuner

            tuner = AutoTuner(
                interval_s=config.autotune_interval_s,
            ).start()
        results = _train_loop(
            config, dataset, val_dataset, mesh, state, rng, train_step,
            eval_step, logger, timer, worker_pool, ckpt, start_epoch,
            total_start, n_devices, results, global_step, profiling,
            index_pool, lr_schedule_fn(config, total_steps), val_pool,
            resume_epoch_step=resume_epoch_step,
            resume_global_step=resume_global_step,
            preempt=preempt, chaos=chaos, trace=trace, journal=journal,
            tuner=tuner, batch_cache=batch_cache, folder_fp=folder_fp,
        )
        results.update(device_info)
        return results
    except BaseException as exc:
        run_exc = exc
        raise
    finally:
        if config.profile_dir:
            try:  # stop a trace left open by a mid-window exception
                jax.profiler.stop_trace()
            except Exception:
                pass
        if tuner is not None:
            # Before the worker pool: a controller mid-tick must not
            # actuate a resize against a pool that is shutting down.
            tuner.stop()
        if slo_tracker is not None:
            slo_tracker.stop()
        if exporter is not None:
            exporter.stop()
        if worker_pool is not None:
            worker_pool.shutdown()
        if batch_cache is not None:
            # After the loaders are down (the loop exited; producers
            # drained): releases the RAM ring's BufferPool leases. The
            # disk tier stays — it is what makes a restarted run warm.
            batch_cache.close()
        try:
            if ckpt is not None:
                # The crash-path save gap (r8): a preempted OR crashed run
                # must persist its last completed step — AWAITED — before
                # the process exits; ckpt.close() additionally waits out
                # any periodic save still committing in the background.
                try:
                    if journal.dirty and (journal.preempted
                                          or run_exc is not None):
                        if ckpt.save(journal.abs_step, journal.state,
                                     cursor=journal.make_cursor(),
                                     wait=True):
                            journal.saved_step = journal.abs_step
                finally:
                    ckpt.close()
        except Exception:
            # A failed emergency save must fail a SIGTERM drain loudly
            # (never exit 0 claiming a checkpoint it didn't take) — but on
            # the crash path it must not mask the original run exception.
            if run_exc is None:
                raise
        finally:
            # Teardown that must survive a failed save: the process-wide
            # SIGTERM disposition, the trace file, and the metric sinks.
            preempt.uninstall()
            if trace is not None:
                trace.close()
            logger.close()


class _StepStats:
    """A task's step stats, from the scalars the step returns beside the
    loss (``Task.stats``) to ``obs/registry`` under their own names: a
    ``*_total`` is a counter, summed on the device over the steps since the
    last log point (``moe_assignments_total``: every step's token-to-expert
    assignments; ``mlm_selected_tokens_total`` / ``mlm_head_fallback_total``:
    the masked positions, and the steps whose batch overflowed the gathered
    head); any other name is a gauge of the step just logged, which rides
    the log line too (``moe_expert_load_max`` / ``_mean``,
    ``mlm_head_capacity_tokens``, ``mlm_head_fill_pct``). All of them come
    to the host in one array: ``pack`` enqueues its program right behind the
    log point's step, ahead of the next one, so it is ready when that step's
    loss is, and ``publish`` fetches it after the loss fetch has waited for
    the step: nothing here waits for anything enqueued later."""

    def __init__(self):
        self._sums = None
        self._last = None

    def add(self, stats) -> None:
        sums = {k: v for k, v in stats.items() if k.endswith("_total")}
        if self._sums is not None:
            sums = {k: self._sums[k] + v for k, v in sums.items()}
        self._sums, self._last = sums, stats

    def pack(self):
        """At a log point, its step just dispatched: the sums up to it and
        its gauges go into one device array (one program, no wait), and the
        sums start anew with the next step. Returns what ``publish`` takes;
        None for a task with nothing to report."""
        if not self._last:
            return None
        names = list(self._sums) + [n for n in self._last
                                    if n not in self._sums]
        packed = _pack_scalars(
            [self._sums.get(n, self._last[n]) for n in names])
        counters = len(self._sums)  # the names' head: the rest are gauges
        self._sums = self._last = None
        return names, counters, packed

    @staticmethod
    def publish(packed, entry: dict) -> None:
        if packed is None:
            return
        names, counters, values = packed
        registry = default_registry()
        # one array, one fetch (a round trip a scalar was 6 ms each on the
        # v5e's host), of a program that ran right behind the step whose
        # loss the loop has just fetched
        values = np.asarray(values).tolist()  # ldt: ignore[LDT1704] -- log-point fetch of scalars packed behind the step the drain waited for
        for i, (name, value) in enumerate(zip(names, values)):
            if i < counters:
                registry.counter(name).inc(value)
            else:
                registry.gauge(name).set(value)
                entry[name] = round(value, 4)


@jax.jit
def _pack_scalars(scalars):
    return jnp.stack([jnp.asarray(x, jnp.float32) for x in scalars])


class _StepsInFlight:
    """The steps the loop has dispatched and not yet seen finished, counted
    without a wait: the loss array of each, oldest first, asked
    ``is_ready()`` (no block, no transfer) where a ``train.step`` phase
    begins and where it ends. Counter ``train_steps_dispatched_total``;
    counter ``train_dispatch_starved_total``: steps whose dispatch began
    with nothing in flight although the loop had not just emptied the queue
    itself (a ``train.drain`` that found no step to follow, an epoch's
    start, the sampled transform await), so the chips had run dry because
    the host was late; gauge ``train_steps_in_flight_max``: the most in
    flight after a dispatch since the last log point, which is how a run
    learns the runtime's limit. A ``train.drain`` waits for the step its
    drain point is for, never for a newer one, and counts in one of two:
    ``train_drain_ahead_total``, the next step was already dispatched and
    is in flight when the wait returns (the steady state: the step after it
    begins with ``in_flight`` 1), and ``train_drain_empty_total``, the
    drained step was the newest, so the queue is empty (the epoch's last
    step, ``max_steps``, a due checkpoint, a preemption, a chaos hook)."""

    def __init__(self):
        registry = default_registry()
        self._pending: deque = deque()
        self._emptied = True  # the run's first step finds an empty queue
        self._dispatched = registry.counter("train_steps_dispatched_total")
        self._starved = registry.counter("train_dispatch_starved_total")
        self._drains = {
            ahead: registry.counter(f"train_drain_{name}_total")
            for ahead, name in ((True, "ahead"), (False, "empty"))
        }
        self._max_gauge = registry.gauge("train_steps_in_flight_max")
        self._max_after = 0
        self._min_began = None

    def _poll(self) -> int:
        pending = self._pending
        while pending and pending[0].is_ready():
            pending.popleft()
        return len(pending)

    def began(self) -> int:
        """A step's dispatch begins: the steps still running or queued."""
        n = self._poll()
        if not self._emptied:
            if n == 0:
                self._starved.inc()
            if self._min_began is None or n < self._min_began:
                self._min_began = n
        self._emptied = False
        self._dispatched.inc()
        return n

    def dispatched(self, loss) -> int:
        """The dispatch has returned ``loss``: the steps in flight now."""
        self._pending.append(loss)
        n = self._poll()
        self._max_after = max(self._max_after, n)
        return n

    def emptied(self) -> None:
        """The loop has just waited for the newest thing it dispatched, so
        every step before it is done: the next dispatch finds the queue
        empty by the loop's own doing."""
        self._pending.clear()
        self._emptied = True

    def drained(self, ahead: bool) -> None:
        """A ``train.drain`` has returned. ``ahead``: a newer step was
        dispatched before the wait and is still the device's to run, so the
        queue is not empty and a dispatch that finds it so is starved."""
        self._drains[ahead].inc()
        if not ahead:
            self.emptied()

    def publish(self, entry: dict):
        """At a log point: the gauge rides the log line and the interval's
        extremes start again. Returns the fewest in flight where a dispatch
        began, over the interval's steps before which the loop had not
        emptied the queue itself; None if it had none."""
        self._max_gauge.set(self._max_after)
        entry["train_steps_in_flight_max"] = self._max_after
        fewest, self._max_after, self._min_began = self._min_began, 0, None
        return fewest


@dataclasses.dataclass
class _DrainPoint:
    """The step a drain point is for, held from its dispatch until the loop
    has waited for it: what its ``train.drain`` fetches and what its
    progress record is written from, while ``loss`` and ``gnorm`` in the
    loop already name the next step's."""

    step: int  # steps done with it: the record's "step"
    loss: Any
    gnorm: Any
    stats: Any  # ``_StepStats.pack()``'s, enqueued behind the step
    log: bool  # a log point; else a ``sync_every`` drain alone


def _host_device():
    """The CPU backend's device of this process, where the loop evaluates
    telemetry it must not enqueue on the accelerator; None where the
    process has no CPU backend (``JAX_PLATFORMS`` names the accelerator
    alone), which leaves such a value to the default device."""
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return None


class _SlowIntervals:
    """A log interval that took over ``FACTOR`` times the run's median
    seconds a step leaves a record, traced or not: counter
    ``log_interval_slow_total`` and one ``slow_interval`` line through the
    logger, its ``by_span`` summed from the tracer's ring (which every run
    keeps) over the loop thread's phases and the ``loop.*`` spans under
    them since the last log point. The median is over the earlier
    intervals of the same kind, the last ``KEEP`` of them, and an interval
    is judged from the third of its kind on: one that holds an epoch
    turnover is held against those that held one (where ``log_every`` is an
    epoch, every interval does). An interval in which something compiled is
    judged like any other, its line says how many ``compiles``, and it is
    left out of the median. Runs at log points only and reads what is
    recorded anyway."""

    FACTOR = 1.5
    KEEP = 101
    clock = staticmethod(time.monotonic_ns)  # the spans' clock

    def __init__(self):
        registry = default_registry()
        self._slow = registry.counter("log_interval_slow_total")
        self._compiles = registry.counter("xla_compiles_total")
        self._earlier = {False: deque(maxlen=self.KEEP),
                         True: deque(maxlen=self.KEEP)}
        self._mark = None  # the last log point

    def check(self, step: int, epoch: int, in_flight_min, logger) -> None:
        tracer = default_tracer()
        mark = (self.clock(), step, epoch, self._compiles.value,
                tracer.dropped)
        last, self._mark = self._mark, mark
        if last is None or step <= last[1]:
            return
        seconds = (mark[0] - last[0]) / 1e9
        steps = step - last[1]
        compiles = int(mark[3] - last[3])
        earlier = self._earlier[epoch != last[2]]
        median = statistics.median(earlier) if len(earlier) >= 2 else None
        if not compiles:
            earlier.append(seconds / steps)
        if median is None or seconds / steps <= self.FACTOR * median:
            return
        self._slow.inc()
        thread = threading.get_ident() % 2**31
        by_span: dict = {}
        for s in tracer.spans():
            if s.thread_id == thread and s.start_ns >= last[0] \
                    and s.name.startswith(("train.", "loop.")):
                by_span[s.name] = by_span.get(s.name, 0.0) \
                    + (s.end_ns - s.start_ns) / 1e9
        logger.log({"slow_interval": {
            "steps": [last[1], step], "seconds": round(seconds, 6),
            "median_seconds": round(median * steps, 6),
            "by_span": {k: round(v, 6) for k, v in sorted(by_span.items())},
            "in_flight_min": in_flight_min, "compiles": compiles,
            "spans_dropped": mark[4] - last[4],
        }}, to_wandb=False)


def _union_s(intervals) -> float:
    """Seconds covered by ``(thread, start_ns, end_ns)`` intervals: the union
    on each thread, summed over threads. Traces nest (a ``jit`` met inside
    another's trace), so their durations may not simply be added."""
    total, reach = 0, {}
    for thread, start, end in sorted(intervals):
        start = max(start, reach.get(thread, start))
        if end > start:
            total += end - start
            reach[thread] = end
    return total / 1e9


class _StartupRecord:
    """One ``startup`` line a run, traced or not: where the time from the
    process's start to the end of the first ``train.step`` went. Summed once,
    when that step's dispatch has returned, from what the tracer's ring holds
    anyway (as ``_SlowIntervals`` does), and written at the first log point:

    * ``process_age_s`` at ``train()``'s entry and the compile cache found
      there (the attributes of the ``startup.devices`` phase);
    * ``phases``: seconds of the loop thread's phases from entry to the end
      of the first ``train.step``, by name; they tile, so they add up to
      ``entry_to_first_step_s``;
    * ``in_train`` and ``before_train`` (what the process did before it
      entered ``train()``: a caller's own programs): seconds of tracing,
      lowering, compiling and loading from the cache, each the union of its
      spans on each thread, and how many programs hit, missed or went past
      the cache;
    * ``programs``: everything of ``PROGRAM_S`` or more, in order, with
      ``at_s`` from entry (negative: before ``train()``)."""

    PROGRAM_S = 0.5
    KINDS = {"jax.trace": "trace", "jax.lower": "lower",
             "xla.compile": "compile"}

    def __init__(self):
        self.due = True  # the first train.step has not returned yet
        self._line = None

    def first_step_done(self) -> None:
        self.due = False
        tracer = default_tracer()
        thread = threading.get_ident() % 2**31
        spans = tracer.spans()
        own = [s for s in spans if s.thread_id == thread and s.parent_id == 0
               and s.name.startswith(("startup.", "train."))]
        entries = [s for s in own if s.name == "startup.devices"]
        if not entries:
            return  # the ring is too short for this start-up: no record
        entry = entries[-1]
        own = [s for s in own if s.start_ns >= entry.start_ns]
        end = next(s.end_ns for s in own if s.name == "train.step")
        own = [s for s in own if s.end_ns <= end]
        phases: dict = {}
        for s in own:
            phases[s.name] = phases.get(s.name, 0) + s.end_ns - s.start_ns
        programs = [s for s in spans if s.name in self.KINDS
                    and s.start_ns < end]
        before = [s for s in programs if s.end_ns <= entry.start_ns]
        attrs = dict(entry.attrs or {})
        self._line = {"startup": {
            "process_age_s": attrs.pop("process_age_s", None),
            "entry_to_first_step_s": round((end - entry.start_ns) / 1e9, 6),
            "phases": {k: round(v / 1e9, 6) for k, v in phases.items()},
            "in_train": self._sums(
                [s for s in programs if s.end_ns > entry.start_ns],
                entry.start_ns, end),
            "before_train": self._sums(before, 0, entry.start_ns),
            "programs": [
                {"fun_name": s.attrs["fun_name"], "kind": self.KINDS[s.name],
                 **({"cache": s.attrs["cache"]} if "cache" in s.attrs
                    else {}),
                 "seconds": round((s.end_ns - s.start_ns) / 1e9, 3),
                 "at_s": round((s.start_ns - entry.start_ns) / 1e9, 3)}
                for s in sorted(programs, key=lambda s: s.start_ns)
                if s.end_ns - s.start_ns >= self.PROGRAM_S * 1e9],
            "cache": {k[len("cache_"):]: v for k, v in attrs.items()
                      if k.startswith("cache_")},
            "spans_dropped": tracer.dropped,
        }}

    @staticmethod
    def _sums(programs, lo: int, hi: int) -> dict:
        def seconds(spans) -> float:
            return round(_union_s((s.thread_id, max(s.start_ns, lo),
                                   min(s.end_ns, hi)) for s in spans), 6)

        compiles = [s for s in programs if s.name == "xla.compile"]
        loaded = [s for s in compiles if s.attrs["cache"] == "hit"]
        answers = [s.attrs["cache"] for s in compiles]
        return {
            "trace_s": seconds(s for s in programs if s.name == "jax.trace"),
            "lower_s": seconds(s for s in programs if s.name == "jax.lower"),
            "compile_s": seconds(s for s in compiles
                                 if s.attrs["cache"] != "hit"),
            "cache_load_s": seconds(loaded),
            "hits": len(loaded), "misses": answers.count("miss"),
            "off": answers.count("off"),
        }

    def write(self, logger) -> None:
        line, self._line = self._line, None
        if line is not None:
            logger.log(line, to_wandb=False)


def _train_loop(config, dataset, val_dataset, mesh, state, rng, train_step,
                eval_step, logger, timer, worker_pool, ckpt, start_epoch,
                total_start, n_devices, results, global_step, profiling,
                index_pool=None, lr_fn=None, val_pool=None, *,
                resume_epoch_step=0, resume_global_step=0, preempt=None,
                chaos=None, trace=None, journal=None, tuner=None,
                batch_cache=None, folder_fp=None):
    known = dict(results)  # before the first step: attention_fused
    if journal is None:
        journal = _CkptJournal(resume_global_step)
    step_stats = _StepStats()
    flight = _StepsInFlight()
    slow = _SlowIntervals()
    startup = _StartupRecord()
    # Device-decode transform stage (--device_decode): one jitted kernel
    # call replacing a batch's coefficient pages with the decoded image —
    # device work dispatched from the consumer thread, so it overlaps the
    # previous step's compute exactly like the H2D ring does. Its dispatch
    # is the train.transform phase (the device cost itself lands inside
    # the step's execution window on async backends). Pixel batches
    # (the --no_device_decode arm) pass through, so one handle covers both
    # arms. Applied BEFORE the device_cache fill: the cache then holds
    # finished image batches, decoding each coefficient page exactly once
    # per run.
    transform = None
    device_ms_hist = None
    probe_key = "image"  # leaf the sampled transform-await fetches from
    if config.token_pack:
        # Ragged token plane: the pack kernel (ops/token_device.py)
        # scatters values/offsets pages into packed (rows, L) slabs with
        # segment/position ids — the text-path twin of the device-decode
        # stage below (mutually exclusive by task type). Padded batches
        # (the control arm, and every eval loader) pass through whole.
        from .ops.token_device import make_pack_transform

        # Packed grids come out of the replicated-input kernel replicated;
        # re-lay them onto the data axis so the step's in_shardings accept
        # them (the planner's rows_align makes the row count divide).
        transform = make_pack_transform(
            batch_sharding=batch_sharding(mesh) if mesh is not None else None
        )
        device_ms_hist = default_registry().histogram("pack_device_ms")
        probe_key = "input_ids"
    if config.device_decode:
        from .ops.jpeg_device import make_batch_transform

        transform = make_batch_transform(config.image_size)
        # decode_device_ms: the kernel's REAL device cost, sampled — every
        # 16th batch the transform is awaited to completion and timed (one
        # sync per 16 steps; the other 15 stay fully async). This is what
        # feeds the autotuner's decode_split attribution and the /metrics
        # series the CI smoke scrapes.
        device_ms_hist = default_registry().histogram("decode_device_ms")
        _eval_raw = eval_step

        def eval_step(state, batch, _inner=_eval_raw, _tx=transform):
            # Eval loaders share the decoder, so their batches carry
            # coefficient pages too (plus _weight, which passes through).
            return _inner(state, _tx(batch))
    # HBM replay tier (--device_cache): epoch-``start`` batches kept on
    # device, replayed afterwards — the fill/replay/size-guard/partial-
    # epoch-exclusion rules now live in the cache plane
    # (data/cache.DeviceReplayCache) next to the host tiers', not as a
    # bespoke list here. See TrainConfig.device_cache.
    from .data.cache import DeviceReplayCache

    dev_cache = DeviceReplayCache(
        enabled=config.device_cache,
        budget_gb=config.device_cache_gb,
        seed=config.seed,
    )
    history: list = []  # per-epoch metrics, returned as results["history"]
    # Schedule position survives resume inside the restored optimizer state;
    # the lr telemetry must count from there, not from this run's step 0.
    base_step = int(state.step)  # ldt: ignore[LDT1704] -- one-off schedule-position read before the loop starts
    trace_done = False  # one profiler window per run
    # Eval-loader selection, shared by eval_every and eval_at_end.
    # Pool precedence: val_fraction split → train pool (eval over the train
    # loader) → a val dataset resolves its OWN filter pool via the fallback
    # in _build_eval_loader. (Eval decodes on producer threads, never the
    # train worker pool — pools are bound to the TRAIN dataset URI.)
    eval_dataset = val_dataset if val_dataset is not None else dataset
    eval_pool = (
        val_pool if val_pool is not None
        else index_pool if val_dataset is None
        else None
    )
    stop = False  # set by max_steps; ends the epoch loop after bookkeeping
    build_loader = partial(
        _build_loader, config, dataset, mesh, workers=worker_pool,
        index_pool=index_pool, batch_cache=batch_cache, folder_fp=folder_fp,
    )
    handovers = {
        state: default_registry().counter(f"epoch_handover_{state}_total")
        for state in ("warm", "cold")
    }
    loader = None
    # A drain point's step waits here until the loop has fetched its loss:
    # at most one, and with it at most one step in flight behind a drain.
    owed: Optional[_DrainPoint] = None
    host = _host_device()

    def checkpoint_due(abs_step: int) -> bool:
        # ">= saved + N" rather than "% N" so data_echo's multi-step jumps
        # can't skip the trigger.
        return (
            ckpt is not None
            and config.checkpoint_every_steps > 0
            and abs_step >= journal.saved_step + config.checkpoint_every_steps
        )

    def nothing_may_follow(steps_done: int) -> bool:
        """Whether the step just dispatched has to be the device's last
        before the loop has seen it finish: the run stops with it, or its
        state is about to be used (the step after it would donate that
        state). Read from the loop's own variables before the next dispatch;
        the epoch's last step shows when ``next`` finds no batch."""
        return (
            0 < config.max_steps <= steps_done
            or chaos is not None  # its hook may stop or kill at any step
            or (preempt is not None and preempt.requested)
            or checkpoint_due(resume_global_step + steps_done)
        )

    def drain(point: _DrainPoint, ahead: bool) -> None:
        # The loop thread waiting for the device: for the point's own step,
        # never a newer one. ``ahead``: the next step is already dispatched
        # and runs while everything up to the dispatch after it happens.
        obs_phase("train.drain", step=point.step - 1)
        _ = float(point.loss)  # ldt: ignore[LDT1704] -- deliberate bounded drain: fetch at sync_every/log points keeps dispatch depth finite
        flight.drained(ahead)

    def progress(point: _DrainPoint) -> None:
        # Per-step progress — the reference's live tqdm it/s + loss
        # (lance_iterable.py:106,116-117). Console/JSONL only; wandb stays
        # on the per-epoch axis. Called after the point's drain, so every
        # fetch here is of something ready with the point's step (its loss,
        # its gradient norm, the stats packed behind it): with a newer step
        # in flight none of them waits for it. The wall-clock rate (not the
        # dispatch-time upper bound) leads the progress line, so it agrees
        # with the epoch metrics' wall-clock rate on async backends.
        obs_phase("train.log", step=point.step)
        with obs_span("loop.log_entry", step=point.step):
            w = timer.window(batch_size=config.batch_size)
            wt = w["loader_s"] + w["step_s"]
            entry = {
                "step": point.step,
                "epoch": epoch,
                "loss": round(float(point.loss), 4),  # ldt: ignore[LDT1704] -- log-interval telemetry fetch of the already-drained scalar
                "images_per_sec": w["images_per_sec_wall"],
                "images_per_sec_dispatch": w["images_per_sec_dispatch"],
                "loader_stall_pct": (
                    100.0 * w["loader_s"] / wt if wt else 0.0
                ),
            }
            if "placement_h2d_s" in w:
                # H2D dispatch time this window (runs on the placement
                # thread, overlapping the step) as a share of the same
                # loader+step denominator — the transfer cost the pre-r7
                # accounting folded invisibly into loader_stall_pct.
                entry["h2d_pct"] = (
                    100.0 * w["placement_h2d_s"] / wt if wt else 0.0
                )
            # Data-service windows (RemoteLoader counters attached to the
            # timer): svc_client_stall_s, …
            entry.update({
                k: round(v, 4) if isinstance(v, float) else v
                for k, v in w.items() if k.startswith("svc_")
            })
        if lr_fn is not None:
            # Schedules count optimizer updates, not micro-steps; base_step
            # carries the restored position across resume. Telemetry, taken
            # on the host: the schedule's few eager programs run on the CPU
            # backend, so nothing queues behind the step in flight.
            updates = (base_step + point.step) // max(config.grad_accum, 1)
            with obs_span("loop.log_lr", step=point.step), \
                    jax.default_device(host):
                entry["lr"] = float(
                    lr_fn(updates) if callable(lr_fn) else lr_fn
                )
        if point.gnorm is not None:
            entry["grad_norm"] = round(float(point.gnorm), 4)  # ldt: ignore[LDT1704] -- log-interval divergence telemetry, rides the loss drain
        with obs_span("loop.stats_fetch", step=point.step):
            step_stats.publish(point.stats, entry)
        in_flight_min = flight.publish(entry)
        if "attention_fused" in known:
            entry["attention_fused"] = known["attention_fused"]
            # the steps so far were traced: each splash kernel they built
            # says once what tiling it runs
            for line in splash_tilings_built():
                logger.log(line, to_wandb=False)
        for name in _EXPERT_GAUGES:
            if name in known:
                entry[name] = default_registry().gauge(name).value
        if config.data_echo > 1:
            # The windowed rate counts echoed steps; report the unique-data
            # rate next to it (as the epoch metrics do) so the live stream
            # is never silently inflated.
            entry["data_echo"] = config.data_echo
            entry["unique_images_per_sec"] = (
                entry["images_per_sec"] / config.data_echo
            )
        with obs_span("loop.log_write", step=point.step):
            logger.log(entry, to_wandb=False)
        slow.check(point.step, epoch, in_flight_min, logger)
        startup.write(logger)
        obs_phase("train.bookkeep")

    def settle(point: _DrainPoint, ahead: bool) -> None:
        drain(point, ahead)
        if point.log:
            progress(point)
        else:
            obs_phase("train.bookkeep")

    for epoch in range(start_epoch, config.epochs):
        # Mid-epoch resume cursor: batches of THIS epoch already consumed
        # by the checkpointed run (first epoch after a restart only).
        resume_step = resume_epoch_step if epoch == start_epoch else 0
        if epoch > start_epoch:
            # The run's first loader build belongs to startup.loader; later
            # ones are the second half of an epoch turnover.
            obs_phase("train.epoch_start", epoch=epoch)
        replay_it = dev_cache.replay_iter(
            epoch, start_epoch,
            shuffled=config.shuffle or config.loader_style == "map",
        )
        replay = replay_it is not None
        # Partial-epoch exclusion (PR 7) lives in the cache plane now: a
        # resumed epoch never seeds the replay set.
        filling = dev_cache.start_fill(replay, resume_step)
        if replay:
            it = replay_it
            loader = None
        else:
            # Epoch handover (data/placement.py): the previous epoch's ring
            # may already be reading this epoch's loader, and then its
            # first batches are placed. Otherwise (first epoch, after a
            # replay) the pipeline starts cold here.
            loader = (
                loader.take_successor() if loader is not None else None
            ) or build_loader(epoch=epoch)
            if resume_step:
                # Position the loader at the cursor: the rebuilt plan is
                # deterministic, so the tail it serves is bit-identical to
                # what the uninterrupted run would have consumed.
                loader.load_state_dict({"epoch": epoch, "step": resume_step})
            it = iter(loader)
            # What this epoch's ring reads next. It gets nothing, and ends
            # with the epoch, in the last epoch, in one that max_steps
            # ends, and before a device_cache replay (which has no loader).
            ends_run = epoch + 1 >= config.epochs or (
                0 < config.max_steps <= global_step
                + (len(loader) - resume_step) * max(config.data_echo, 1)
            )
            if not ends_run and not dev_cache.expects_replay():
                loader.set_successor(partial(build_loader, epoch=epoch + 1))
        # RemoteLoader exposes ServiceCounters: merge its stall/queue window
        # into per-step progress lines so loader-stall% stays attributable
        # (client receive stall vs server queue vs H2D vs device), next to
        # the placement plane's counters (placement_h2d_s → the h2d_pct
        # progress field). None (a device_cache replay epoch) detaches.
        timer.attach_counters(
            loader.counters if loader is not None else None,
            loader.placement_counters if loader is not None else None,
        )
        if tuner is not None:
            # Register this epoch's live knobs (the loader is rebuilt per
            # epoch; the controller outlives it). Replay epochs
            # (device_cache) have no pipeline to tune — empty the set so a
            # stale epoch's knobs are never actuated.
            from .tune import collect_tunables

            tuner.set_tunables(collect_tunables(
                loader, worker_pool, _loader_buffer_pool(config),
                batch_cache,
            ) if loader is not None else [])
        timer.reset()
        epoch_start = time.perf_counter()
        loss_sum = jnp.zeros((), jnp.float32)  # stays on device all epoch
        epoch_step = 0
        epoch_batches = resume_step  # host batches consumed this epoch
        while True:
            timer.loader_start()
            obs_phase("train.loader", step=global_step,
                      epoch_step=epoch_step)
            batch = next(it, None)
            if batch is None:
                timer.loader_stop()
                if owed is not None:
                    # the epoch's last step was a drain point: none follows
                    settle(owed, ahead=False)
                    owed = None
                obs_phase("train.epoch_end", epoch=epoch)
                break
            obs_phase("train.bookkeep")
            timer.loader_stop()
            if transform is not None:
                # Coefficient pages → image, on device (dispatch-timed;
                # async backends execute it inside the step window).
                sample = epoch_batches % 16 == 0
                raw = batch
                obs_phase("train.transform", step=global_step)
                t0 = time.monotonic_ns() if sample else 0
                with obs_span("loop.transform_dispatch", step=global_step):
                    batch = transform(raw)
                if sample and batch is not raw:
                    if probe_key in batch:
                        # Await the sampled kernel run so the device-cost
                        # histogram records execution, not dispatch. Waiting
                        # on the leaf compiles nothing and copies nothing
                        # (an element fetch compiled a slice per grid shape,
                        # inside the window it measured). Degraded/padded
                        # batches pass through `raw` unchanged and are
                        # never sampled. Every earlier step is done by then:
                        # the wait empties the queue as a drain does.
                        with obs_span("loop.transform_await",
                                      step=global_step):
                            jax.block_until_ready(batch[probe_key])
                        flight.emptied()
                    if global_step > 0:
                        # Skip the run's first sample: it pays the kernel's
                        # XLA compile, which would dominate the histogram's
                        # p50 and skew the autotuner's decode_split toward
                        # device_transform_bound on cold starts.
                        device_ms_hist.observe(
                            (time.monotonic_ns() - t0) / 1e6)
                obs_phase("train.bookkeep")
            epoch_batches += 1
            if filling:
                refused = dev_cache.admit(batch, len(loader))
                if refused is not None:
                    # First-batch projection over budget: the cache plane
                    # disabled itself; report why, keep streaming.
                    filling = False
                    logger.log(
                        {
                            "device_cache": "disabled",
                            "projected_per_device_gb": round(
                                refused["projected"] / 1e9, 3
                            ),
                            "limit_per_device_gb": round(
                                refused["budget"] / 1e9, 3
                            ),
                        },
                        to_wandb=False,
                    )
            if (
                config.profile_dir
                and epoch == start_epoch
                and jax.process_index() == 0
            ):
                # Trace a post-compile window of the first epoch: from the
                # first host batch at epoch_step >= 2 until epoch_step >= 12
                # (or epoch end). Step 0/1 are compile+warmup noise.
                # Threshold comparisons + a one-shot flag, not equality or a
                # half-open range: with data_echo > 1 epoch_step advances by
                # the echo factor per host batch and can step over any
                # single value — or the whole [2, 12) window when echo >= 12.
                if epoch_step >= 2 and not profiling and not trace_done:
                    jax.profiler.start_trace(config.profile_dir)
                    profiling = True
                elif profiling and epoch_step >= 12:
                    jax.profiler.stop_trace()
                    profiling = False
                    trace_done = True
            for _echo in range(max(config.data_echo, 1)):
                # Data echoing: each echo re-splits the rng, so on-device
                # augmentation / MLM masking differ between echoes of the
                # same host batch (TrainConfig.data_echo).
                with obs_span("loop.rng_split", step=global_step):
                    rng, step_rng = jax.random.split(rng)
                timer.step_start()
                # dispatch only
                at_step = obs_phase("train.step", step=global_step)
                at_step["in_flight"] = flight.began()
                state, loss, *extras = train_step(state, batch, step_rng)
                at_step["in_flight_after"] = flight.dispatched(loss)
                gnorm = extras.pop(0) if config.log_grad_norm else None
                obs_phase("train.bookkeep")
                if startup.due:
                    startup.first_step_done()
                with obs_span("loop.loss_sum", step=global_step):
                    loss_sum = loss_sum + loss
                with obs_span("loop.stats_add", step=global_step):
                    step_stats.add(extras[0])
                if owed is not None:
                    # The step after the owed one is in the queue: the
                    # device has it to run while the loop waits for the owed
                    # one, writes its line and goes on to the next dispatch.
                    # Until then the loop waits for nothing enqueued behind
                    # the step just dispatched.
                    settle(owed, ahead=True)
                    owed = None
                # Bound the async dispatch queue (each in-flight step pins
                # its global batch on device) — independent of logging, so
                # neither log_every=0 nor a huge log_every can unbound
                # device memory. A drain point's step is waited for (a
                # scalar value fetch, which hands the log line its loss in
                # the same D2H) once the step after it is dispatched, so
                # the wait leaves one step in flight and not an empty
                # queue; here and now only where nothing may follow it. Log
                # points are drain points too (log_every may exceed or not
                # divide sync_every). Either way the wait lands INSIDE a
                # timed step segment (all but the one for an epoch's last
                # step, which the loop owes until ``next`` finds no batch),
                # and both edges of a progress window lie behind the same
                # one step in flight, so the window's rate stays honest.
                sync_every = min(config.log_every or 50, 50)
                log_point = bool(config.log_every) and (
                    (global_step + 1) % config.log_every == 0
                )
                last = None  # a drain point after which nothing may follow
                if (global_step + 1) % sync_every == 0 or log_point:
                    packed = None
                    if log_point:
                        # ahead of the next step in the device's queue
                        with obs_span("loop.stats_pack", step=global_step):
                            packed = step_stats.pack()
                    point = _DrainPoint(global_step + 1, loss, gnorm, packed,
                                        log_point)
                    if nothing_may_follow(global_step + 1):
                        drain(point, ahead=False)
                        obs_phase("train.bookkeep")
                        last = point
                    else:
                        owed = point
                timer.step_stop()
                global_step += 1
                epoch_step += 1
                if trace is not None:
                    # Resume-fidelity instrument (LDT_STEP_TRACE_PATH):
                    # absolute step + batch hash + loss, compared step-for-
                    # step against a control arm by the chaos harness.
                    trace.record(resume_global_step + global_step, epoch,
                                 batch, loss)
                if 0 < config.max_steps <= global_step:
                    stop = True
                if last is not None and last.log:
                    progress(last)
                if stop:
                    break
            # Step boundary: the journal always pairs the post-step model
            # state with the cursor naming the NEXT batch (the loader's
            # state_dict reads "batches handed out", which at this point
            # equals batches consumed — see the data/pipeline.py contract).
            with obs_span("loop.cursor", step=global_step):
                journal.state = state
                journal.rng = rng
                journal.abs_step = resume_global_step + global_step
                if loader is not None:
                    cursor_base = dict(loader.state_dict())
                    cursor_base.setdefault("epoch", epoch)
                else:
                    # device_cache replay arm: the cached stream is the
                    # FROZEN epoch-0 batch set under a cache-local
                    # permutation — for shuffled/map configs a cacheless
                    # restart building the fresh epoch-e plan would serve a
                    # DIFFERENT set/order, so a mid-epoch cursor here would
                    # silently skip and repeat samples. Pin the epoch start
                    # instead: a restart re-runs this epoch from storage —
                    # deterministic over-training of up to one epoch, never
                    # silently lost data.
                    cursor_base = {"epoch": epoch, "step": 0}
                journal.cursor_base = cursor_base
                if checkpoint_due(journal.abs_step):
                    # Async step checkpoint (the epoch-boundary save awaits
                    # via ckpt.close()).
                    if ckpt.save(journal.abs_step, state,
                                 cursor=journal.make_cursor()):
                        journal.saved_step = journal.abs_step
                if chaos is not None:
                    chaos.on_step(global_step)
                if preempt is not None and preempt.requested and not stop:
                    # Orchestrated preemption (SIGTERM): no step follows.
                    # The steps in flight finish before the epoch's loss sum
                    # is fetched; drain the loader/placement ring below and
                    # let train()'s finally take the awaited emergency
                    # checkpoint.
                    if owed is not None:
                        # the flag came up behind the drain point's test
                        settle(owed, ahead=False)
                        owed = None
                    journal.preempted = True
                    logger.log({"preempted": True,
                                "at_step": journal.abs_step,
                                "epoch": epoch}, to_wandb=False)
                    stop = True
            if stop:
                # max_steps / preemption mid-epoch: close the loader's
                # generator so producer threads and the placement ring
                # observe the stop flag, drain, and release their
                # BufferPool leases.
                obs_phase("train.epoch_end", epoch=epoch)
                if hasattr(it, "close"):
                    it.close()
                break
        if profiling:  # epoch shorter than the trace window
            jax.profiler.stop_trace()
            profiling = False
        # Value fetch BEFORE stopping the clock, so epoch_time covers all
        # device work (and the epoch's mean loss needs the value anyway).
        loss_sum_host = float(loss_sum)  # ldt: ignore[LDT1704] -- epoch-boundary fetch: the D2H is what guarantees epoch_time covers all device work
        epoch_time = time.perf_counter() - epoch_start
        flight.emptied()  # the fetch waited for the epoch's last step
        steps = timer.steps
        epoch_metrics = {
            "epoch": epoch,
            "loss": loss_sum_host / max(steps, 1),
            "epoch_time": epoch_time,
            # Wall-clock rate (the final value fetch above makes epoch_time
            # cover ALL device work). The StepTimer sums only dispatch time
            # on async backends, so a timer-based rate overstates throughput;
            # the timer is kept solely for the host-side stall share.
            "images_per_sec": config.batch_size * steps / epoch_time
            if epoch_time > 0 else 0.0,
            "images_per_sec_per_chip": (
                config.batch_size * steps / epoch_time / n_devices
                if epoch_time > 0 else 0.0
            ),
            "loader_stall_pct": timer.loader_stall_pct,
        }
        handover = loader.handover if loader is not None else None
        if handover is not None:
            # The ring at this epoch's first next: "warm" (a batch was
            # already placed) or "cold" (the loop waited for the pipeline
            # to start).
            epoch_metrics["epoch_handover"] = handover
            handovers[handover].inc()
        # Phase-latency distribution (run-wide fixed-bucket histograms):
        # the p95/p99 tail the mean loader_stall_pct hides.
        epoch_metrics.update(timer.percentiles())
        if config.data_echo > 1:
            # Rate above counts every echoed step's batch; unique images/sec
            # is that divided by the echo factor — report both honestly.
            epoch_metrics["data_echo"] = config.data_echo
            epoch_metrics["unique_images_per_sec"] = (
                epoch_metrics["images_per_sec"] / config.data_echo
            )
        if config.eval_every and (epoch + 1) % config.eval_every == 0:
            val_loader = _build_eval_loader(
                config, eval_dataset, mesh, index_pool=eval_pool,
                batch_cache=batch_cache, folder_fp=folder_fp,
            )
            epoch_metrics["val_acc"] = evaluate(state, val_loader, eval_step)
        logger.log(epoch_metrics, step=epoch)
        history.append(dict(epoch_metrics))
        results = {**known, **epoch_metrics}
        if (
            ckpt is not None
            and (epoch + 1) % config.checkpoint_every == 0
            and not stop
        ):
            # Epoch-boundary checkpoint — step-id'd (absolute data step,
            # monotonic across restarts) with a cursor naming the next
            # epoch's first batch. A max_steps stop mid-epoch must not
            # checkpoint the partial epoch as completed — resume would
            # silently skip its remainder (preemptions go through the
            # journal's emergency path instead).
            journal.state = state
            journal.rng = rng
            journal.cursor_base = {"epoch": epoch + 1, "step": 0}
            if ckpt.save(journal.abs_step, state,
                         cursor=journal.make_cursor()):
                journal.saved_step = journal.abs_step
        if stop:
            break

    obs_phase("train.shutdown")  # final eval, then train()'s teardown
    startup.write(logger)  # a run that never came to a log point
    results["history"] = history
    for name in _EXPERT_GAUGES:
        if name in results:  # as the traced steps left it
            results[name] = default_registry().gauge(name).value
    results["steps"] = global_step  # train steps executed this run
    results["global_step"] = journal.abs_step  # absolute, across restarts
    results["total_time"] = time.perf_counter() - total_start
    results["start_epoch"] = start_epoch
    if journal.preempted:
        results["preempted"] = True
    if config.eval_at_end and not journal.preempted:
        # Final eval — over the val split when given, else over the train
        # loader as the reference does (lance_iterable.py:125-127); all
        # processes participate since eval is itself a sharded computation.
        key = (
            "val_acc"
            if (val_dataset is not None or val_pool is not None)
            else "train_acc"
        )
        loader = _build_eval_loader(
            config, eval_dataset, mesh, index_pool=eval_pool,
            batch_cache=batch_cache, folder_fp=folder_fp,
        )
        results[key] = evaluate(state, loader, eval_step)
        logger.log({key: results[key]})
    return results
