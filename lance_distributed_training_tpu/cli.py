"""CLI — the reference's argparse surface, one entry point instead of four.

Flag-for-flag parity with ``/root/reference/lance_iterable.py:136-146`` (plus
``--loader_style`` to select the map-style path that was a separate script,
``lance_map_style.py:128-148``, and TPU knobs). Topology comes from JAX
process discovery, not torchrun env vars (``lance_iterable.py:154-156``).

The subcommands share the ``ldt`` entry point:

* ``ldt train …`` (or bare flags, backward-compatible) — the trainer;
* ``ldt serve-data …`` — the disaggregated input-data service: decode on
  CPU hosts, trainers point at it with ``--data_service host:port`` (or
  join a fleet with ``--coordinator host:port``);
* ``ldt coordinator …`` — the fleet control plane: membership, shard
  leases, heartbeats for N serve-data members; trainers point at it with
  ``--coordinator host:port`` (README "Fleet");
* ``ldt jobs …`` — the job plane's operator view against a running
  coordinator: per-job priority, sessions, resume cursor, cache hit
  rate and SLO burn-down (README "Job plane");
* ``ldt check …`` — the AST-based distributed-training lint (exits
  non-zero on new findings; see README "Static analysis");
* ``ldt graph …`` — the cross-module concurrency model (spawned threads,
  locks, lock-order edges) as Graphviz DOT or a text summary;
* ``ldt trace export …`` — merge recorded span JSONLs (LDT_TRACE_PATH,
  one per process) into a Perfetto-loadable Chrome trace with
  cross-process flow arrows (see README "Causal tracing & SLOs");
* ``ldt trace critical-path …`` — per-batch dominant-segment attribution
  (decode/cache/queue-wait/wire/h2d/step) + straggler table;
* ``ldt costs report …`` — the per-item cost ledger (LDT_COST_PATH):
  totals and the slowest items by decode cost.

Usage::

    python -m lance_distributed_training_tpu.cli --dataset_path /data/food101 \
        --sampler_type batch --batch_size 512 --epochs 10 --lr 0.05

    ldt serve-data --dataset_path /data/food101 --port 8476 --num_workers 8
    ldt train --dataset_path /data/food101 --data_service cpu-host:8476
"""

from __future__ import annotations

import argparse

from .models.transformer import CAUSAL_LMS
from .trainer import TrainConfig, train


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU-native distributed training")
    p.add_argument("--dataset_path", type=str, required=True)
    p.add_argument("--val_dataset_path", type=str, default=None,
                   help="held-out split for evaluation (default: train loader)")
    p.add_argument("--val_fraction", type=float, default=0.0,
                   help=">0: carve a seeded held-out fraction of the train "
                        "dataset as the val split (map-style columnar path; "
                        "composes with --filter)")
    p.add_argument("--task_type", type=str, default="classification",
                   choices=["classification", "masked_lm", "causal_lm",
                            "contrastive"])
    p.add_argument("--num_classes", type=int, default=101)
    p.add_argument("--sampler_type", type=str, default="batch",
                   choices=["batch", "fragment", "full",
                            "sharded_batch", "sharded_fragment", "full_scan"])
    p.add_argument("--loader_style", type=str, default="iterable",
                   choices=["iterable", "map"])
    p.add_argument("--filter", type=str, default=None,
                   help="row predicate, e.g. \"label < 50\" or "
                        "\"label >= 10 & label != 13\" (map-style columnar "
                        "path; resolved to an index pool once)")
    p.add_argument("--data_format", type=str, default="columnar",
                   choices=["columnar", "folder"],
                   help="folder = the file-reading control arm (torch_version/)")
    p.add_argument("--batch_size", type=int, default=512,
                   help="GLOBAL batch size across all devices")
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--max_steps", type=int, default=0,
                   help=">0: stop after N train steps regardless of epochs "
                        "(compile check / smoke / fixed-step bench; counted "
                        "in data steps like --total_steps)")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--optimizer", type=str, default="sgd",
                   choices=["sgd", "adamw"])
    p.add_argument("--weight_decay", type=float, default=0.0)
    p.add_argument("--lr_schedule", type=str, default="constant",
                   choices=["constant", "cosine"],
                   help="cosine decays to 0 over total_steps (derived from "
                        "dataset size x epochs unless --total_steps is given)")
    p.add_argument("--warmup_steps", type=int, default=0,
                   help="linear lr warmup before the schedule")
    p.add_argument("--total_steps", type=int, default=None,
                   help="schedule horizon override")
    p.add_argument("--grad_clip", type=float, default=0.0,
                   help=">0: clip gradients by global norm")
    p.add_argument("--grad_accum", type=int, default=1,
                   help=">1: accumulate N micro-batches per optimizer update")
    p.add_argument("--num_workers", type=int, default=0)
    p.add_argument("--no_shm_workers", action="store_true",
                   help="worker-pool IPC falls back to pickling decoded "
                        "batches instead of shared-memory ring slots "
                        "(A/B control arm; shm is the default)")
    p.add_argument("--no_buffer_pool", action="store_true",
                   help="disable the recycled decode/receive buffer pool — "
                        "every batch faults a fresh allocation (pre-r6 "
                        "behavior; bufpool_* metrics stay at zero)")
    dd = p.add_mutually_exclusive_group()
    dd.add_argument("--device_decode", action="store_true",
                    help="split JPEG decode at the entropy boundary: the "
                         "host does only the Huffman/entropy half and "
                         "ships half-decoded coefficient pages; dequant + "
                         "IDCT + upsample + color + resize run as a pure "
                         "jitted device kernel fused ahead of the step "
                         "(classification only; needs the native "
                         "extractor)")
    dd.add_argument("--no_device_decode", action="store_true",
                    help="force the host pixel-decode path — the exact "
                         "r11 pipeline, the A/B control arm for "
                         "--device_decode (this is also the default)")
    tp = p.add_mutually_exclusive_group()
    tp.add_argument("--token_pack", action="store_true",
                    help="ragged token plane (text tasks): variable-length "
                         "sequences ride the pipeline as values+offsets "
                         "pages with a deterministic first-fit-decreasing "
                         "pack plan; a pure jitted kernel scatters them "
                         "into packed (rows, pack_len) slabs with segment-"
                         "masked attention ahead of the step — padding "
                         "waste becomes a measured, autotuned quantity "
                         "(pad_waste_pct on /metrics)")
    tp.add_argument("--no_token_pack", action="store_true",
                    help="force the padded token path — the exact r14 "
                         "control arm for --token_pack (this is also the "
                         "default)")
    p.add_argument("--pack_len", type=int, default=0,
                   help="packed slot-length cap (0 = --seq_len); a bounded "
                        "autotuner Tunable")
    p.add_argument("--pack_rows_multiple", type=int, default=8,
                   help="packed row-count rounding quantum: smaller = less "
                        "padding waste, more distinct compiled shapes (the "
                        "autotuner trades these live)")
    p.add_argument("--data_service", type=str, default=None, metavar="HOST:PORT",
                   help="stream decoded batches from a running `ldt "
                        "serve-data` service instead of decoding locally "
                        "(disaggregated input plane; iterable columnar path)")
    p.add_argument("--coordinator", type=str, default=None, metavar="HOST:PORT",
                   help="stream decoded batches from an elastic fleet of "
                        "`ldt serve-data` servers discovered via this `ldt "
                        "coordinator` (striped across live members, failover "
                        "at the resume cursor). Mutually exclusive with "
                        "--data_service; NOT the jax multi-host rendezvous "
                        "(--coordinator_address)")
    p.add_argument("--job_id", type=str, default=None,
                   help="declare this run's job on a shared data "
                        "service/fleet (v6 job plane): per-job resume "
                        "cursor, fairness weight and admission server-side. "
                        "Needs --data_service or --coordinator; default = "
                        "the implicit 'default' job")
    p.add_argument("--job_priority", type=str, default=None,
                   choices=["inference", "training", "bulk"],
                   help="priority class for --job_id: inference = "
                        "low-latency read-only probes that preempt bulk "
                        "scans; training (default) and bulk share capacity "
                        "by weighted-fair stride scheduling")
    p.add_argument("--no_ddp", action="store_true",
                   help="single-device debug mode (reference --no_ddp)")
    p.add_argument("--no_wandb", action="store_true")
    p.add_argument("--model_name", type=str, default=None,
                   help="default per task: resnet50 / bert_base / gpt_base / "
                        "clip_resnet50_bert; causal_lm has "
                        + ", ".join(sorted(CAUSAL_LMS)) + " (models/"
                        "transformer.py states each preset's source and "
                        "sizes; the first log line says which form each of "
                        "its kernels runs: attention=, scan=, delta=, ssd=, conv=, "
                        "norm=, and yarn= where a layer's rotary turn runs "
                        "YaRN's table: laguna_s_2_1, laguna_tiny)")
    p.add_argument("--num_layers", type=int, default=0,
                   help=">0: this many layers of a masked_lm/causal_lm "
                        "transformer preset in place of its own depth, at "
                        "every published width (one chip's share of a model "
                        "that does not fit); 0 keeps the preset's depth")
    p.add_argument("--expert_share", type=str, default=None,
                   metavar="RANK/RANKS",
                   help="the experts of each dropless expert layer (a "
                        "causal_lm preset with experts of its own) that this "
                        "process holds as rank "
                        "RANK of RANKS that share the layer: E/RANKS of them "
                        "from RANK*E/RANKS on (0/8 of 64 experts, or 0/32 of "
                        "laguna_s_2_1's 256: experts 0-7). The "
                        "router stays whole; what absent experts would add "
                        "is left out. With --vocab_size as the vocabulary's "
                        "slice and --num_layers, one chip's share of an "
                        "expert-parallel job. Default: all")
    p.add_argument("--layer_span", type=str, default=None,
                   metavar="FIRST:END",
                   help="the published layers [FIRST, END) that this process "
                        "holds of a preset whose layers differ by kind, "
                        "as a pipeline stage would (laguna_s_2_1 0:5: the "
                        "leading dense layer and one period); a layer keeps "
                        "its published index, and a span in which a G or X "
                        "layer has no M* or F* before it is refused. With "
                        "--vocab_size as the vocabulary's slice, one chip's "
                        "share of a stated deployment. Default: all layers")
    p.add_argument("--no_compile_cache", action="store_true",
                   help="set up no persistent XLA compile cache (by default "
                        "accelerator runs cache under <checkout>/.jax_cache; "
                        "JAX_COMPILATION_CACHE_DIR, where set, places the "
                        "cache and is left alone either way)")
    p.add_argument("--pretrained", type=str, default=None,
                   help="path to a torch.save'd torchvision ResNet "
                        "state_dict: fine-tune from its backbone weights "
                        "(the reference's pretrained-ResNet50 task shape)")
    p.add_argument("--image_size", type=int, default=224)
    p.add_argument("--seq_len", type=int, default=128)
    p.add_argument("--vocab_size", type=int, default=None,
                   help="token vocabulary; default = the model's own "
                        "(bert_*: 30522, clip_tiny: 1000)")
    p.add_argument("--prefetch", type=int, default=2)
    p.add_argument("--producer_threads", type=int, default=4,
                   help="decode-producer threads (cross-batch decode "
                        "overlap)")
    p.add_argument("--placement_depth", type=int, default=2,
                   help="device-resident global batches the placement "
                        "plane keeps transferred ahead of the step "
                        "(default 2 = double-buffered H2D)")
    p.add_argument("--no_autotune", action="store_true",
                   help="disable the closed-loop pipeline autotuner (tune/) "
                        "— run the exact fixed-knob configuration (workers/"
                        "prefetch/pool/ring/stripes as passed); the control "
                        "arm for benchmarking and bisection")
    p.add_argument("--autotune_interval_s", type=float, default=1.0,
                   help="autotune controller tick period (decisions also "
                        "respect a policy cooldown between actuations)")
    p.add_argument("--data_echo", type=int, default=1,
                   help=">1: run N train steps per host batch with fresh "
                        "on-device augmentation each echo (data echoing) — "
                        "~Nx throughput when the input pipeline is the "
                        "bottleneck")
    p.add_argument("--device_cache", action="store_true",
                   help="keep epoch-0 batches resident in HBM and replay "
                        "them in later epochs (no host decode / H2D; "
                        "augment + MLM masking stay fresh on device)")
    p.add_argument("--device_cache_gb", type=float, default=8.0,
                   help="fall back to streaming when the projected resident "
                        "size exceeds this")
    bc = p.add_mutually_exclusive_group()
    bc.add_argument("--batch_cache", action="store_true",
                    help="epoch-coherent decoded-batch cache (tiered "
                         "RAM/disk, data/cache.py): epoch >= 2 and "
                         "restarted runs stream byte-identical cached "
                         "batches instead of re-reading + re-decoding; "
                         "content-keyed, so the stream is bit-identical "
                         "to the uncached run")
    bc.add_argument("--no_batch_cache", action="store_true",
                    help="force the uncached decode path — the control "
                         "arm against --batch_cache (this is also the "
                         "default)")
    p.add_argument("--cache_ram_budget_mb", type=int, default=512,
                   help="batch-cache RAM ring budget (BufferPool-leased "
                        "pages; LRU spill to disk over budget); a live "
                        "autotuner Tunable")
    p.add_argument("--cache_disk_budget_mb", type=int, default=2048,
                   help="batch-cache disk-spill budget (atomic "
                        "sha256-verified segments; oldest evicted over "
                        "budget); a live autotuner Tunable")
    p.add_argument("--cache_dir", type=str, default=None,
                   help="batch-cache spill directory (default "
                        "~/.cache/<pkg>/batch-cache — stable across "
                        "restarts, so resumed runs start warm)")
    p.add_argument("--shuffle", action="store_true",
                   help="iterable path: reshuffle batch order every epoch "
                        "(same permutation on every process)")
    p.add_argument("--no_augment", action="store_true")
    p.add_argument("--eval_every", type=int, default=0)
    p.add_argument("--no_eval_at_end", action="store_true",
                   help="skip the final eval pass (smokes/benches that only "
                        "need the train stream)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--run_name", type=str, default=None)
    p.add_argument("--metrics_port", type=int, default=None,
                   help="process 0 serves /metrics (Prometheus text) and "
                        "/healthz on this port for the run's lifetime "
                        "(trainer_* histograms, svc_*/lineage_* when "
                        "streaming from a data service); 0 = ephemeral, "
                        "logged at startup (same contract as serve-data; "
                        "default off)")
    p.add_argument("--metrics_host", type=str, default="127.0.0.1",
                   help="exporter bind address (default loopback; the "
                        "endpoint is unauthenticated — 0.0.0.0 is an "
                        "explicit opt-in)")
    p.add_argument("--log_every", type=int, default=50,
                   help="per-step progress line every N steps (0 = off)")
    p.add_argument("--log_grad_norm", action="store_true",
                   help="include the micro-batch global gradient norm in "
                        "per-step progress lines (with --grad_accum the "
                        "optimizer clips the accumulated mean, which is "
                        "smoother than this per-micro-batch value)")
    p.add_argument("--model_parallelism", type=int, default=1,
                   help="tensor-parallel degree (the 'model' mesh axis)")
    p.add_argument("--seq_parallelism", type=int, default=1,
                   help="sequence/context-parallel degree (ring attention)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize transformer blocks (long-context)")
    p.add_argument("--pipeline_parallelism", type=int, default=1,
                   help="GPipe pipeline stages (the 'pipe' mesh axis)")
    p.add_argument("--pp_microbatches", type=int, default=4,
                   help="microbatches per pipeline round")
    p.add_argument("--fsdp", action="store_true",
                   help="fully shard params + optimizer state over the "
                        "'data' axis (ZeRO-3 equivalent)")
    p.add_argument("--zero", nargs="?", type=int, const=1, default=0,
                   choices=[1, 2], metavar="LEVEL",
                   help="ZeRO gradient/optimizer sharding over the 'data' "
                        "axis, params replicated. Bare --zero (or "
                        "--zero 1) = ZeRO-1: shard only the optimizer "
                        "moments; --zero 2 = ZeRO-2: additionally shard "
                        "the gradient-accumulation buffer (--grad_accum) "
                        "and reduce-scatter the step's gradients into the "
                        "shards. Both are mutually exclusive with --fsdp, "
                        "which already shards everything")
    p.add_argument("--num_experts", type=int, default=0,
                   help=">0: switch-MoE transformer blocks; experts shard "
                        "over the 'model' mesh axis (expert parallelism)")
    p.add_argument("--moe_every", type=int, default=2,
                   help="MoE MLP on every Nth block")
    p.add_argument("--flash_attention", action="store_true",
                   help="force the Pallas fused attention kernel on a TPU "
                        "(the kernel or an error; off a TPU exact dense "
                        "attention). Without the flag a sequence model gets "
                        "the kernel where its shapes and mesh allow it "
                        "(ops/flash.py fused_attention_applies)")
    p.add_argument("--checkpoint_dir", type=str, default=None,
                   help="orbax checkpoint root; resumes from the latest "
                        "checkpoint when one exists")
    p.add_argument("--checkpoint_every", type=int, default=1,
                   help="save every N epochs")
    p.add_argument("--checkpoint_every_steps", type=int, default=0,
                   help=">0: ALSO checkpoint every N data steps — step-"
                        "granular, crash-consistent saves carrying the "
                        "data-plane cursor, so a preempted run resumes "
                        "mid-epoch at the exact next batch with a bit-"
                        "identical stream (counted in absolute steps "
                        "across restarts)")
    p.add_argument("--no_resume", action="store_true",
                   help="ignore existing checkpoints, start fresh")
    p.add_argument("--profile_dir", type=str, default=None,
                   help="capture a jax.profiler trace of early steps")
    p.add_argument("--coordinator_address", type=str, default=None,
                   help="host:port of process 0 for multi-host rendezvous "
                        "(torchrun MASTER_ADDR equivalent)")
    p.add_argument("--num_processes", type=int, default=None,
                   help="multi-host process count (WORLD_SIZE equivalent)")
    p.add_argument("--process_id", type=int, default=None,
                   help="this host's index (RANK equivalent)")
    p.add_argument("--backend", type=str, default=None,
                   choices=["tpu", "cpu"],
                   help="force a JAX platform (the BASELINE --backend knob); "
                        "default: whatever the environment provides")
    p.add_argument("--num_cpu_devices", type=int, default=0,
                   help="with --backend cpu: simulate an N-device mesh")
    return p


def build_serve_parser() -> argparse.ArgumentParser:
    """``ldt serve-data`` — run a DataService on this (CPU) host. Plan
    parameters (sampler/batch/shard/seed/epoch) come from each trainer's
    handshake; this parser only configures the decode plane."""
    p = argparse.ArgumentParser(
        prog="ldt serve-data",
        description="Serve decoded, plan-ordered training batches over TCP "
                    "(disaggregated input-data service)",
    )
    p.add_argument("--dataset_path", type=str, required=True)
    p.add_argument("--host", type=str, default="0.0.0.0")
    p.add_argument("--port", type=int, default=8476,
                   help="0 = pick an ephemeral port (printed at startup)")
    p.add_argument("--task_type", type=str, default="classification",
                   choices=["classification", "masked_lm", "causal_lm",
                            "contrastive"],
                   help="selects the decode hook; must match the trainer's")
    p.add_argument("--image_size", type=int, default=224)
    p.add_argument("--num_workers", type=int, default=0,
                   help=">0: decode in N spawned worker processes (size to "
                        "this host's cores)")
    p.add_argument("--no_shm_workers", action="store_true",
                   help="worker-pool IPC falls back to pickling decoded "
                        "batches instead of shared-memory ring slots")
    p.add_argument("--sched_lookahead", type=int, default=0,
                   help=">0: straggler-aware dispatch — reorder worker "
                        "dispatch predicted-heaviest-first within this many "
                        "buffered plan items (needs --num_workers > 0; the "
                        "yielded stream stays in plan order, bit-identical)")
    p.add_argument("--sched_heavy_share", type=int, default=0,
                   help="percent of decode workers reserved as a dedicated "
                        "heavy lane for items predicted far above the "
                        "running mean (0 = single lane)")
    p.add_argument("--no_buffer_pool", action="store_true",
                   help="disable the recycled decode-buffer pool (every "
                        "batch faults a fresh allocation)")
    p.add_argument("--device_decode", action="store_true",
                   help="serve half-decoded JPEG coefficient pages "
                        "(entropy-only host decode) instead of finished "
                        "pixels — trainers must also run --device_decode "
                        "(the HELLO is skew-checked); classification only")
    p.add_argument("--token_pack", action="store_true",
                   help="serve packed variable-length token batches "
                        "(values/offsets pages + pack plan; text tasks) to "
                        "v4 clients that request --token_pack; every other "
                        "peer still streams the bit-identical padded arm")
    p.add_argument("--seq_len", type=int, default=128,
                   help="padded sequence length for text tasks (must match "
                        "the trainer's --seq_len; decode config, like "
                        "--image_size)")
    p.add_argument("--pack_len", type=int, default=0,
                   help="packed slot-length cap (0 = --seq_len)")
    p.add_argument("--pack_rows_multiple", type=int, default=8,
                   help="packed row-count rounding quantum")
    p.add_argument("--batch_cache", action="store_true",
                   help="epoch-coherent decoded-batch cache (tiered "
                        "RAM/disk): a second epoch, a reconnected "
                        "trainer, or a second client streaming the same "
                        "plan is served from cache — no fragment read, "
                        "no decode; content-keyed, stream bit-identical")
    p.add_argument("--cache_ram_budget_mb", type=int, default=512,
                   help="batch-cache RAM ring budget (MiB)")
    p.add_argument("--cache_disk_budget_mb", type=int, default=2048,
                   help="batch-cache disk-spill budget (MiB)")
    p.add_argument("--cache_dir", type=str, default=None,
                   help="batch-cache spill directory (default "
                        "~/.cache/<pkg>/batch-cache)")
    p.add_argument("--queue_depth", type=int, default=4,
                   help="bounded per-client batch queue (backpressure)")
    p.add_argument("--admission_max_jobs", type=int, default=0,
                   help=">0: refuse a NEW job's first session once this "
                        "many non-read-only jobs are admitted (diagnosable "
                        "MSG_ERROR at HELLO; read-only/inference jobs and "
                        "reconnects of admitted jobs always pass); 0 = "
                        "unlimited (pre-r20 behavior)")
    p.add_argument("--admission_max_stall_pct", type=float, default=0.0,
                   help=">0: refuse a NEW job while this server's windowed "
                        "decode stall is above this percentage — admitting "
                        "another tenant would burn the existing jobs' "
                        "stall SLO budget; 0 = no stall gate")
    p.add_argument("--handshake_timeout_s", type=float, default=30.0,
                   help="per-connection HELLO deadline; a peer that "
                        "connects and stays silent is dropped after this "
                        "(0 = wait forever)")
    p.add_argument("--read_retries", type=int, default=3,
                   help="dataset-read attempts (exponential backoff) before "
                        "erroring a client stream")
    p.add_argument("--log_every_s", type=float, default=30.0,
                   help="periodic service-stats line; 0 = off")
    p.add_argument("--metrics_port", type=int, default=None,
                   help="serve /metrics (Prometheus text: svc_* counters, "
                        "decode/queue-wait histograms) and /healthz (queue "
                        "depths, client liveness) on this port "
                        "(0 = ephemeral, printed at startup; default off)")
    p.add_argument("--metrics_host", type=str, default="127.0.0.1",
                   help="exporter bind address (default loopback; the "
                        "endpoint is unauthenticated — 0.0.0.0 is an "
                        "explicit opt-in)")
    p.add_argument("--coordinator", type=str, default=None,
                   metavar="HOST:PORT",
                   help="register with this fleet coordinator (`ldt "
                        "coordinator`) and serve as one elastic member: "
                        "heartbeats, shard lease, deregister on stop")
    p.add_argument("--advertise_addr", type=str, default=None,
                   metavar="HOST:PORT",
                   help="the address CLIENTS dial, as registered with the "
                        "coordinator (default: bind host + bound port, "
                        "hostname when binding a wildcard — set explicitly "
                        "behind NAT/containers)")
    p.add_argument("--server_id", type=str, default=None,
                   help="stable fleet identity (default: advertise addr + "
                        "random suffix)")
    p.add_argument("--heartbeat_interval_s", type=float, default=0.0,
                   help="heartbeat period; 0 = use the coordinator's "
                        "advertised interval")
    return p


def build_coordinator_parser() -> argparse.ArgumentParser:
    """``ldt coordinator`` — the fleet control plane: membership,
    generation-numbered shard leases, heartbeat expiry. Carries no data."""
    p = argparse.ArgumentParser(
        prog="ldt coordinator",
        description="Coordinate an elastic fleet of `ldt serve-data` "
                    "servers: registration, heartbeats, shard leases, "
                    "membership resolution for trainers",
    )
    p.add_argument("--host", type=str, default="0.0.0.0")
    p.add_argument("--port", type=int, default=8470,
                   help="0 = pick an ephemeral port (printed at startup)")
    p.add_argument("--heartbeat_interval_s", type=float, default=2.0,
                   help="heartbeat period advertised to members")
    p.add_argument("--lease_ttl_s", type=float, default=6.0,
                   help="heartbeat silence after which a member is expired "
                        "and its lease reassigned (keep >= 2-3 heartbeat "
                        "intervals)")
    p.add_argument("--handshake_timeout_s", type=float, default=10.0,
                   help="per-connection request deadline (a silent peer is "
                        "dropped after this)")
    p.add_argument("--log_every_s", type=float, default=30.0,
                   help="periodic membership line; 0 = off")
    p.add_argument("--metrics_port", type=int, default=None,
                   help="serve /metrics (fleet_members, "
                        "fleet_lease_generation, fleet_rebalance_ms, ...) "
                        "and /healthz (member table, heartbeat ages) on "
                        "this port (0 = ephemeral; default off)")
    p.add_argument("--metrics_host", type=str, default="127.0.0.1",
                   help="exporter bind address (default loopback)")
    p.add_argument("--scale_up_stall_pct", type=float, default=50.0,
                   help="a member heartbeat reporting windowed stall above "
                        "this flips the fleet recommendation to scale_up "
                        "(/healthz, fleet_scale_recommendation gauge, "
                        "`ldt fleet recommend`)")
    p.add_argument("--scale_down_stall_pct", type=float, default=5.0,
                   help="every member below this (with clients attached, "
                        ">1 members) marks the fleet a drain candidate")
    return p


def build_fleet_parser() -> argparse.ArgumentParser:
    """``ldt fleet`` — operator queries against a running coordinator."""
    p = argparse.ArgumentParser(
        prog="ldt fleet",
        description="Query a running `ldt coordinator`: membership, "
                    "per-member heartbeat pressure, and the scale "
                    "recommendation the autotune fleet half derives",
    )
    p.add_argument("action", choices=["recommend"],
                   help="recommend: print the member table with each "
                        "member's windowed stall pressure and the "
                        "coordinator's scale-up/ok/drain recommendation")
    p.add_argument("--coordinator", type=str, required=True,
                   metavar="HOST:PORT")
    p.add_argument("--timeout_s", type=float, default=10.0)
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="print the raw RESOLVE payload as JSON (scripting)")
    return p


def build_jobs_parser() -> argparse.ArgumentParser:
    """``ldt jobs`` — the job plane's operator view: every job the
    coordinator's registry knows, aggregated across member heartbeats."""
    p = argparse.ArgumentParser(
        prog="ldt jobs",
        description="Query a running `ldt coordinator` for the v6 job "
                    "plane: per-job priority class, session count, resume "
                    "cursor, cache hit rate and SLO burn-down",
    )
    p.add_argument("action", choices=["list", "describe"],
                   help="list: one row per registered job; describe: full "
                        "detail (per-objective burn windows) for one job")
    p.add_argument("job_id", nargs="?", default=None,
                   help="the job to describe (describe only)")
    p.add_argument("--coordinator", type=str, required=True,
                   metavar="HOST:PORT")
    p.add_argument("--timeout_s", type=float, default=10.0)
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="print the raw per-job rows as JSON (scripting)")
    return p


def _job_row_line(row: dict) -> str:
    rate = row.get("cache_hit_rate")
    return (
        f"  {row.get('job_id')} [{row.get('priority')}] "
        f"sessions {row.get('sessions', 0)} "
        f"cursor {row.get('cursor', -1)} "
        f"batches {row.get('batches_sent', 0)} "
        f"cache_hit_rate {'-' if rate is None else rate}"
    )


def jobs_main(argv=None) -> int:
    """``jobs`` subcommand body. Exit status: 0 on success, 4 when
    ``describe`` names a job the registry does not know (scripting can
    distinguish 'no such tenant' from transport failure)."""
    import json

    args = build_jobs_parser().parse_args(argv)
    if args.action == "describe" and not args.job_id:
        build_jobs_parser().error("describe needs a job_id")
    from .fleet.balancer import resolve_fleet

    payload = resolve_fleet(args.coordinator, timeout_s=args.timeout_s)
    rows = payload.get("jobs") or []
    if args.action == "describe":
        rows = [r for r in rows if r.get("job_id") == args.job_id]
        if not rows:
            print(f"job {args.job_id!r} not registered with "
                  f"{args.coordinator}")
            return 4
    if args.as_json:
        print(json.dumps(rows, indent=2, sort_keys=True))
        return 0
    if args.action == "list":
        print(f"{len(rows)} job(s), generation {payload.get('generation')}")
        for row in rows:
            print(_job_row_line(row))
        return 0
    row = rows[0]
    print(f"job {row.get('job_id')}")
    print(f"  priority:       {row.get('priority')}")
    print(f"  sessions:       {row.get('sessions', 0)}")
    print(f"  resume cursor:  {row.get('cursor', -1)}")
    print(f"  batches sent:   {row.get('batches_sent', 0)}")
    rate = row.get("cache_hit_rate")
    print(f"  cache hit rate: {'-' if rate is None else rate} "
          f"(hit {row.get('cache_hit', 0)} / "
          f"miss {row.get('cache_miss', 0)})")
    burn = row.get("slo_burn") or {}
    for name in sorted(burn):
        windows = burn[name]
        line = " ".join(
            f"{label}={windows[label]}" for label in sorted(windows)
        )
        print(f"  slo {name}: burn {line}")
    return 0


def fleet_main(argv=None) -> int:
    """``fleet`` subcommand body. Exit status encodes the recommendation
    for scripting: 0 = ok/drain_candidate, 3 = scale_up (so an operator
    cron can `ldt fleet recommend … || page`)."""
    import json

    args = build_fleet_parser().parse_args(argv)
    from .fleet.balancer import resolve_fleet

    payload = resolve_fleet(args.coordinator, timeout_s=args.timeout_s)
    recommendation = payload.get("recommendation") or {"action": "ok"}
    if args.as_json:
        print(json.dumps(payload, indent=2, sort_keys=True))
    else:
        print(
            f"generation {payload.get('generation')}, "
            f"{payload.get('stripe_count')} members"
        )
        for m in payload.get("members", []):
            pressure = m.get("pressure") or {}
            print(
                f"  {m.get('server_id')} @ {m.get('addr')} "
                f"stripe {m.get('stripe_index')} "
                f"stall {pressure.get('stall_pct', '-')}% "
                f"clients {pressure.get('active_clients', '-')} "
                f"(heartbeat {m.get('heartbeat_age_s')}s ago)"
            )
        for entry in payload.get("stale_members", []) or []:
            # Expired members whose last pressure window is retained (v6):
            # evidence that went stale, not absent — the reason a drain
            # recommendation may be withheld right after a blip.
            pressure = entry.get("pressure") or {}
            print(
                f"  {entry.get('server_id')} EXPIRED "
                f"{entry.get('stale_age_s')}s ago, last stall "
                f"{pressure.get('stall_pct', '-')}%"
            )
        jobs = payload.get("jobs") or []
        if jobs:
            print(f"{len(jobs)} job(s):")
            for row in jobs:
                print(_job_row_line(row))
        queue_wait = payload.get("queue_wait_ms")
        if isinstance(queue_wait, dict):
            # Fleet-wide percentiles merged from the members' heartbeat
            # histograms (protocol v5) — exact, not a mean of p99s.
            print(
                "fleet queue_wait: "
                f"p50 {queue_wait.get('p50_ms')}ms "
                f"p95 {queue_wait.get('p95_ms')}ms "
                f"p99 {queue_wait.get('p99_ms')}ms "
                f"({queue_wait.get('count')} waits, "
                f"{queue_wait.get('members')} members reporting)"
            )
        print(
            f"recommendation: {recommendation.get('action')} — "
            f"{recommendation.get('reason', '')}"
        )
    return 3 if recommendation.get("action") == "scale_up" else 0


def coordinator_main(argv=None) -> dict:
    """``coordinator`` subcommand body — blocks until interrupted."""
    args = build_coordinator_parser().parse_args(argv)
    from .fleet.coordinator import Coordinator, CoordinatorConfig

    coordinator = Coordinator(CoordinatorConfig(
        host=args.host,
        port=args.port,
        heartbeat_interval_s=args.heartbeat_interval_s,
        lease_ttl_s=args.lease_ttl_s,
        handshake_timeout_s=args.handshake_timeout_s,
        log_every_s=args.log_every_s,
        metrics_port=args.metrics_port,
        metrics_host=args.metrics_host,
        scale_up_stall_pct=args.scale_up_stall_pct,
        scale_down_stall_pct=args.scale_down_stall_pct,
    ))
    coordinator.serve_forever()
    return coordinator.registry.snapshot()


def serve_main(argv=None) -> dict:
    """``serve-data`` subcommand body — blocks until interrupted."""
    args = build_serve_parser().parse_args(argv)
    from .service.server import DataService, ServeConfig

    service = DataService(ServeConfig(
        dataset_path=args.dataset_path,
        host=args.host,
        port=args.port,
        task_type=args.task_type,
        image_size=args.image_size,
        num_workers=args.num_workers,
        shm_workers=not args.no_shm_workers,
        sched_lookahead=args.sched_lookahead,
        sched_heavy_share=args.sched_heavy_share,
        buffer_pool=not args.no_buffer_pool,
        device_decode=args.device_decode,
        token_pack=args.token_pack,
        seq_len=args.seq_len,
        pack_len=args.pack_len,
        pack_rows_multiple=args.pack_rows_multiple,
        batch_cache=args.batch_cache,
        cache_ram_budget_mb=args.cache_ram_budget_mb,
        cache_disk_budget_mb=args.cache_disk_budget_mb,
        cache_dir=args.cache_dir,
        queue_depth=args.queue_depth,
        admission_max_jobs=args.admission_max_jobs,
        admission_max_stall_pct=args.admission_max_stall_pct,
        handshake_timeout_s=args.handshake_timeout_s,
        read_retries=args.read_retries,
        log_every_s=args.log_every_s,
        metrics_port=args.metrics_port,
        metrics_host=args.metrics_host,
        coordinator_addr=args.coordinator,
        advertise_addr=args.advertise_addr,
        server_id=args.server_id,
        heartbeat_interval_s=args.heartbeat_interval_s,
    ))
    service.serve_forever()
    return service.counters.snapshot()


def console_entry() -> int:
    """Entry point for the ``ldt`` / ``ldt-train`` console scripts. ``main``
    returns the final metrics dict for programmatic callers; a setuptools
    script wraps its return in ``sys.exit(...)``, which would turn every
    successful run into exit status 1 with the dict dumped to stderr —
    so the script target is this wrapper, which discards the dict. The
    ``check`` subcommand instead returns an int exit status (its non-zero
    exit IS the lint gate), which passes through."""
    result = main()
    return result if isinstance(result, int) else 0


def main(argv=None) -> dict:
    if argv is None:
        import sys

        argv = sys.argv[1:]
    argv = list(argv)
    # Subcommand dispatch, backward-compatible: bare flags mean `train`
    # (every existing invocation keeps working).
    if argv and argv[0] == "serve-data":
        return serve_main(argv[1:])
    if argv and argv[0] == "coordinator":
        # The fleet control plane: membership + shard leases for N
        # serve-data members (README "Fleet").
        return coordinator_main(argv[1:])
    if argv and argv[0] == "fleet":
        # Operator queries against a running coordinator (pressure table +
        # scale recommendation). Returns an int exit status: 3 = scale_up.
        return fleet_main(argv[1:])
    if argv and argv[0] == "jobs":
        # Job-plane queries against a running coordinator (per-job cursor,
        # priority, sessions, cache hit rate, SLO burn). Returns an int
        # exit status: 4 = describe target not registered.
        return jobs_main(argv[1:])
    if argv and argv[0] == "check":
        # The static-analysis gate: returns an int exit status (0 = clean /
        # no new findings), not a metrics dict.
        from .analysis.cli import check_main

        return check_main(argv[1:])
    if argv and argv[0] == "protocol":
        # Wire-protocol golden corpus: decode every checked-in frame blob
        # and re-encode it byte-identically per version (`ldt protocol
        # goldens`, `--update` to regenerate). Returns an int exit status.
        from .service.goldens import goldens_main

        return goldens_main(argv[1:])
    if argv and argv[0] == "graph":
        # The cross-module concurrency model (thread roots, locks,
        # lock-order edges) as DOT (--dot) or a text summary.
        from .analysis.cli import graph_main

        return graph_main(argv[1:])
    if argv and argv[0] == "trace":
        # Telemetry export: span JSONL (LDT_TRACE_PATH) → Chrome-trace JSON
        # loadable in Perfetto (`ldt trace export`, multi-process merge with
        # flow arrows) and per-batch critical-path attribution with a
        # straggler table (`ldt trace critical-path`). Returns an int exit
        # status.
        from .obs.spans import trace_main

        return trace_main(argv[1:])
    if argv and argv[0] == "costs":
        # Per-item cost ledger report: decode cost JSONL (LDT_COST_PATH) →
        # totals + slowest-items table (`ldt costs report`). Returns an int
        # exit status.
        from .obs.costs import costs_main

        return costs_main(argv[1:])
    if argv and argv[0] == "train":
        argv = argv[1:]
    args = build_parser().parse_args(argv)
    if args.backend == "cpu":
        import jax

        # Platform config must run before the first backend query (and before
        # rendezvous, which may query local devices).
        if args.num_cpu_devices > 0:
            try:
                jax.config.update("jax_num_cpu_devices", args.num_cpu_devices)
            except RuntimeError as e:
                raise SystemExit(
                    f"--num_cpu_devices must be set before JAX initializes: {e}"
                )
        jax.config.update("jax_platforms", "cpu")
    # Multi-host rendezvous must precede ANY backend query, including the
    # --backend tpu device probe below. Unconditional: with no explicit
    # --coordinator_address it still honours JAX_COORDINATOR_ADDRESS from the
    # environment (torchrun's env-first contract,
    # /root/reference/lance_iterable.py:154-156); no-op when single-process.
    from .parallel.mesh import maybe_initialize_distributed

    maybe_initialize_distributed(
        args.coordinator_address, args.num_processes, args.process_id
    )
    if args.backend == "tpu":
        import jax

        # Verify rather than force: the flag must never run the job on
        # another platform without saying so.
        platform = jax.devices()[0].platform
        if platform != "tpu":
            raise SystemExit(
                f"--backend tpu requested but JAX found platform={platform!r}; "
                "check JAX_PLATFORMS / the TPU runtime"
            )
    config = TrainConfig(
        dataset_path=args.dataset_path,
        val_dataset_path=args.val_dataset_path,
        val_fraction=args.val_fraction,
        task_type=args.task_type,
        num_classes=args.num_classes,
        sampler_type=args.sampler_type,
        loader_style=args.loader_style,
        filter=args.filter,
        data_format=args.data_format,
        batch_size=args.batch_size,
        epochs=args.epochs,
        max_steps=args.max_steps,
        lr=args.lr,
        momentum=args.momentum,
        optimizer=args.optimizer,
        weight_decay=args.weight_decay,
        lr_schedule=args.lr_schedule,
        warmup_steps=args.warmup_steps,
        total_steps=args.total_steps,
        grad_clip=args.grad_clip,
        grad_accum=args.grad_accum,
        fsdp=args.fsdp,
        zero_opt=args.zero,
        num_workers=args.num_workers,
        shm_workers=not args.no_shm_workers,
        buffer_pool=not args.no_buffer_pool,
        device_decode=args.device_decode and not args.no_device_decode,
        token_pack=args.token_pack and not args.no_token_pack,
        pack_len=args.pack_len,
        pack_rows_multiple=args.pack_rows_multiple,
        data_service_addr=args.data_service,
        coordinator_addr=args.coordinator,
        job_id=args.job_id,
        job_priority=args.job_priority,
        no_ddp=args.no_ddp,
        no_wandb=args.no_wandb,
        model_name=args.model_name,
        num_layers=args.num_layers,
        expert_share=args.expert_share,
        layer_span=args.layer_span,
        pretrained=args.pretrained,
        compile_cache=not args.no_compile_cache,
        image_size=args.image_size,
        seq_len=args.seq_len,
        vocab_size=args.vocab_size,
        prefetch=args.prefetch,
        producer_threads=args.producer_threads,
        placement_depth=args.placement_depth,
        autotune=not args.no_autotune,
        autotune_interval_s=args.autotune_interval_s,
        data_echo=args.data_echo,
        device_cache=args.device_cache,
        device_cache_gb=args.device_cache_gb,
        batch_cache=args.batch_cache and not args.no_batch_cache,
        cache_ram_budget_mb=args.cache_ram_budget_mb,
        cache_disk_budget_mb=args.cache_disk_budget_mb,
        cache_dir=args.cache_dir,
        shuffle=args.shuffle,
        augment=not args.no_augment,
        eval_at_end=not args.no_eval_at_end,
        eval_every=args.eval_every,
        seed=args.seed,
        run_name=args.run_name,
        metrics_port=args.metrics_port,
        metrics_host=args.metrics_host,
        log_every=args.log_every,
        log_grad_norm=args.log_grad_norm,
        model_parallelism=args.model_parallelism,
        seq_parallelism=args.seq_parallelism,
        remat=args.remat,
        flash_attention=args.flash_attention,
        num_experts=args.num_experts,
        moe_every=args.moe_every,
        pipeline_parallelism=args.pipeline_parallelism,
        pp_microbatches=args.pp_microbatches,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        checkpoint_every_steps=args.checkpoint_every_steps,
        resume=not args.no_resume,
        profile_dir=args.profile_dir,
        coordinator_address=args.coordinator_address,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    return train(config)


if __name__ == "__main__":
    _result = main()
    if isinstance(_result, int) and _result != 0:
        raise SystemExit(_result)
