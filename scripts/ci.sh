#!/usr/bin/env bash
# CI entrypoint: the static-analysis gate, then the tier-1 tests.
#
# Stage 1 — `ldt check`: the AST lint over the package (determinism, jit
# purity, concurrency hygiene, resource ownership, protocol consistency,
# obs hygiene). Fails fast: a lint finding costs
# seconds to see here and minutes to rediscover inside a test run.
# Stage 2 — telemetry exporter smoke: a short-lived `serve-data` with
# --metrics_port, one loopback client pass, then fetch /metrics and
# /healthz (the scriptable curl equivalent, stdlib-only so CI needs no
# curl binary) and assert the Prometheus histogram series are there.
# Stage 3 — buffer-plane smoke (scripts/zc_smoke.py): shm-worker loopback,
# asserts bufpool_hit_total > 0 / shm_batches_total > 0 via /metrics and
# zero leaked /dev/shm segments after shutdown.
# Stage 4 — fleet smoke (scripts/fleet_smoke.py): coordinator + 2 real
# serve-data subprocesses, SIGKILL one mid-stream — the striped client
# stream must complete bit-identical with fleet_failovers_total >= 1, the
# coordinator must expire the corpse, the survivor must drain on SIGTERM
# with exit 0, and /dev/shm must end clean.
# Stage 5 — placement smoke (scripts/placement_smoke.py): 8 XLA-forced CPU
# devices, a 2-simulated-process shard parity check, global batch
# shape/sharding through the async placement plane (bit-identical to
# make_global_batch over the same host batches), and trainer_h2d_ms / placement_buffer_depth on
# /metrics.
# Stage 6 — preemption smoke (scripts/preempt_smoke.py): a real trainer
# subprocess SIGKILLed after exactly N steps (deterministic chaos,
# LDT_CHAOS=sigkill@N) restarts from the newest intact step checkpoint and
# replays the exact remaining batch stream — per-step batch hashes AND
# losses equal to an uninterrupted control arm; a second trainer SIGTERMed
# mid-epoch drains with an awaited emergency checkpoint and exit 0 while
# its /metrics serves the ckpt_* series.
# Stage 7 — autotune smoke (scripts/autotune_smoke.py): a deliberately
# under-provisioned pipeline (1 decode worker, prefetch 1) driven by a
# live AutoTuner — the controller must raise the worker count and
# autotune_decisions_total must be > 0 on a live /metrics scrape, the
# consumed stream must stay bit-identical to a fixed-knob control pass,
# and the LDT_AUTOTUNE_TRACE decision trace must replay deterministically.
# Stage 7b — device-decode smoke (scripts/device_decode_smoke.py): the
# JPEG entropy split on forced-CPU devices — host-vs-device parity within
# the pinned envelope with bit-identical device-arm repeats, a live
# /metrics scrape of the decode_entropy_ms / decode_device_ms /
# decode_*_bytes_total series during a real
# --device_decode train run, and zero BufferPool-lease or /dev/shm leaks
# under LDT_LEAK_SANITIZER=1.
# Stage 7c — batch-cache smoke (scripts/cache_smoke.py): a real two-epoch
# --batch_cache train run asserting cache_hit_total > 0 on a live
# /metrics scrape (epoch 2 streams hits), per-step batch digests
# bit-identical to a --no_batch_cache control arm, zero leaked BufferPool
# leases under the leak sanitizer, and zero stray spill temp files (every
# disk segment committed atomically via os.replace).
# Stage 7d — protocol golden corpus (`ldt protocol goldens`): every
# checked-in frame blob — v1 bare HELLO through v3 striped/coeff/lineage/
# fingerprint and the fleet control plane — must decode with the current
# build and re-encode byte-identically per version; the current encoders
# must reproduce every blob exactly (constructor/framing drift fails the
# gate; `ldt protocol goldens --update` regenerates a reviewable diff).
# Stage 7e — trace smoke (scripts/trace_smoke.py): coordinator + 2
# serve-data subprocesses + a real 1-epoch fleet train, every process
# recording spans (LDT_TRACE_PATH) and servers recording per-item decode
# costs (LDT_COST_PATH); the merged `ldt trace export` must stitch
# cross-process batch chains with intact parent edges from BOTH servers
# into the trainer, critical-path attribution must tile >= 90% of batch
# wall, slo_* value+burn gauges must be live on a member /metrics, and
# the coordinator /healthz must carry build info + fleet queue-wait
# percentiles merged from both members' heartbeat histograms.
# Stage 7f — straggler smoke (scripts/straggler_smoke.py): a skewed
# corpus through one shared WorkerPool, plan-order vs DecodeScheduler —
# sched_dispatch_reorders_total > 0 on a live /metrics scrape during the
# warm scheduled epoch, per-step batch digests bit-identical to the
# plan-order control arm (reordered dispatch is capacity, never
# content), and zero leaked leases / shm ring slots under
# LDT_LEAK_SANITIZER=1 despite out-of-order result holding.
# Stage 7g — jobs smoke (scripts/jobs_smoke.py): the r20 multi-tenant
# plane over real subprocesses — coordinator + 2 serve-data members
# (--batch_cache --admission_max_jobs 1) + two real `ldt train
# --coordinator --job_id` runs (one training-class, one inference-class
# probe riding the read_only exemption). Both runs must exit 0, a third
# non-read-only HELLO must be refused with the frozen "admission
# refused" marker, per-job svc_job_<slug>_* / slo_job_<slug>_* scopes
# plus svc_jobs_active / svc_admission_refusals must be live on a
# member /metrics, the inference tenant must stream cross-job cache
# hits off the training run's content keys, `ldt jobs list/describe`
# must show both tenants against the live coordinator, and /dev/shm
# must end clean under LDT_LEAK_SANITIZER=1.
# Stage 8 — the tier-1 verify command from ROADMAP.md, verbatim — run
# under LDT_LOCK_SANITIZER=1, LDT_LEAK_SANITIZER=1, LDT_WIRE_SANITIZER=1
# AND LDT_COMPILE_SANITIZER=1: every threading.Lock/RLock the package
# creates is wrapped to record actual acquisition orderings, every
# BufferPool page lease/release and shm slot token handoff is recorded
# against its acquire site, every control frame's (msg, field) tuples
# are counted as they cross the loopback wire, every jit funnel's
# dispatches/abstract signatures/post-warmup retraces and H2D/D2H
# transfers are recorded per def site, and conftest dumps all four
# witness JSONs on exit.
# Stage 9 — `ldt check --lock-witness` against the lock witness: the
# runtime evidence corroborates (or prunes) the static LDT1001 lock-order
# cycles, and any NEW LDT10xx finding fails the build exactly like stage 1.
# Stage 10 — `ldt check --leak-witness` against the lease witness: runtime
# acquire/release evidence corroborates (or prunes) the static LDT1201
# ownership findings, and the stage asserts the witness actually
# corroborates the model (>= 1 runtime site matching a static acquire
# site — a zero-overlap witness means the sanitizer hooks or the
# ownership model silently rotted).
# Stage 11 — `ldt check --wire-witness` against the wire witness: observed
# (msg, field) traffic corroborates (or prunes) the static LDT1403
# orphan-read findings, with the same >= 1 matched-tuple receipt — a
# zero-overlap witness means the protocol hooks or the schema model
# silently rotted.
# Stage 12 — `ldt check --compile-witness` against the compile witness:
# runtime compile/transfer evidence corroborates (or prunes) the static
# LDT1703 recompile hazards, with the same >= 1 matched-site receipt.
# Stage 13 — steady-state recompile gate: a short real `train` run under
# the compile sanitizer must record ZERO post-warmup retraces across
# every jit site — the paper's fixed-shape contract (one trace per
# kernel, then pure dispatch), re-proven per commit.
set -e
cd "$(dirname "$0")/.."

echo "== ldt check =="
# Standalone runner: the gate must run even when the training package fails
# to import.
python scripts/ldt_check.py

echo "== telemetry exporter smoke =="
# timeout: a deadlocked service/loader must fail the stage in minutes, not
# hang CI until the job-level kill (same policy as the tier-1 stage below).
timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'PY'
# Equivalent by hand:
#   ldt serve-data --dataset_path <ds> --port 0 --metrics_port 9464 &
#   curl -s localhost:9464/metrics | grep lineage_wire_ms_bucket
#   curl -s localhost:9464/healthz
import io, json, pathlib, shutil, tempfile, urllib.request
import numpy as np, pyarrow as pa
from PIL import Image

from lance_distributed_training_tpu.data import write_dataset
from lance_distributed_training_tpu.service import (
    DataService, RemoteLoader, ServeConfig,
)

rng = np.random.default_rng(0)
def jpeg():
    arr = (rng.random((32, 32, 3)) * 255).astype(np.uint8)
    buf = io.BytesIO(); Image.fromarray(arr).save(buf, format="JPEG")
    return buf.getvalue()

tmp = pathlib.Path(tempfile.mkdtemp(prefix="ldt-ci-obs-"))
table = pa.table({
    "image": pa.array([jpeg() for _ in range(48)], pa.binary()),
    "label": pa.array(rng.integers(0, 10, 48), pa.int64()),
})
ds = write_dataset(table, tmp / "ds", mode="create", max_rows_per_file=24)
svc = DataService(ServeConfig(
    dataset_path=ds.uri, host="127.0.0.1", port=0, image_size=32,
    metrics_port=0,
)).start()
try:
    n = len(list(RemoteLoader(
        f"127.0.0.1:{svc.port}", 8, 0, 1,
        connect_retries=2, backoff_s=0.01,
    )))
    base = f"http://127.0.0.1:{svc.metrics_port}"
    metrics = urllib.request.urlopen(f"{base}/metrics", timeout=10).read().decode()
    for series in ("svc_batches_sent", "svc_decode_ms_bucket",
                   "lineage_wire_ms_bucket", "lineage_batch_age_ms_count"):
        assert series in metrics, f"missing {series} in /metrics"
    health = json.loads(
        urllib.request.urlopen(f"{base}/healthz", timeout=10).read()
    )
    assert health["status"] == "ok", health
    print(f"exporter smoke ok: {n} batches, /metrics + /healthz healthy")
finally:
    svc.stop()
    shutil.rmtree(tmp, ignore_errors=True)
PY

echo "== buffer-plane smoke (shm workers + pooled pages) =="
# A serve-data with shm worker IPC, one loopback client pass, then assert
# via /metrics that the plane actually recycled (bufpool_hit_total > 0) and
# the batches actually rode shared memory (shm_batches_total > 0, zero
# pickle fallbacks), and that no shm segment outlives shutdown. A real
# script file, not a heredoc: spawn workers re-import __main__, which must
# be an importable path.
timeout -k 10 300 env JAX_PLATFORMS=cpu PYTHONPATH=. python scripts/zc_smoke.py

echo "== fleet smoke (coordinator + 2 servers, SIGKILL mid-stream) =="
# Real subprocess members (the `ldt serve-data --coordinator` CLI path) so
# the SIGKILL is a genuine process death and the SIGTERM drain is the real
# docker-stop path, not an in-process simulation.
timeout -k 10 420 env JAX_PLATFORMS=cpu PYTHONPATH=. python scripts/fleet_smoke.py

echo "== placement smoke (mesh-native global batches + H2D telemetry) =="
# 2-simulated-process shard parity on 8 forced CPU devices (the script
# sets JAX_PLATFORMS and XLA_FLAGS itself), placed-vs-make_global_batch
# bit parity, and the trainer_h2d_ms series scraped from a live /metrics.
timeout -k 10 300 env PYTHONPATH=. python scripts/placement_smoke.py

echo "== preemption smoke (SIGKILL resume fidelity + SIGTERM drain) =="
# Real subprocess trainers: the SIGKILL is genuine process death mid-epoch
# (no handler runs — the crash-consistency manifest must carry recovery),
# and the SIGTERM is the real k8s-eviction path asserted to exit 0.
timeout -k 10 540 env JAX_PLATFORMS=cpu PYTHONPATH=. python scripts/preempt_smoke.py

echo "== autotune smoke (closed-loop controller on live /metrics) =="
# Real script file (spawn workers re-import __main__): start starved — 1
# worker, prefetch 1 — and require the controller to grow the pool, count
# decisions on a live scrape, keep the stream bit-identical, and leave a
# deterministically-replayable decision trace.
timeout -k 10 300 env JAX_PLATFORMS=cpu PYTHONPATH=. python scripts/autotune_smoke.py

echo "== device-decode smoke (entropy split, parity + live decode_* scrape) =="
# Forced-CPU devices; the same jitted kernel path runs unmodified on real
# TPU (no host callbacks — LDT101/LDT1301 pin it). Leak sanitizer on: the
# stage fails on any stranded BufferPool lease or /dev/shm segment.
timeout -k 10 480 env JAX_PLATFORMS=cpu LDT_LEAK_SANITIZER=1 PYTHONPATH=. python scripts/device_decode_smoke.py

echo "== batch-cache smoke (epoch-2 hits, digest parity, leak-clean) =="
# A real two-epoch --batch_cache train: cache_hit_total > 0 on a live
# /metrics scrape during epoch 2, per-step batch digests bit-identical to
# a --no_batch_cache control arm (LDT_STEP_TRACE_PATH), zero leaked
# leases under LDT_LEAK_SANITIZER=1 and zero stray spill temp files.
timeout -k 10 540 env JAX_PLATFORMS=cpu LDT_LEAK_SANITIZER=1 PYTHONPATH=. python scripts/cache_smoke.py

echo "== token-pack smoke (padded-vs-packed waste cut, digest parity) =="
# The ragged token plane's two-arm gate: a --token_pack masked-LM run over
# a long-tail variable-length corpus must put pack_* waste series on a
# live /metrics scrape, cut measured padding waste >= 30 points vs the
# padded control arm, reproduce bit-identical per-step digests across
# packed repeats, and strand zero ragged page leases under the sanitizer.
timeout -k 10 540 env JAX_PLATFORMS=cpu LDT_LEAK_SANITIZER=1 PYTHONPATH=. python scripts/token_pack_smoke.py

echo "== trace smoke (cross-process causal chains, costs, SLOs) =="
# The r18 observability plane over real subprocesses: coordinator + 2
# serve-data + a 1-epoch fleet train, every process recording spans under
# its own LDT_TRACE_PATH (servers also LDT_COST_PATH). `ldt trace export`
# must merge the four JSONLs with >=1 chain from EACH server reaching the
# trainer (parent edges intact), critical-path attribution must tile
# >=90% of batch wall, slo_* value+burn gauges must be live on a member
# /metrics, and the coordinator /healthz must carry build info plus
# queue-wait percentiles merged from BOTH members' heartbeat histograms.
timeout -k 10 720 env JAX_PLATFORMS=cpu PYTHONPATH=. python scripts/trace_smoke.py

echo "== straggler smoke (reordered dispatch, digest parity, leak-clean) =="
# One shared worker pool, two arms: the DecodeScheduler must actually
# reorder dispatch on its warm epoch (live scrape of
# sched_dispatch_reorders_total), the yielded stream must stay
# bit-identical to plan order, and the out-of-order result holding must
# release every ring slot (leak sanitizer on).
timeout -k 10 300 env JAX_PLATFORMS=cpu LDT_LEAK_SANITIZER=1 PYTHONPATH=. python scripts/straggler_smoke.py

echo "== jobs smoke (multi-tenant fleet: admission, fairness, per-job metrics) =="
# Real tenants on real subprocesses: two `ldt train --job_id` runs share
# one 2-member fleet under --admission_max_jobs 1 (the inference probe
# rides the read_only exemption), a third tenant is refused on the live
# wire, per-job metric scopes + cross-job cache hits are asserted on a
# live member /metrics, and `ldt jobs` reads the coordinator registry.
timeout -k 10 540 env JAX_PLATFORMS=cpu LDT_LEAK_SANITIZER=1 PYTHONPATH=. python scripts/jobs_smoke.py

echo "== protocol goldens (cross-version byte-identity gate) =="
# Every checked-in frame blob decodes with the current build and
# re-encodes byte-identically per version; the current encoders must
# reproduce every blob (wire-format drift fails here, with --update as
# the reviewable escape hatch).
timeout -k 10 120 env JAX_PLATFORMS=cpu PYTHONPATH=. python -m lance_distributed_training_tpu.cli protocol goldens

echo "== tier-1 tests (lock + leak + wire + compile sanitizers on) =="
WITNESS=/tmp/_ldt_lock_witness.json
LEAK_WITNESS=/tmp/_ldt_leak_witness.json
WIRE_WITNESS=/tmp/_ldt_wire_witness.json
COMPILE_WITNESS=/tmp/_ldt_compile_witness.json
rm -f "$WITNESS" "$LEAK_WITNESS" "$WIRE_WITNESS" "$COMPILE_WITNESS"
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu LDT_LOCK_SANITIZER=1 LDT_LOCK_WITNESS_PATH="$WITNESS" LDT_LEAK_SANITIZER=1 LDT_LEAK_WITNESS_PATH="$LEAK_WITNESS" LDT_WIRE_SANITIZER=1 LDT_WIRE_WITNESS_PATH="$WIRE_WITNESS" LDT_COMPILE_SANITIZER=1 LDT_COMPILE_WITNESS_PATH="$COMPILE_WITNESS" python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
if [ "$rc" -ne 0 ]; then exit "$rc"; fi

echo "== lock-order witness cross-check =="
# The instrumented run's observed acquisition orderings, fed back into the
# static gate: a real lock-order cycle now carries a reproducing trace; a
# statically-inferred cycle the run contradicts is marked witness_pruned.
test -s "$WITNESS" || { echo "missing lock witness $WITNESS"; exit 1; }
python scripts/ldt_check.py --lock-witness "$WITNESS"

echo "== resource-lease witness cross-check =="
# The instrumented run's pool-lease / shm-token evidence, fed back into
# the LDT1201 ownership gate — and an assertion that the witness actually
# overlaps the static model: at least one runtime acquire site must match
# a static acquire record, or the corroboration loop is dead machinery.
test -s "$LEAK_WITNESS" || { echo "missing leak witness $LEAK_WITNESS"; exit 1; }
python scripts/ldt_check.py --leak-witness "$LEAK_WITNESS" | tee /tmp/_leakcheck.log
grep -E 'leak witness: [1-9][0-9]*/[0-9]+ runtime sites match' /tmp/_leakcheck.log \
  || { echo "leak witness corroborated no static acquire site"; exit 1; }

echo "== wire-traffic witness cross-check =="
# The instrumented run's (msg, field) wire evidence, fed back into the
# LDT1403 gate — and an assertion that the witness actually overlaps the
# static schema: at least one observed tuple must match a modeled field,
# or the corroboration loop is dead machinery.
test -s "$WIRE_WITNESS" || { echo "missing wire witness $WIRE_WITNESS"; exit 1; }
python scripts/ldt_check.py --wire-witness "$WIRE_WITNESS" | tee /tmp/_wirecheck.log
grep -E 'wire witness: [1-9][0-9]*/[0-9]+ observed \(msg, field\) tuples match' /tmp/_wirecheck.log \
  || { echo "wire witness corroborated no static schema field"; exit 1; }

echo "== compile/transfer witness cross-check =="
# The instrumented run's per-jit-site compile and H2D/D2H evidence, fed
# back into the LDT1703 gate — and an assertion that the witness actually
# overlaps the static mesh model: at least one runtime jit site must
# match a static jit def site, or the def-site join key silently rotted.
test -s "$COMPILE_WITNESS" || { echo "missing compile witness $COMPILE_WITNESS"; exit 1; }
python scripts/ldt_check.py --compile-witness "$COMPILE_WITNESS" | tee /tmp/_compilecheck.log
grep -E 'compile witness: [1-9][0-9]*/[0-9]+ runtime jit sites match' /tmp/_compilecheck.log \
  || { echo "compile witness corroborated no static jit site"; exit 1; }

echo "== steady-state recompile gate (short train smoke) =="
# A real multi-step train run: after the first dispatch per jit site
# (warmup trace) every later call must reuse a seen abstract signature.
# Any post-warmup retrace — a per-batch shape, a drifting static — fails.
timeout -k 10 300 env JAX_PLATFORMS=cpu LDT_COMPILE_SANITIZER=1 PYTHONPATH=. python - <<'PY'
import json
import numpy as np

from lance_distributed_training_tpu.data import create_text_token_dataset
from lance_distributed_training_tpu.trainer import TrainConfig, train
from lance_distributed_training_tpu.utils import compiletrack

import pathlib, tempfile
tmp = pathlib.Path(tempfile.mkdtemp(prefix="ldt-ci-compile-"))
gen = np.random.default_rng(0)
docs = [gen.integers(2, 512, gen.integers(10, 60)).tolist() for _ in range(200)]
uri = str(tmp / "tokens")
create_text_token_dataset(uri, docs, seq_len=32, fragment_size=32)
results = train(TrainConfig(
    dataset_path=uri, task_type="masked_lm", model_name="bert_small",
    batch_size=16, epochs=2, seq_len=32, vocab_size=512, no_wandb=True,
    eval_at_end=True,
))
assert np.isfinite(results["loss"])
sites = compiletrack.sites()
assert sites, "compile sanitizer recorded no jit sites during train"
recompiled = {s: e for s, e in sites.items() if e["post_warmup"] > 0}
assert not recompiled, f"post-warmup recompiles in steady state: {recompiled}"
exercised = sum(1 for e in sites.values() if e["calls"] > 1)
print(f"recompile gate ok: {len(sites)} jit sites, {exercised} exercised "
      f"past warmup, 0 post-warmup retraces "
      f"(h2d events: {sum(v['count'] for v in compiletrack.transfers()['h2d'].values())})")
PY
