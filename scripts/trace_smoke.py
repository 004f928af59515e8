"""CI causal-tracing smoke: coordinator + 2 real `ldt serve-data`
subprocesses + a real `ldt train --coordinator` subprocess, every process
recording spans under its own ``LDT_TRACE_PATH`` (servers also record
per-item decode costs under ``LDT_COST_PATH``). Asserts the r18
observability plane end-to-end, on real subprocess artifacts:

* ``ldt trace export`` merges the four JSONLs into ONE Perfetto trace:
  clock anchors from >=4 processes aligned, and >=1 batch chain from EACH
  server reaches the trainer with the parent edge intact
  (``fleet.recv``'s ``trace_parent`` == that batch's ``svc.decode``
  ``trace_span``), so the merged chains collectively span >=3 processes;
* ``ldt trace critical-path`` attributes >=90% of batch wall time to
  named segments, with >=1 chain carrying the full
  decode → queue_wait → wire → merge → h2d → step tiling;
* both servers' cost ledgers have records (``ldt costs report`` exits 0)
  keyed by the BatchCache content hash;
* ``slo_*`` value + burn gauges are live on a server's ``/metrics``;
* the coordinator ``/healthz`` carries the build block and fleet
  queue-wait percentiles merged from BOTH members' heartbeat histograms
  (``fleet_queue_wait_p99_ms`` live on its ``/metrics``).

Durability of the span files it reads (``obs/spans.py``, batched since PR
35): a process writes its file when 64 KiB of lines are pending, when a span
completes a quarter of a second after the oldest pending one, at
``SpanTracer.flush()`` / ``close()`` and at interpreter exit; its first span
opens the file at once. The three subprocesses have exited (SIGTERM drain,
normal exit) before their files are read, so those are complete; this
process's own tracer (the coordinator's) is flushed by hand before the merge.

Equivalent by hand:
    LDT_TRACE_PATH=coord.jsonl ldt coordinator --port 8470 &
    LDT_TRACE_PATH=srv0.jsonl LDT_COST_PATH=cost0.jsonl \
        ldt serve-data --coordinator 127.0.0.1:8470 --metrics_port 0 … &
    …  # x2
    LDT_TRACE_PATH=train.jsonl ldt train --coordinator 127.0.0.1:8470 …
    ldt trace export --spans coord.jsonl --spans srv0.jsonl … --out t.json
    ldt trace critical-path --spans … --costs cost.jsonl
    ldt costs report --costs cost0.jsonl --costs cost1.jsonl

Run as a real script:
    PYTHONPATH=. python scripts/trace_smoke.py
"""

import io
import json
import os
import pathlib
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

import numpy as np
import pyarrow as pa
from PIL import Image

TRAIN_TIMEOUT_S = 600


def load_events(paths) -> list:
    events = []
    for path in paths:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        events.append(json.loads(line))
                    except ValueError:
                        pass  # a line torn by a dying writer proves nothing
    return events


def scrape(port: int, path: str = "/metrics") -> str:
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ).read().decode()


def main() -> None:
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="ldt-ci-trace-"))
    # The smoke process hosts the coordinator; its spans (coord.handle)
    # must land in their own JSONL. Set BEFORE the first span opens — the
    # default tracer is created lazily and reads the env then.
    os.environ["LDT_TRACE_PATH"] = str(tmp / "coord.jsonl")

    from lance_distributed_training_tpu.cli import main as cli_main
    from lance_distributed_training_tpu.data import write_dataset
    from lance_distributed_training_tpu.fleet import (
        Coordinator,
        CoordinatorConfig,
    )
    from lance_distributed_training_tpu.obs.critpath import (
        analyze,
        rebase_events,
    )

    rng = np.random.default_rng(0)

    def jpeg() -> bytes:
        arr = (rng.random((32, 32, 3)) * 255).astype(np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG")
        return buf.getvalue()

    procs: list = []
    coord = None
    try:
        table = pa.table({
            "image": pa.array([jpeg() for _ in range(240)], pa.binary()),
            "label": pa.array(rng.integers(0, 10, 240), pa.int64()),
        })
        ds = write_dataset(table, tmp / "ds", mode="create",
                           max_rows_per_file=60)

        coord = Coordinator(CoordinatorConfig(
            host="127.0.0.1", port=0, heartbeat_interval_s=0.25,
            lease_ttl_s=5.0, metrics_port=0,
        )).start()
        caddr = f"127.0.0.1:{coord.port}"

        srv_logs = [tmp / "srv0.out", tmp / "srv1.out"]
        for i in range(2):
            env = dict(
                os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.getcwd(),
                LDT_TRACE_PATH=str(tmp / f"srv{i}.jsonl"),
                LDT_COST_PATH=str(tmp / f"cost{i}.jsonl"),
            )
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "lance_distributed_training_tpu.cli",
                 "serve-data", "--dataset_path", str(ds.uri),
                 "--host", "127.0.0.1", "--port", "0", "--image_size", "32",
                 "--queue_depth", "2", "--coordinator", caddr,
                 "--metrics_port", "0", "--log_every_s", "0"],
                env=env, stdout=open(srv_logs[i], "wb"),
                stderr=subprocess.STDOUT,
            ))

        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if coord._healthz()["stripe_count"] == 2:
                break
            for p in procs:
                if p.poll() is not None:
                    raise SystemExit(
                        f"serve-data exited early: {p.returncode}"
                    )
            time.sleep(0.2)
        else:
            raise SystemExit("members never registered")
        print("[smoke] 2 members registered")

        # One real short train: fleet.recv + train.step spans come from the
        # actual trainer, not a stand-in loop, so the h2d/step segments in
        # the attribution are the genuine article.
        train_env = dict(
            os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.getcwd(),
            LDT_TRACE_PATH=str(tmp / "train.jsonl"),
        )
        train = subprocess.run(
            [sys.executable, "-m", "lance_distributed_training_tpu.cli",
             "train", "--dataset_path", str(ds.uri),
             "--coordinator", caddr, "--num_classes", "10",
             "--model_name", "resnet18", "--image_size", "32",
             "--batch_size", "16", "--epochs", "1", "--lr", "0.01",
             "--seed", "7", "--no_wandb", "--no_augment",
             "--no_eval_at_end", "--no_autotune", "--log_every", "0"],
            env=train_env, timeout=TRAIN_TIMEOUT_S,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        if train.returncode != 0:
            print(train.stdout.decode(errors="replace")[-4000:])
            raise SystemExit(f"trainer exited rc={train.returncode}")
        print("[smoke] 1-epoch fleet train done (rc=0)")

        # Fleet SLO half: both members' heartbeats now carry their
        # svc_queue_wait_ms bucket counts; the coordinator merges them into
        # exact cross-member percentiles on /healthz + fleet_* gauges.
        while time.monotonic() < deadline:
            qw = coord._healthz().get("queue_wait_ms")
            if qw and qw.get("members") == 2:
                break
            time.sleep(0.2)
        hz = coord._healthz()
        qw = hz.get("queue_wait_ms")
        assert qw and qw["members"] == 2, hz
        assert qw["count"] > 0 and qw["p50_ms"] <= qw["p99_ms"], qw
        assert hz.get("build", {}).get("protocol_versions"), hz
        metrics = scrape(coord.metrics_port)
        assert "fleet_queue_wait_p99_ms" in metrics, metrics[-2000:]
        print(f"[smoke] coordinator merged queue-wait from 2 members: "
              f"p50={qw['p50_ms']} p99={qw['p99_ms']} ms; build block ok")

        # SLO gauges on a member /metrics (the tick thread runs at 5s).
        port = None
        while time.monotonic() < deadline and port is None:
            text = srv_logs[0].read_text(errors="replace")
            for line in text.splitlines():
                if "metrics on :" in line:
                    port = int(line.split("metrics on :")[1].split(" ")[0])
                    break
            time.sleep(0.2)
        assert port, "server 0 never logged its metrics port"
        while time.monotonic() < deadline:
            metrics = scrape(port)
            if ("slo_stall_pct" in metrics
                    and "slo_queue_wait_p99_ms" in metrics
                    and "slo_queue_wait_p99_ms_burn_5m" in metrics):
                break
            time.sleep(0.5)
        else:
            raise SystemExit(f"slo_* gauges never appeared:\n{metrics}")
        hz = json.loads(scrape(port, "/healthz"))
        assert hz.get("slo") and hz.get("build"), hz
        print("[smoke] slo_* value + burn gauges live on member /metrics; "
              "/healthz carries slo + build blocks")

        # Graceful drain so every JSONL is complete before the merge.
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            assert p.wait(timeout=60) == 0, p.returncode
        print("[smoke] both members drained cleanly on SIGTERM")
        # Quiesce the in-process coordinator too, so coord.jsonl is not
        # being appended to while the merge below reads it.
        coord.stop()
        from lance_distributed_training_tpu.obs.spans import default_tracer

        default_tracer().flush()  # the file is written in batches

        jsonls = [tmp / "coord.jsonl", tmp / "srv0.jsonl",
                  tmp / "srv1.jsonl", tmp / "train.jsonl"]
        for path in jsonls:
            assert path.exists(), f"missing span JSONL {path}"
        merged = tmp / "fleet-trace.json"
        argv = ["trace", "export", "--out", str(merged)]
        for path in jsonls:
            argv += ["--spans", str(path)]
        assert cli_main(argv) == 0
        trace = json.loads(merged.read_text())
        flow = [e for e in trace["traceEvents"] if e.get("ph") in ("s", "t")]
        assert flow, "no flow arrows in the merged trace"

        events = load_events(jsonls)
        rebased, offsets = rebase_events(events)
        assert len(offsets) >= 4, f"clock anchors from {len(offsets)} pids"
        attrs = analyze(rebased)
        assert attrs, "no batch chains in the merged trace"

        # Parent edges: every chain's fleet.recv names the decode root as
        # its parent (trace_parent == the root's trace_span).
        roots, recvs = {}, {}
        for ev in events:
            args = ev.get("args") or {}
            tid = args.get("trace_id")
            if ev.get("name") == "svc.decode" and tid:
                roots[tid] = args
            elif ev.get("name") == "fleet.recv" and tid:
                recvs[tid] = args
        linked = [t for t in recvs if t in roots
                  and recvs[t].get("trace_parent") == roots[t]["trace_span"]]
        assert linked, "no chain with an intact parent edge"

        train_pid = {e.get("pid") for e in load_events([tmp / "train.jsonl"])}
        chain_pids = set()
        srv_pids_reaching_trainer = set()
        for a in attrs:
            chain_pids.update(a["pids"])
            if train_pid & set(a["pids"]):
                srv_pids_reaching_trainer.update(
                    set(a["pids"]) - train_pid
                )
        assert len(chain_pids) >= 3, sorted(chain_pids)
        assert len(srv_pids_reaching_trainer) == 2, (
            f"chains reach the trainer from "
            f"{len(srv_pids_reaching_trainer)} servers, want 2"
        )

        full = [a for a in attrs
                if {"queue_wait", "wire", "merge", "h2d", "step"}
                <= set(a["segments_ms"])
                and ("decode" in a["segments_ms"]
                     or "cache" in a["segments_ms"])]
        assert full, "no chain carries the full segment tiling"
        mean_cov = sum(a["coverage_pct"] for a in attrs) / len(attrs)
        worst = sorted(attrs, key=lambda a: a["coverage_pct"])[:3]
        for a in worst:
            print(f"[smoke]   cover {a['coverage_pct']}% step={a['step']} "
                  f"wall={a['wall_ms']}ms {a['segments_ms']}")
        assert mean_cov >= 90.0, f"mean coverage {mean_cov:.1f}% < 90%"
        print(f"[smoke] {len(attrs)} chains merged across "
              f"{len(chain_pids)} processes, {len(linked)} parent edges "
              f"intact, mean coverage {mean_cov:.1f}%")

        # The operator CLIs over the same artifacts: critical-path with the
        # cost join, and the ledger report from both servers.
        cost_all = tmp / "cost.jsonl"
        with open(cost_all, "w") as out_f:
            for i in range(2):
                out_f.write((tmp / f"cost{i}.jsonl").read_text())
        argv = ["trace", "critical-path", "--costs", str(cost_all)]
        for path in jsonls:
            argv += ["--spans", str(path)]
        assert cli_main(argv) == 0
        assert cli_main(["costs", "report",
                         "--costs", str(tmp / "cost0.jsonl"),
                         "--costs", str(tmp / "cost1.jsonl")]) == 0
        for i in range(2):
            rec = json.loads(
                (tmp / f"cost{i}.jsonl").read_text().splitlines()[0]
            )
            key = rec["key"]
            assert len(key) == 64 and int(key, 16) >= 0, rec
        print("[smoke] critical-path + costs CLIs ok over both ledgers")
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=30)
        if coord is not None:
            coord.stop()
        if os.environ.get("LDT_SMOKE_KEEP") != "1":
            shutil.rmtree(tmp, ignore_errors=True)
        else:
            print(f"[smoke] artifacts kept in {tmp}")

    print("[smoke] trace smoke ok: cross-process chains, parent edges, "
          ">=90% attribution, slo gauges, fleet queue-wait merge")


if __name__ == "__main__":
    main()
