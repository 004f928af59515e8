"""Step 0 of a tiling change: each splash attention kernel timed alone on the
chip, over the block sizes and backward forms the library offers.

``ops/flash.py`` ``unequal_attention`` runs three Mosaic kernels a call
(forward, dkv, dq; or forward and one fused backward). Each has a tiling of
its own in the library's ``BlockSizes``, so one program of ``jax.value_and_
grad`` over the call times one candidate for each of the three at once: the
kernels are told apart by name in the device trace, and the whole program's
time says what the fused backward's sum of dq partials costs outside the
kernels. Times are device times from the trace's
``XLA Ops`` and ``XLA Modules`` lines, never the host clock, so the timing
mode wants a TPU and fails without one.

    chiprun --chips 1 --timeout 3000 -- python3 scripts/splash_tiling_sweep.py
    python3 scripts/splash_tiling_sweep.py --compile_only   # no chip: which
        # tilings Mosaic takes, compiled for a described v5e

Writes ``chiprun_out/splash_sweep/<shape>.jsonl`` (a line a program, as it
goes) and prints a table a shape. Not tier-1; ``PERF.md`` section 6 holds
the tables it gave. A tiling that compiles alone can still be refused inside
a train step, where the operands come in other layouts (PR 34: a fused
backward that fits here was 0.9 MiB over the 16 MiB of scoped VMEM there):
run the cell before a winner goes into ``ops/flash.py``'s table. The fused
backward is timed for the record: it rounds dq's partials to the queries'
dtype (``scripts/splash_gradient_error.py`` reads what that costs), so
``ops/flash.py`` has no entry that takes it. PR 34 also timed ``SEQ_MINOR``
layouts of k and v at the best two tilings of each kernel: under 1% of a
call either way, so neither ``ops/flash.py`` nor this script sets them.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# q, k, v as the cells' layers call unequal_attention (one row of 8,192
# tokens, or of a shape's own ``seq``; segment ids present and all ones)
SEQ = 8192
SHAPES = {
    "moonlight": dict(q=(16, 192), k=(16, 192), v=(16, 128), window=0),
    "phi4_causal": dict(q=(40, 64), k=(20, 64), v=(10, 128), window=0),
    "phi4_window": dict(q=(40, 64), k=(20, 64), v=(10, 128), window=512),
    # heads as wide in values as in keys, in groups: the library's blocked
    # kernel with the keys and values repeated is timed beside the splash ones
    "zaya": dict(q=(8, 128), k=(2, 128), v=(2, 128), window=0),
    # Qwen3-Next's gated attention: heads of 256, twice the VMEM a block
    "qwen3_next": dict(q=(16, 256), k=(2, 256), v=(2, 256), window=0),
    # SmallThinker's grouped attention at its 16,384-token row: the N layer's
    # whole causal row, and the W layers' band of 4,096 (eight 512-blocks)
    "smallthinker_full": dict(q=(28, 128), k=(4, 128), v=(4, 128), window=0,
                              seq=16384),
    "smallthinker_window": dict(q=(28, 128), k=(4, 128), v=(4, 128),
                                window=4096, seq=16384),
    # Granite 4.0-H's grouped attention: 32 query heads over 8 of 64
    "granite": dict(q=(32, 64), k=(8, 64), v=(8, 64), window=0),
    # Laguna's two kinds of layer over 8 key/value heads of 128: 72 query
    # heads in a band of 512 (one block wide, as Phi-4's), 48 over the whole
    # causal row
    "laguna_window": dict(q=(72, 128), k=(8, 128), v=(8, 128), window=512),
    "laguna_full": dict(q=(48, 128), k=(8, 128), v=(8, 128), window=0),
}
KERNELS = ("fwd", "dkv", "dq")
CALLS = 20  # after one call of warm-up
OUT = os.path.join("chiprun_out", "splash_sweep")


def seq_of(shape: dict) -> int:
    return shape.get("seq", SEQ)


def candidates(window: int):
    """``(block_q, block_kv, block_kv_compute)`` triples to try."""
    if window >= 2048:  # a band several blocks wide
        qs, kvs = (256, 512, 1024, 2048), (512, 1024, 2048)
        computes = (256, 512, 1024)
    elif window:
        qs, kvs, computes = (128, 256, 512), (128, 256, 512), (128, 256, 512)
    else:
        qs, kvs = (256, 512, 1024, 2048), (512, 1024, 2048, 4096)
        computes = (256, 512, 1024)
    return [(q, kv, c) for q, kv in itertools.product(qs, kvs)
            for c in computes if c <= kv]


def fused_candidates(window: int):
    if window:
        return [(512, 512, 512), (256, 512, 256)]
    return [(q, kv, c) for kv in (1024, 2048, 4096, 8192)
            for q in (512, 1024, 2048) for c in (512, 1024)]


def tiling_of(fwd, dkv, dq=None):
    """Three triples as a ``SplashTiling``; no ``dq``: the fused backward."""
    from lance_distributed_training_tpu.ops import flash

    return flash.SplashTiling(tuple(fwd), tuple(dkv), dq and tuple(dq[:2]))


def make_program(index, shape, tiling, sharding=None):
    """``value_and_grad`` of one call, as a jitted function named by index
    (the trace's module events carry the name)."""
    import jax
    import jax.numpy as jnp

    from lance_distributed_training_tpu.ops import flash

    def loss(q, k, v, ids):
        if isinstance(tiling, int):  # the library's blocked kernel
            from jax.experimental.pallas.ops.tpu import flash_attention as fa

            k, v = (flash._expand_heads(t, q.shape[1]) for t in (k, v))
            out = fa.flash_attention(
                q, k, v, segment_ids=fa.SegmentIds(q=ids, kv=ids),
                sm_scale=q.shape[-1] ** -0.5, causal=True,
                block_sizes=fa.BlockSizes(
                    block_b=1, **dict.fromkeys((
                        "block_q", "block_k_major", "block_k",
                        "block_q_major_dkv", "block_k_major_dkv",
                        "block_k_dkv", "block_q_dkv", "block_k_major_dq",
                        "block_k_dq", "block_q_dq"), tiling)))
        else:
            out = flash.unequal_attention(
                q, k, v, ids, causal=True, window=shape["window"],
                tiling=tiling)
        return out.astype(jnp.float32).sum()

    def program(q, k, v, ids):
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v, ids)

    program.__name__ = f"sweep_{index}"
    seq = seq_of(shape)
    specs = [jax.ShapeDtypeStruct((1, heads, seq, width), jnp.bfloat16,
                                  sharding=sharding)
             for heads, width in (shape["q"], shape["k"], shape["v"])]
    specs.append(jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=sharding))
    return jax.jit(program).lower(*specs).compile()


def refusal(error: Exception) -> str:
    """A compile error in a line: which kernel, and what it wanted."""
    text = " ".join(str(error).split())
    kernel = re.search(r"splash_mha_(fwd|dkv|dq)", text)
    sizes = re.search(r"[Ss]coped allocation with size [^.]*", text)
    return (f"refused ({kernel.group(1) if kernel else '?'}): "
            + (sizes.group(0) if sizes else text[:160]))


def kernel_times(events: dict, names: dict) -> dict:
    """``{index: {"fwd": [ms a call], "dkv": [...], "dq": [...], "program":
    [...]}}`` from one device's ``XLA Modules`` and ``XLA Ops`` events
    (``(name, start, duration)`` in ns): an operation belongs to the program
    run it lies inside."""
    modules = sorted((start, start + dur, names[name.split("(")[0]])
                     for name, start, dur in events["XLA Modules"]
                     if name.split("(")[0] in names)
    ops = sorted((start, name, dur) for name, start, dur in events["XLA Ops"]
                 if "splash_mha_" in name.split("=")[0])
    out: dict = {}
    at = 0
    for start, end, index in modules:
        run = dict.fromkeys(KERNELS, 0.0)
        while at < len(ops) and ops[at][0] < start:
            at += 1
        while at < len(ops) and ops[at][0] < end:
            _, name, dur = ops[at]
            for kernel in KERNELS:
                if f"splash_mha_{kernel}" in name.split("=")[0]:
                    run[kernel] += dur / 1e6
            at += 1
        run["program"] = (end - start) / 1e6
        per = out.setdefault(index, {k: [] for k in run})
        for key, value in run.items():
            per[key].append(value)
    return out


def device_events(profile_dir: str) -> dict:
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    data = ProfileData.from_file(paths[-1])
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            return {line.name: [(e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events]
                    for line in plane.lines
                    if line.name in ("XLA Modules", "XLA Ops")}
    raise SystemExit(f"no /device:TPU plane in {paths[-1]}: planes "
                     f"{[p.name for p in data.planes]}")


class Sweep:
    def __init__(self, name, compile_only, sharding):
        self.name = name
        self.shape = SHAPES[name]
        self.compile_only, self.sharding = compile_only, sharding
        self.rows: list = []
        self.index = 0
        os.makedirs(OUT, exist_ok=True)
        self.path = os.path.join(OUT, f"{name}.jsonl")
        open(self.path, "w").close()
        if not compile_only:
            self.inputs = self._inputs()

    def _inputs(self):
        import jax
        import jax.numpy as jnp

        keys = jax.random.split(jax.random.key(0), 3)
        seq = seq_of(self.shape)
        arrays = [jax.random.normal(key, (1, heads, seq, width),
                                    jnp.bfloat16)
                  for key, (heads, width) in zip(keys, (
                      self.shape["q"], self.shape["k"], self.shape["v"]))]
        return (*arrays, jnp.ones((1, seq), jnp.int32))

    def stage(self, label, tilings):
        """Compile and time one program a tiling; a row each."""
        import jax

        programs, rows = {}, []
        for tiling in tilings:
            self.index += 1
            row = {"shape": self.name, "stage": label, "index": self.index,
                   **(dict.fromkeys(KERNELS, (tiling,))
                      if isinstance(tiling, int) else tiling._asdict())}
            rows.append(row)
            t0 = time.monotonic()
            try:
                programs[self.index] = make_program(
                    self.index, self.shape, tiling, self.sharding)
                row["temp_mib"] = round(programs[
                    self.index].memory_analysis().temp_size_in_bytes / 2**20)
            except Exception as e:  # Mosaic's refusal is a row of the table
                row["error"] = refusal(e)
            row["compile_s"] = round(time.monotonic() - t0, 2)
        if not self.compile_only and programs:
            profile = os.path.join(OUT, "profile", f"{self.name}_{label}")
            for program in programs.values():  # warm-up, outside the trace
                jax.block_until_ready(program(*self.inputs))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(profile, profiler_options=options)
            for program in programs.values():
                for _ in range(CALLS):
                    out = program(*self.inputs)
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            times = kernel_times(device_events(profile), {
                f"jit_sweep_{i}": i for i in programs})
            for row in rows:
                per = times.get(row["index"], {"program": []})
                row["calls"] = len(per["program"])
                for key, values in per.items():
                    if values:
                        row[f"{key}_ms"] = round(statistics.median(values), 4)
        with open(self.path, "a") as f:
            f.writelines(json.dumps(row) + "\n" for row in rows)
        self.rows += rows
        return rows

    def isolate(self, label, triples, default):
        """Where a program of three candidates was refused, each candidate
        again beside the default's other two: which kernel Mosaic refused."""
        again = []
        for triple in triples:
            again += [tiling_of(triple, default, default),
                      tiling_of(default, triple, default),
                      tiling_of(default, default, triple)]
        return self.stage(label, list(dict.fromkeys(again)))  # dq has two


def best(rows, kernel):
    """The fastest tiling of one kernel, or None where none was timed."""
    ranked = sorted((r for r in rows if r.get(f"{kernel}_ms")),
                    key=lambda r: r[f"{kernel}_ms"])
    return tuple(ranked[0][kernel]) if ranked else None


def sweep_shape(name, compile_only, sharding, record_stages=True):
    """Without ``record_stages`` the two stages no entry of ``ops/flash.py``
    can take are left out (the library's blocked kernel on repeated keys, the
    fused backward): timed for the record at PRs 34, 38 and 41."""
    sweep = Sweep(name, compile_only, sharding)
    window = SHAPES[name]["window"]
    default = (512, 512, 512)
    triples = candidates(window)
    # the baseline first: today's tiling, and the check that the trace is read
    base = sweep.stage("today", [tiling_of(default, default, default)])
    if not compile_only and not base[0].get("fwd_ms"):
        raise SystemExit(f"no splash kernel found in the trace: {base}")
    if record_stages and SHAPES[name]["q"][1] == SHAPES[name]["v"][1]:
        # program ms is what compares: its kernels have other names
        sweep.stage("blocked", [512, 1024, 2048])
    blocks = sweep.stage("blocks", [tiling_of(t, t, t) for t in triples])
    refused = [r["fwd"] for r in blocks if "error" in r]
    if refused:
        sweep.isolate("blocks_alone", refused, default)
    if compile_only:
        return sweep.rows
    # (a kernel left at the default beside a candidate is the default timed
    # once more: best() keeps each tiling's fastest reading)
    timed = [r for r in sweep.rows if "error" not in r]
    top = {k: best(timed, k) or default for k in KERNELS}
    sweep.stage("best", [tiling_of(top["fwd"], top["dkv"], top["dq"])])
    if not record_stages:
        return sweep.rows
    # the fused backward: one kernel for dk, dv and dq's partials
    sweep.stage("fused", [tiling_of(top["fwd"], t)
                          for t in fused_candidates(window)])
    return sweep.rows


def table(rows) -> str:
    head = ("| stage | fwd | dkv | dq | fwd ms | dkv ms | dq ms | "
            "program ms | note |\n" + "|---" * 9 + "|\n")
    lines = []
    for r in rows:
        cells = [r["stage"], *("/".join(map(str, r[k])) if r[k] else "fused"
                               for k in KERNELS),
                 *(r.get(f"{k}_ms", "") for k in (*KERNELS, "program")),
                 r.get("error", f"temp {r.get('temp_mib', '')} MiB")]
        lines.append("| " + " | ".join(str(c) for c in cells) + " |")
    return head + "\n".join(lines)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--compile_only", action="store_true",
                        help="compile for a described v5e; time nothing")
    parser.add_argument("--no_record_stages", action="store_true",
                        help="leave out the blocked kernel and the fused "
                        "backward, which no entry can take")
    args = parser.parse_args()
    sharding = None
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    else:
        import jax

        device = jax.devices()[0]
        if device.platform != "tpu":
            raise SystemExit(f"a device time needs a TPU; found {device}")
        print(f"device: {device.device_kind} x {jax.device_count()}")
    for name in args.shapes.split(","):
        t0 = time.monotonic()
        rows = sweep_shape(name, args.compile_only, sharding,
                           not args.no_record_stages)
        with open(os.path.join(OUT, f"{name}.md"), "w") as f:
            f.write(table(rows) + "\n")
        print(f"\n## {name} ({time.monotonic() - t0:.0f} s, "
              f"{len(rows)} programs)\n")
        print(table(rows), flush=True)


if __name__ == "__main__":
    main()
