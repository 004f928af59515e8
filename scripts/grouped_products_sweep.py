"""Step 0 of a change to the held experts' grouped products: each of the
three forms a layer needs, timed alone on the chip under XLA's kernel and
under the Pallas grouped matmul of the installed JAX
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm``, ``gmm`` with
``transpose_rhs`` and ``tgmm``) over a handful of tilings, at the shapes the
five sparse cells call them.

A layer's three products are two of ``[R, H] x [G, H, D] -> [R, D]`` (gate,
up: ``in`` below) and one of ``[R, D] x [G, D, H] -> [R, H]`` (down:
``out``); each has three forms:

* ``fwd``: rows x weights, ``jax.lax.ragged_dot`` or ``gmm``;
* ``dlhs``: cotangent x weights^T into the rows' shape, ``ragged_dot``'s own
  derivative or ``gmm(transpose_rhs=True)`` against the same weights;
* ``drhs``: rows^T x cotangent into ``[G, K, N]``, ``ragged_dot``'s own
  derivative or ``tgmm``.

The weights come in as the f32 parameters they are and are cast to bf16
inside the program, as ``models/moe.py`` casts them, so a layout copy that
XLA's kernel wants and the cast that it can ride on are both in the time;
``drhs`` ends in the cast back to f32. The kernel's ``fwd`` and ``dlhs``
include the pass that zeroes the rows past the last group's end (the library
leaves what the buffer held there). A step runs the forward products twice
(the routed rule recomputes them) and each derivative once:

    layer = 2 * (2 * fwd.in + fwd.out)
            + 2 * (dlhs.in + drhs.in) + dlhs.out + drhs.out

``whole`` rows time the three products and their gate together, the
forward and ``value_and_grad`` in one program (XLA merges the repeated
forward, so: each form once) through ``jax.lax.ragged_dot`` and through
``ops/grouped.py``'s kernel form at the fastest tilings that leave the
kernels room (:func:`vmem_bytes`): what the casts, the layout copies and the
zeroing cost when the products share a program. **They are the verdict**
(:func:`verdict`; ``ops/grouped.py``'s rule of admission): a shape gets an
entry in ``TILINGS`` iff the kernels' whole-layer program is ahead of XLA's
by ``ops/grouped.py``'s ``AHEAD`` on even groups and on the cell's skew
alike; the single forms are the way to find the tilings. Group sizes are the
live rows spread over the held experts evenly (``even``) or so that the
largest group over the mean is what the cell's ledger line reads
(``skewed``: a seeded softmax of normal draws, sharpened until it is); the
rows past the last group are dead.

    chiprun --chips 1 --timeout 1800 -- python3 scripts/grouped_products_sweep.py
    python3 scripts/grouped_products_sweep.py --compile_only   # no chip:
        # which tilings Mosaic takes, compiled for a described v5e

Times are device times from the trace's ``XLA Modules`` line, never the host
clock, so the timing mode wants a TPU and fails without one. Writes
``chiprun_out/grouped_sweep/<shape>.jsonl`` (a line a program) and appends
the shape's table, with its verdict line, to ``summary.md``. The file an
entry of ``TILINGS`` rests on is copied to ``scripts/grouped_sweep/`` and
committed there (no commit takes anything under ``chiprun_out/``), where
``tests/test_grouped.py`` holds the table to it. A
stage's trace (tens of MiB) is deleted once its ``XLA Modules`` line is read
unless ``--keep_traces``: the chip tool brings back 64 MiB and no more, so
one shape a call. Not tier-1; ``PERF.md`` section 6 holds the tables it
gave, and ``ops/grouped.py``'s ``TILINGS`` the entries that came of them.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import shutil
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: rows built, groups held, live rows, hidden H, expert width D, and the
# largest group over the mean as the cell's ledger line reads it (PR 49)
SHAPES = {
    "moonlight": (12288, 8, 6144, 2048, 1408, 1.69),
    "qwen3_next": (20480, 32, 10240, 2048, 512, 1.64),
    "smallthinker": (49152, 16, 24576, 2560, 768, 7.98),
    "zaya1": (8192, 8, 4096, 2048, 2048, 4.21),
    "olmoe": (65536, 64, 65536, 2048, 1024, 2.51),
}
FORMS = ("fwd", "dlhs", "drhs")
CALLS = 10  # after one call of warm-up
OUT = os.path.join("chiprun_out", "grouped_sweep")
_LANES = 128


def group_sizes(groups: int, live: int, max_over_mean: float, seed: int = 0):
    """``groups`` whole sizes that add up to ``live``, the largest
    ``max_over_mean`` times the mean (1: all equal)."""
    import numpy as np

    if max_over_mean <= 1:
        sizes = np.full(groups, live // groups)
    else:
        z = np.random.default_rng(seed).normal(size=groups)
        lo, hi = 0.0, 50.0
        for _ in range(60):  # sharpen the softmax until the largest fits
            mid = (lo + hi) / 2
            p = np.exp(mid * (z - z.max()))
            p /= p.sum()
            lo, hi = (mid, hi) if p.max() * groups < max_over_mean else (lo, mid)
        sizes = np.floor(p * live).astype(np.int64)
    sizes[np.argmax(sizes)] += live - sizes.sum()
    return sizes.astype(np.int32)


def widths(size: int):
    """Tile widths to try over a dimension of ``size``: its two widest whole
    divisors in lane groups of 256 or more (2,048: 1,024 and 2,048) and,
    where it has no such pair (1,408 is eleven lane groups: itself alone),
    512 and 768 too, whose last tile is ragged and the library masks."""
    lanes = size // _LANES
    found = [d * _LANES for d in range(2, lanes + 1) if lanes % d == 0][-2:]
    if len(found) < 2:
        found = [w for w in (512, 768) if w < size] + found
    return found


def tilings(rows: int, k: int, n: int, wide: bool):
    """``(tm, tk, tn)`` candidates for a product that contracts ``k`` into
    ``n`` columns."""
    tms = [t for t in (128, 256, 512) if rows % t == 0]
    out = list(itertools.product(tms, widths(k), widths(n)))
    if wide:  # the narrowest lane group the issue names, once for the record
        out.append((512, k, 128))
    return out


def vmem_bytes(form: str, tiling) -> int:
    """What a kernel at ``tiling`` holds in VMEM by its blocks: every operand
    and the result twice (the pipeline's two buffers), bf16, and the f32 sum
    (``gmm``: a ``[tm, tn]`` tile of rows; ``tgmm``: a group's ``[tk, tn]``
    tile). A kernel alone compiled at more than Mosaic's 16 MiB by this count
    and the same kernel inside a layer's program was refused (PR 50), so the
    tilings the layer is timed at stay under :data:`VMEM_ROOM`."""
    tm, tk, tn = tiling
    kept = tk * tn if form == "drhs" else tm * tn
    return 4 * (tm * tk + tk * tn + tm * tn) + 4 * kept


VMEM_ROOM = 14 * 2 ** 20


def make_program(index, form, side, sizes, tiling, sharding=None):
    """One form of one product as a compiled program named by ``index``
    (the trace's module events carry the name): ``tiling`` None is XLA's."""
    import jax
    import jax.numpy as jnp

    from lance_distributed_training_tpu.ops import grouped

    rows, groups, _, hidden, width, _ = sizes
    k, n = (hidden, width) if side == "in" else (width, hidden)
    bf16 = jnp.bfloat16

    if tiling is None:
        product = jax.lax.ragged_dot
    else:  # the shipped rule at one tiling: its gmm, its zeroing, its tgmm
        def product(xs, w, gs):
            return grouped.kernel_product(xs, w, gs,
                                          grouped.Tiling(*(tiling,) * 3))

    def fwd(xs, w, gs):
        return product(xs, w.astype(bf16), gs)

    def dlhs(g, w, gs):  # linear in the rows: the zeros are never read
        return jax.vjp(lambda x: product(x, w.astype(bf16), gs),
                       jnp.zeros((rows, k), bf16))[1](g)[0]

    def drhs(xs, g, gs):
        return jax.vjp(lambda w: product(xs, w.astype(bf16), gs),
                       jnp.zeros((groups, k, n), jnp.float32))[1](g)[0]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    xs, g = spec((rows, k), bf16), spec((rows, n), bf16)
    w, gs = spec((groups, k, n), jnp.float32), spec((groups,), jnp.int32)
    program, args = {"fwd": (fwd, (xs, w, gs)), "dlhs": (dlhs, (g, w, gs)),
                     "drhs": (drhs, (xs, g, gs))}[form]
    program.__name__ = f"gp_{index}"
    return jax.jit(program).lower(*args).compile(), args


def make_whole(index, sizes, kernel, sharding=None):
    """A layer's three products and their gate in one program, the forward
    and ``value_and_grad``, through ``jax.lax.ragged_dot`` (``kernel`` None)
    or ``ops/grouped.py``'s kernel form at ``kernel``'s three tilings a
    side."""
    import jax
    import jax.numpy as jnp

    from lance_distributed_training_tpu.ops import grouped

    rows, groups, _, hidden, width, _ = sizes
    bf16 = jnp.bfloat16

    def product(xs, w, gs):
        if kernel is None:
            return jax.lax.ragged_dot(xs, w, gs)
        k, n = w.shape[1:]
        return grouped.kernel_product(xs, w, gs, grouped.Tiling(
            *kernel["in" if k == hidden else "out"]))

    def experts(xs, gs, w_gate, w_up, w_down):
        gate = product(xs, w_gate.astype(bf16), gs)
        up = product(xs, w_up.astype(bf16), gs)
        return product(jax.nn.silu(gate) * up, w_down.astype(bf16), gs)

    def program(xs, ct, gs, *ws):
        def loss(xs, *ws):
            return (experts(xs, gs, *ws).astype(jnp.float32) * ct).sum()

        y = experts(xs, gs, *ws)
        return y, jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(xs, *ws)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    program.__name__ = f"gp_{index}"
    args = (spec((rows, hidden), bf16), spec((rows, hidden), jnp.float32),
            spec((groups,), jnp.int32),
            spec((groups, hidden, width), jnp.float32),
            spec((groups, hidden, width), jnp.float32),
            spec((groups, width, hidden), jnp.float32))
    return jax.jit(program).lower(*args).compile(), args


def refusal(error: Exception) -> str:
    import re

    text = " ".join(str(error).split())
    sizes = re.search(r"[Ss]coped allocation with size [^.]*", text)
    return "refused: " + (sizes.group(0) if sizes else text[:200])


class Sweep:
    def __init__(self, name, compile_only, sharding, keep_traces=False):
        self.name, self.sizes = name, SHAPES[name]
        self.compile_only, self.sharding = compile_only, sharding
        self.keep_traces = keep_traces
        self.rows: list = []
        self.arrays: dict = {}
        self.index = 0
        os.makedirs(OUT, exist_ok=True)
        self.path = os.path.join(OUT, f"{name}.jsonl")
        open(self.path, "w").close()

    def inputs(self, args, kind):
        """A program's arrays from its shapes: normal draws (one array a
        shape and type, shared by the programs that take it), and the group
        sizes of ``kind`` where it takes them."""
        import jax
        import jax.numpy as jnp

        rows, groups, live, _, _, skew = self.sizes

        def array(a):
            key = (a.shape, jnp.dtype(a.dtype).name)
            if a.dtype == jnp.int32:
                return jnp.asarray(group_sizes(
                    groups, live, skew if kind == "skewed" else 1))
            if key not in self.arrays:
                self.arrays[key] = (jax.random.normal(
                    jax.random.key(len(self.arrays)), a.shape, jnp.float32)
                    * (1.0 if a.dtype == jnp.bfloat16 else 0.02)).astype(
                        a.dtype)
            return self.arrays[key]

        return [array(a) for a in args]

    def stage(self, label, kind, programs):
        """Compile and time ``programs``: ``(row, make)`` pairs."""
        import jax

        built, rows = {}, []
        for row, make in programs:
            self.index += 1
            row.update(shape=self.name, stage=label, sizes=kind,
                       index=self.index)
            rows.append(row)
            t0 = time.monotonic()
            try:
                built[self.index] = make(self.index)
            except Exception as e:  # Mosaic's refusal is a row of the table
                row["error"] = refusal(e)
            row["compile_s"] = round(time.monotonic() - t0, 2)
        if not self.compile_only and built:
            from splash_tiling_sweep import device_events

            profile = os.path.join(OUT, "profile", f"{self.name}_{label}")
            held = {}
            for index, (program, args) in built.items():
                held[index] = self.inputs(args, kind)
                jax.block_until_ready(program(*held[index]))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(profile, profiler_options=options)
            for index, (program, _) in built.items():
                for _ in range(CALLS):
                    out = program(*held[index])
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            times: dict = {}
            for name, _, dur in device_events(profile)["XLA Modules"]:
                times.setdefault(name.split("(")[0], []).append(dur / 1e6)
            if not self.keep_traces:  # tens of MiB a stage
                shutil.rmtree(profile)
            for row in rows:
                runs = times.get(f"jit_gp_{row['index']}", [])
                row["calls"] = len(runs)
                if runs:
                    row["ms"] = round(statistics.median(runs), 4)
        with open(self.path, "a") as f:
            f.writelines(json.dumps(row) + "\n" for row in rows)
        self.rows += rows
        return rows


def in_room(row) -> bool:
    """A kernel's row whose tiling the layer can run at."""
    return bool(row["tiling"]) and vmem_bytes(
        row["form"], row["tiling"]) <= VMEM_ROOM


def sweep_shape(name, compile_only, sharding, keep_traces=False):
    sweep = Sweep(name, compile_only, sharding, keep_traces)
    sizes = SHAPES[name]
    rows, _, _, hidden, width, _ = sizes
    wide = name == "moonlight"

    def product(form, side, tiling):
        row = {"form": form, "side": side, "tiling": tiling}
        if tiling:
            row["vmem_mib"] = round(vmem_bytes(form, tiling) / 2 ** 20, 1)
        return (row, lambda index: make_program(index, form, side, sizes,
                                                tiling, sharding))

    # a square expert (ZAYA1: 2,048 x 2,048) multiplies the same shapes both
    # ways: one side is timed and stands for both
    sides = ("in",) if hidden == width else ("in", "out")

    def both_sides(rows):
        copies = [{**r, "side": "out"} for r in rows if len(sides) == 1]
        sweep.rows += copies
        with open(sweep.path, "a") as f:
            f.writelines(json.dumps(row) + "\n" for row in copies)
        return rows + copies

    programs = []
    for side in sides:
        k, n = (hidden, width) if side == "in" else (width, hidden)
        for form in FORMS:
            programs.append(product(form, side, None))
            # dlhs contracts the forward's columns into its rows' width
            programs += [product(form, side, t) for t in tilings(
                rows, *((n, k) if form == "dlhs" else (k, n)), wide)]
    timed = both_sides(sweep.stage("forms", "skewed", programs))
    if compile_only:  # the layer whole at the first tilings Mosaic took
        took = {(r["side"], r["form"]): tuple(r["tiling"]) for r in timed[::-1]
                if in_room(r) and "error" not in r}
        kernel = {side: tuple(took[side, form] for form in FORMS)
                  for side in ("in", "out")}
        sweep.stage("whole", "skewed", [
            ({"form": "whole", "side": "", "tiling": kernel},
             lambda index: make_whole(index, sizes, kernel, sharding))])
        return sweep.rows, None
    best, next_best = {}, {}
    for side, form in itertools.product(("in", "out"), FORMS):
        ranked = sorted((r for r in timed if r.get("ms") and in_room(r)
                         and (r["side"], r["form"]) == (side, form)),
                        key=lambda r: r["ms"])
        if ranked:
            best[side, form] = tuple(ranked[0]["tiling"])
            next_best[side, form] = tuple(ranked[min(1, len(ranked) - 1)][
                "tiling"])
    # the winners and XLA's again on even groups, and the layer whole
    again = [product(form, side, t) for (side, form), t in best.items()
             if side in sides]
    again += [product(form, side, None)
              for side, form in itertools.product(sides, FORMS)]
    both_sides(sweep.stage("forms", "even", again))
    if len(best) == 6:
        # the layer whole: XLA's, the fastest tiling of each form and, should
        # Mosaic refuse that inside a layer's program, each form's runner-up
        choices = [None]
        for pick in (best, next_best):
            kernel = {side: tuple(pick[side, form] for form in FORMS)
                      for side in ("in", "out")}
            if kernel not in choices:
                choices.append(kernel)
        for kind in ("skewed", "even"):
            sweep.stage("whole", kind, [
                ({"form": "whole", "side": "", "tiling": choice},
                 lambda index, c=choice: make_whole(index, sizes, c,
                                                    sharding))
                for choice in choices])
    return sweep.rows, best


def layer_ms(rows, kind, pick):
    """The formula of the docstring over the rows ``pick`` keeps, the
    fastest of each form."""
    def ms(side, form):
        found = [r["ms"] for r in rows if r.get("ms") and r["stage"] == "forms"
                 and r["sizes"] == kind and (r["side"], r["form"]) == (
                     side, form) and pick(r)]
        return min(found) if found else float("nan")

    return (2 * (2 * ms("in", "fwd") + ms("out", "fwd"))
            + 2 * (ms("in", "dlhs") + ms("in", "drhs"))
            + ms("out", "dlhs") + ms("out", "drhs"))


def verdict(rows) -> dict:
    """The rule of admission over a shape's rows: the whole-layer program's
    ms through XLA's kernel and through the Pallas kernels on ``even`` and
    ``skewed`` groups, ``entry`` (the kernels ahead by ``grouped.AHEAD`` on
    both) and ``tiling``: of the kernels' choices timed, the admitted one
    that is fastest over both, else the fastest."""
    from lance_distributed_training_tpu.ops.grouped import AHEAD

    whole = [r for r in rows if r["stage"] == "whole" and r.get("ms")]
    xla = {r["sizes"]: r["ms"] for r in whole if not r["tiling"]}
    ours: dict = {}
    for r in whole:
        if r["tiling"]:
            ours.setdefault(json.dumps(r["tiling"]), {})[r["sizes"]] = r["ms"]
    kinds = ("even", "skewed")

    def admitted(ms):
        return all(xla.get(k) and ms.get(k) and ms[k] <= (1 - AHEAD) * xla[k]
                   for k in kinds)

    ranked = sorted(ours.items(), key=lambda c: (
        not admitted(c[1]), sum(c[1].get(k, float("inf")) for k in kinds)))
    tiling, ms = ranked[0] if ranked else ("null", {})
    return {**{k: (xla.get(k), ms.get(k)) for k in kinds},
            "entry": admitted(ms), "tiling": json.loads(tiling)}


def entries(name, rows) -> dict:
    """What ``TILINGS`` may hold for a shape by its rows: nothing, or the
    admitted tilings under the two calls' keys (gate and up; down)."""
    says = verdict(rows)
    if not says["entry"]:
        return {}
    built, groups, _, hidden, width, _ = SHAPES[name]
    return {(built, groups, *kn): tuple(map(tuple, says["tiling"][side]))
            for side, kn in (("in", (hidden, width)),
                             ("out", (width, hidden)))}


def summary(name, rows, best) -> str:
    lines = [f"### {name}: rows built, groups, live, H, D, max/mean = "
             f"{SHAPES[name]}", "",
             "| sizes | side | form | XLA ms | kernel ms | tiling |",
             "|---|---|---|---|---|---|"]
    for kind in ("skewed", "even"):
        for side, form in itertools.product(("in", "out"), FORMS):
            both = [r for r in rows if r.get("ms") and r["stage"] == "forms"
                    and r["sizes"] == kind
                    and (r["side"], r["form"]) == (side, form)]
            xla = [r["ms"] for r in both if not r["tiling"]]
            ours = sorted((r for r in both if in_room(r)),
                          key=lambda r: r["ms"])
            lines.append(
                f"| {kind} | {side} | {form} | {xla[0] if xla else ''} | "
                f"{ours[0]['ms'] if ours else ''} | "
                f"{'/'.join(map(str, ours[0]['tiling'])) if ours else ''} |")
        lines.append(
            f"| {kind} | layer | by the formula | "
            f"{layer_ms(rows, kind, lambda r: not r['tiling']):.3f} | "
            f"{layer_ms(rows, kind, in_room):.3f} | |")
        for r in rows:
            if r["stage"] == "whole" and r["sizes"] == kind and r.get("ms"):
                lines.append(f"| {kind} | layer | whole, "
                             f"{'kernel' if r['tiling'] else 'XLA'} | "
                             + (f" | {r['ms']} | " if r["tiling"]
                                else f"{r['ms']} | | ") + "|")
    says = verdict(rows)
    lines += ["", f"best tilings: {best}", "",
              f"{name}: whole, XLA | whole, kernel: " + ", ".join(
                  f"{kind} {says[kind][0]} | {says[kind][1]}"
                  for kind in ("even", "skewed"))
              + f"; entry: {'yes' if says['entry'] else 'no'}"
              + f" at {says['tiling']}", ""]
    return "\n".join(lines)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--compile_only", action="store_true",
                        help="compile for a described v5e; time nothing")
    parser.add_argument("--keep_traces", action="store_true",
                        help="leave each stage's trace under the output "
                             "directory (tens of MiB a stage)")
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
    os.makedirs(OUT, exist_ok=True)
    for name in args.shapes.split(","):
        t0 = time.monotonic()
        rows, best = sweep_shape(name, args.compile_only, sharding,
                                 args.keep_traces)
        refused = [r for r in rows if "error" in r]
        print(f"\n## {name} ({time.monotonic() - t0:.0f} s, {len(rows)} "
              f"programs, {len(refused)} refused)\n")
        for r in refused:
            print(f"{r['side']} {r['form']} {r['tiling']}: {r['error']}")
        if not args.compile_only:
            text = summary(name, rows, best)
            with open(os.path.join(OUT, "summary.md"), "a") as f:
                f.write(text + "\n")
            print(text, flush=True)


if __name__ == "__main__":
    main()
