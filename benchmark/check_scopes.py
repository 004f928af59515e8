#!/usr/bin/env python3
"""Checks the scope split (``reduce/scopes.py``) against the small recorded
trace under ``benchmark/fixtures/scopes/`` (cut by
``fixtures/make_scopes_fixture.py`` from a traced ResNet-50 run on the v5e;
a directory of its own because ``check_reduce.py`` takes every
``fixtures/*.json.gz`` for a fixture of its kind): forward + backward +
optimizer + unattributed is the operations' total to the picosecond, each
agrees with the count the fixture's maker made another way, a step's phases
add up to the operations of one run, and hand-made ``op_name``s fall where
they should. Also walks a hand-made ``.xplane.pb`` through the wire reader.

    python3 benchmark/check_scopes.py        # exit 0 and "scopes ok", or the faults
"""

from __future__ import annotations

import glob
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

NAMES = {
    "jit(step)/jvp(forward)/ResNet/BottleneckBlock_3/Conv_0/conv_general_dilated":
        ("forward", "BottleneckBlock_3"),
    "jit(step)/transpose(jvp(forward))/ResNet/BottleneckBlock_3/BatchNorm_1/mul":
        ("backward", "BottleneckBlock_3"),
    "jit(step)/jvp(loss)/reduce_sum": ("forward", "-"),
    "jit(step)/transpose(jvp(loss))/mul": ("backward", "-"),
    "jit(step)/optimizer/add": ("optimizer", "-"),
    "jit(step)/jvp(forward)/ResNet/head/dot_general": ("forward", "head"),
    "jit(step)/jit(main)/ResNet/conv_init/conv_general_dilated":
        ("unattributed", "conv_init"),
    # a program from before the scopes: nothing of it is attributed
    "jit(step)/jit(main)/transpose(jvp(TransformerEncoder))/layer_3/attn/mul":
        ("unattributed", "attn"),
    "": ("unattributed", "-"),
}


def check_fixture(path: str) -> list:
    from reduce import scopes

    raw = scopes.load_fixture(path)
    want = raw["expected"]
    found = scopes.split(raw)
    tag = os.path.basename(path)
    if found is None:
        return [f"{tag}: no scoped operation found"]
    faults = []
    totals = found["totals_ps"]
    if sum(totals.values()) != found["ops_ps"]:
        faults.append(f"{tag}: phases {sum(totals.values())} ps, operations "
                      f"{found['ops_ps']} ps")
    for key in scopes.PHASES:
        if totals[key] != want[key]:
            faults.append(f"{tag}: {key} {totals[key]} ps, want {want[key]}")
    if (found["ops_ps"], found["runs"]) != (want["ops_ps"], want["runs"]):
        faults.append(f"{tag}: {found['ops_ps']} ps in {found['runs']} runs, "
                      f"want {want['ops_ps']} in {want['runs']}")
    if sum(found["unattributed_kinds"].values()) != totals["unattributed"]:
        faults.append(f"{tag}: unattributed kinds do not add up")
    # whole runs only, all of one shape: a step's phases are the median run's
    step_ms = sum(found["per_step_ms"].values())
    mean_ms = found["ops_ps"] / found["runs"] / 1e9
    if abs(step_ms - mean_ms) > 0.01 * mean_ms:
        faults.append(f"{tag}: phases of a step {step_ms} ms, operations of "
                      f"a run {mean_ms} ms")
    covered = 100.0 * (1 - totals["unattributed"] / found["ops_ps"])
    print(f"  {tag}: a step " + ", ".join(
        f"{k} {found['per_step_ms'][k]:.3f}" for k in scopes.PHASES)
        + f" ms; scoped {covered:.2f} %")
    return faults


def check_wire() -> list:
    """A two-event plane written by hand, field by field, and read back."""
    from reduce import scopes

    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out

    def field(number, payload):
        if isinstance(payload, int):
            return varint(number << 3) + varint(payload)
        return varint(number << 3 | 2) + varint(len(payload)) + payload

    def entry(key, message):
        return field(1, key) + field(2, message)

    stat_names = (field(5, entry(1, field(1, 1) + field(2, b"tf_op")))
                  + field(5, entry(2, field(1, 2) + field(2, b"program_id")))
                  + field(5, entry(3, field(1, 3)
                                   + field(2, b"jit(step)/optimizer/add:"))))
    op = field(1, 7) + field(2, b"%fusion.1 = f32[] fusion()") + field(
        5, field(1, 1) + field(7, 3)) + field(5, field(1, 2) + field(3, 99))
    module = field(1, 8) + field(2, b"jit_step(99)")
    lines = (field(3, field(2, b"XLA Modules") + field(3, 2) + field(
        4, field(1, 8) + field(2, 1000) + field(3, 500000)))
        + field(3, field(2, b"XLA Ops") + field(3, 2) + field(
            4, field(1, 7) + field(2, 2000) + field(3, 300000))))
    plane = (field(2, scopes.PLANE.encode()) + lines + stat_names
             + field(4, entry(7, op)) + field(4, entry(8, module)))
    other = field(2, b"/host:CPU")
    with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as f:
        f.write(field(1, other) + field(1, plane))
        f.flush()
        raw = scopes.load_plane(f.name)
    want = {"metadata": {7: {"name": "%fusion.1 = f32[] fusion()",
                             "tf_op": "jit(step)/optimizer/add",
                             "program_id": 99, "category": ""},
                         8: {"name": "jit_step(99)", "tf_op": "",
                             "program_id": None, "category": ""}},
            "modules": [[8, 3000, 500000]], "ops": [[7, 4000, 300000]]}
    return [] if raw == want else [f"hand-made plane read back as {raw}"]


def main() -> int:
    from reduce import scopes

    faults = []
    paths = sorted(glob.glob(os.path.join(HERE, "fixtures", "scopes",
                                          "*.json.gz")))
    if not paths:
        faults.append("no fixture under benchmark/fixtures/scopes/")
    for path in paths:
        faults += check_fixture(path)
    got = {name: (scopes.phase_of(name), scopes.module_of(name))
           for name in NAMES}
    if got != NAMES:
        faults.append(f"hand-made op_names: "
                      f"{ {k: v for k, v in got.items() if NAMES[k] != v} }")
    faults += check_wire()
    for fault in faults:
        print("FAULT:", fault)
    print("scopes ok" if not faults else f"{len(faults)} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
