"""Device time of the step program by the scope the program gave it.

``trainer.make_train_step`` wraps the forward, the loss and the optimizer in
``jax.named_scope``s, so every instruction's ``op_name`` starts
``jit(step)/jvp(forward)/...``, ``jit(step)/transpose(jvp(forward))/...`` or
``jit(step)/optimizer/...``, with flax's module names under it. On the TPU
the profiler keeps that name, not on the event but on the event's *metadata*
(stat ``tf_op``; looked at by hand, PR 24: an ``XLA Ops`` event itself
carries only ``device_offset_ps`` and ``device_duration_ps``), beside
``program_id`` (the id in the module's name ``jit_step(<id>)``) and
``hlo_category``. ``jax.profiler.ProfileData`` does not show metadata stats,
so this file walks the ``.xplane.pb`` itself: protobuf wire format, the few
fields of ``XSpace`` it needs, no other package.

A fusion carries the ``op_name`` of one of its instructions; copies between
memory spaces (``copy-start``/``copy-done``, ``slice-start``/``-done``) carry
none and are the unattributed rest, which is reported.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import statistics

PLANE = "/device:TPU:0"  # the first chip, as ``step_device_ms`` reads it
PHASES = ("forward", "backward", "optimizer", "unattributed")


# -- protobuf wire format, as far as XSpace needs it -------------------------


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, i, end):
    """``(field number, wire type, value)`` of one message: an int for
    varints and fixed widths, ``(start, end)`` for length-delimited ones."""
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = (i, i + size), i + size
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key = value = None
    for number, _, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _metadata(buf, span, stat_names) -> dict:
    """One ``XEventMetadata``: its name and the stats this file reads."""
    out = {"name": "", "tf_op": "", "program_id": None, "category": ""}
    for number, _, v in _fields(buf, *span):
        if number == 2:
            out["name"] = _text(buf, v)
        elif number == 5:  # XStat
            stat, value = None, None
            for n, _, sv in _fields(buf, *v):
                if n == 1:
                    stat = stat_names.get(sv)
                elif n in (3, 4):
                    value = sv
                elif n == 5:
                    value = _text(buf, sv)
                elif n == 7:  # a reference to a stat's name
                    value = stat_names.get(sv, "")
            if stat == "tf_op":
                out["tf_op"] = str(value).rstrip(":")
            elif stat == "program_id":
                out["program_id"] = value
            elif stat == "hlo_category":
                out["category"] = str(value)
    return out


def _line(buf, span):
    name, stamp_ns, events = "", 0, []
    for number, _, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            stamp_ns = v
        elif number == 4:
            events.append(v)
    return name, stamp_ns, events


def _event(buf, span):
    metadata_id = offset_ps = duration_ps = 0
    for number, _, v in _fields(buf, *span):
        if number == 1:
            metadata_id = v
        elif number == 2:
            offset_ps = v
        elif number == 3:
            duration_ps = v
    return metadata_id, offset_ps, duration_ps


def load_plane(path: str, plane: str = PLANE):
    """``{"metadata": {id: {...}}, "modules": [[id, start_ps, dur_ps]],
    "ops": [...]}`` of one device plane of an ``.xplane.pb``, times in ps
    from the trace's start; None if the file has no such plane."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    for number, wire, v in _fields(buf, 0, len(buf)):
        if number != 1 or wire != 2:
            continue
        parts = {2: [], 3: [], 4: [], 5: []}
        for n, w, pv in _fields(buf, *v):
            if n in parts and w == 2:
                parts[n].append(pv)
        if not parts[2] or _text(buf, parts[2][0]) != plane:
            continue
        stat_names = {}
        for entry in parts[5]:
            key, value = _map_entry(buf, entry)
            for n, _, sv in _fields(buf, *value):
                if n == 2:
                    stat_names[key] = _text(buf, sv)
        out = {"metadata": {}, "modules": [], "ops": []}
        used = set()
        for span in parts[3]:
            name, stamp_ns, events = _line(buf, span)
            key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(name)
            if key is None:
                continue
            for ev in events:
                metadata_id, offset_ps, duration_ps = _event(buf, ev)
                used.add(metadata_id)
                out[key].append([metadata_id, stamp_ns * 1000 + offset_ps,
                                 duration_ps])
        for entry in parts[4]:
            key, value = _map_entry(buf, entry)
            if key in used:
                out["metadata"][key] = _metadata(buf, value, stat_names)
        return out
    return None


def newest_xplane(*under: str):
    """The newest ``.xplane.pb`` under ``<under...>/profile``, or None."""
    paths = sorted(glob.glob(os.path.join(*under, "profile", "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return paths[-1] if paths else None


def load_run(run_dir: str):
    """:func:`load_plane` of a traced run's directory; None without one."""
    path = newest_xplane(run_dir)
    return load_plane(path) if path else None


def load_fixture(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    raw["metadata"] = {int(k): v for k, v in raw["metadata"].items()}
    return raw


# -- the split ---------------------------------------------------------------


def phase_of(tf_op: str) -> str:
    """``forward`` (the loss with it), ``backward``, ``optimizer`` or
    ``unattributed``, from the scopes in an instruction's ``op_name``:
    ``forward`` or ``loss`` without ``transpose(`` is forward, with it
    backward. A ``transpose(`` over anything else (the ``jvp(<Model>)`` of a
    program without the scopes) is not attributed."""
    parts = tf_op.split("/")
    if "optimizer" in parts:
        return "optimizer"
    if any(p in ("forward", "loss") or "(forward)" in p or "(loss)" in p
           for p in parts):
        return "backward" if "transpose(" in tf_op else "forward"
    return "unattributed"


def module_of(tf_op: str) -> str:
    """The top-level flax module under the scope (``ResNet/BottleneckBlock_3``
    → ``BottleneckBlock_3``, numbered blocks kept apart), or ``-``."""
    parts = [p for p in tf_op.split("/")[1:]
             if "(" not in p and p not in ("forward", "loss", "optimizer")]
    if len(parts) >= 3:
        return parts[1]
    return parts[0] if len(parts) == 2 else "-"


def step_ops(raw: dict, base: str = "jit_step") -> tuple:
    """``(runs, ops)``: the runs ``(program id, start, end)`` of the program
    ``base`` and its operations ``(metadata, start, duration)``, in ps."""
    metadata = raw["metadata"]
    runs = []
    for metadata_id, start, dur in raw["modules"]:
        name = metadata[metadata_id]["name"]
        if name.split("(")[0] == base:
            runs.append((int(name[name.index("(") + 1:name.rindex(")")]),
                         start, start + dur))
    programs = {r[0] for r in runs}
    ops = [(metadata[m], start, dur) for m, start, dur in raw["ops"]
           if metadata[m]["program_id"] in programs]
    return sorted(runs, key=lambda r: r[1]), ops


def split(raw: dict, base: str = "jit_step"):
    """Device time of ``base``'s operations by phase.

    ``totals_ps``: every operation of the program in the trace, by phase:
    the four add up to ``ops_ps`` exactly. ``per_step_ms``: each phase's
    time in one run of the program: per compiled shape the median over its
    runs of the operations that lie inside the run (the first and last run
    of a trace are cut and fall out of a median), weighted by how often the
    shape ran, as ``step_device_ms`` is. ``unattributed_kinds``: the rest by
    ``hlo_category``. None if the program never ran or no operation carries
    one of the three scopes (a program without them, as before PR 24)."""
    import bisect

    runs, ops = step_ops(raw, base)
    if not runs or not ops:
        return None
    totals = dict.fromkeys(PHASES, 0)
    kinds: dict = {}
    starts = [r[1] for r in runs]
    per_run = [dict.fromkeys(PHASES, 0) for _ in runs]
    for meta, start, dur in ops:
        phase = phase_of(meta["tf_op"])
        totals[phase] += dur
        if phase == "unattributed":
            kind = meta["category"] or meta["name"][:24]
            kinds[kind] = kinds.get(kind, 0) + dur
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start + dur <= runs[i][2] \
                and runs[i][0] == meta["program_id"]:
            per_run[i][phase] += dur
    if not (totals["forward"] or totals["backward"] or totals["optimizer"]):
        return None
    by_program: dict = {}
    for run, sums in zip(runs, per_run):
        by_program.setdefault(run[0], []).append(sums)
    per_step = {
        phase: sum(statistics.median(s[phase] for s in sums) * len(sums)
                   for sums in by_program.values()) / len(runs) / 1e9
        for phase in PHASES}
    return {"totals_ps": totals, "ops_ps": sum(dur for _, _, dur in ops),
            "per_step_ms": per_step, "runs": len(runs),
            "unattributed_kinds": kinds}


def of_run(ctx: dict):
    """The traced run's split, read once and kept in ``ctx``."""
    if "_scopes" not in ctx:
        # run.py wipes benchmark/out/runs (or /rehearsal) when it starts,
        # so what lies under benchmark/out/*/<cell>/ is this run's
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        raw = load_run(os.path.join(here, "out", "*", ctx["cell"]["name"],
                                    "*"))
        ctx["_scopes"] = split(raw) if raw else None
    return ctx["_scopes"]


def per_step_ms(ctx: dict, phase: str):
    found = of_run(ctx)
    return found["per_step_ms"][phase] if found else None
