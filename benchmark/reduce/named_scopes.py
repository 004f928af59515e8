"""Device time of the step program under scopes the model names itself.

``trainer.make_train_step`` names the phases (``reduce/scopes.py``); inside
the forward the model names its parts with ``jax.named_scope``:
``attention``, ``moe.router``, ``moe.dispatch``, ``moe.experts``,
``moe.combine``, ``lm_head``. A scope is one ``/``-separated part of an
operation's ``op_name`` (``tf_op`` in the trace), in the forward as in the
backward pass, where the phase's part reads ``transpose(jvp(forward))`` and
the model's parts stay as they are. A program that has no such scope (the
parent of the PR that added them) gives None, never an error.

One kind of operation cannot be found that way. On the TPU XLA rewrites
``jax.lax.ragged_dot`` into a kernel of its own and the rewrite drops the
instruction's ``op_name``: the nine ``%ragged-dot-none`` custom calls of a
step (and a ``%ragged-dot-metadata`` one) read ``ragged-dot-none`` there, and
the copies that lay the experts' matrices out for the kernel read the
parameter's name, ``state.params['layer_0']['moe']['w_up']`` (looked at by
hand, PR 26: 26.4 and 3.4 ms of a 128.6 ms step). So a reader may also name
what to look for in an ``op_name`` that has no scope at all, no ``/`` in it
(``also``): the kernel's name is part of the yardstick.
"""

from __future__ import annotations

import bisect
import os
import statistics

from reduce import scopes


def _raw(ctx: dict):
    """The traced run's device plane, read once and kept in ``ctx``."""
    if "_scope_plane" not in ctx:
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ctx["_scope_plane"] = scopes.load_run(os.path.join(
            here, "out", "*", ctx["cell"]["name"], "*"))
    return ctx["_scope_plane"]


def under(tf_op: str, names: tuple) -> bool:
    """Is one of ``names`` a part of this ``op_name``, plain or wrapped by a
    transformation (``jvp(loss)``)?"""
    parts = tf_op.split("/")
    return any(n in parts or any(f"({n})" in p for p in parts)
               for n in names)


def unscoped(tf_op: str, also: tuple) -> bool:
    """An ``op_name`` with no scope in it that holds one of ``also``."""
    return "/" not in tf_op and any(a in tf_op for a in also)


def ms_of(raw: dict, names: tuple, base: str = "jit_step", also: tuple = ()):
    """ms in one run of ``base`` spent in operations under ``names``, or
    whose ``op_name`` has no scope and holds one of ``also``: per
    compiled shape the median over its runs of the operations wholly inside
    the run, weighted by how often the shape ran, as ``step_device_ms`` and
    the phase split are. None if the program never ran or nothing of it is
    under these names."""
    runs, ops = scopes.step_ops(raw, base)
    if not runs:
        return None
    starts = [r[1] for r in runs]
    per_run = [0] * len(runs)
    found = False
    for meta, start, dur in ops:
        if not under(meta["tf_op"], names) and not unscoped(
                meta["tf_op"], also):
            continue
        found = True
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start + dur <= runs[i][2] \
                and runs[i][0] == meta["program_id"]:
            per_run[i] += dur
    if not found:
        return None
    by_program: dict = {}
    for run, total in zip(runs, per_run):
        by_program.setdefault(run[0], []).append(total)
    return sum(statistics.median(v) * len(v)
               for v in by_program.values()) / len(runs) / 1e9


GROUPED_PRODUCTS = ("ragged-dot", "['moe']['w_")  # the kernel XLA makes
# of ``ragged_dot`` and the layout copies of the experts' matrices


def per_step_ms(ctx: dict, *names: str, also: tuple = ()):
    raw = _raw(ctx)
    return ms_of(raw, names, also=also) if raw else None
