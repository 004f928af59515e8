"""Placement-hygiene rule (LDT801).

The r7 placement plane (``data/placement.py``) exists because every loader
used to end in a private ``jax.device_put`` on the consumer thread — the
step then waited on the H2D transfer instead of overlapping it. The
cheapest way to reintroduce that stall is one innocent
``jax.device_put(batch)`` in a hot-path module: it works, it is
synchronous, and nothing measures it separately.

This rule rejects direct calls to the H2D primitives — ``jax.device_put``
and ``make_array_from_single_device_arrays`` (however imported from jax) —
in the ``hot-paths`` modules from ``[tool.ldt-check]``, outside the two
modules allowed to own them: ``data/placement.py`` (the plane) and
``parallel/_compat.py`` (the one door both primitives are re-exported
from). Calls routed through the shim (``from ..parallel._compat import
device_put``) resolve to the compat module's dotted name and are legal;
the import map distinguishes them from jax's, so no suppression comments
are needed for the sanctioned paths. Same baseline machinery as LDT701:
grandfather a deliberate site with ``ldt check --update-baseline`` or a
``# ldt: ignore[LDT801]``.
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..core import Finding, ModuleInfo, Rule, register

# jax-qualified names of the H2D primitives the placement plane owns.
# make_array_from_process_local_data is the synchronous multi-process
# assembly — the exact consumer-thread transfer the plane replaces — so
# it is fenced too (the plane's own fallback uses the _compat re-export).
_H2D_QUALNAMES = {
    "jax.device_put",
    "jax.make_array_from_single_device_arrays",
    "jax.make_array_from_process_local_data",
}

# Modules allowed to touch them directly (besides the compat shim, which
# comes from config so a repo relayout keeps working).
_PLACEMENT_MODULE_SUFFIX = "data/placement.py"


@register
class PlacementHygiene(Rule):
    id = "LDT801"
    family = "placement"
    name = "placement-hygiene"
    description = (
        "hot-path modules: no direct jax.device_put / "
        "make_array_from_single_device_arrays — H2D belongs to the "
        "placement plane (data/placement.py) or the _compat shim, so "
        "transfers stay async, measured (trainer_h2d_ms), and off the "
        "consumer thread"
    )

    def check_module(self, module: ModuleInfo, config) -> Iterable[Finding]:
        import fnmatch

        hot_paths = getattr(config, "hot_paths", [])
        if not any(fnmatch.fnmatch(module.relpath, p) for p in hot_paths):
            return
        if module.relpath.endswith(_PLACEMENT_MODULE_SUFFIX):
            return
        if module.relpath == getattr(config, "compat_module", ""):
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            qn = module.qualname(node.func)
            if qn in _H2D_QUALNAMES:
                yield Finding(
                    self.id, module.relpath, node.lineno, node.col_offset,
                    f"direct {qn}(...) on a hot path runs the H2D transfer "
                    "synchronously on the calling thread, invisible to the "
                    "trainer_h2d_ms accounting — route it through the "
                    "placement plane (data/placement.py) or the _compat "
                    "re-export, or baseline a deliberate site",
                )
