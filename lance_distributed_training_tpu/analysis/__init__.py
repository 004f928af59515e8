"""Static analysis for distributed-training invariants (``ldt check``).

An AST-based lint subsystem with project-specific rules: plan determinism
(LDT001-003), jit purity (LDT101-102), concurrency hygiene (LDT201-203),
resource ownership (LDT301), cross-module wire-protocol consistency
(LDT501), the whole-program concurrency model
(``concmodel.py``): lock-order deadlock cycles (LDT1001), cross-thread
unsynchronized shared state (LDT1002), dispatcher exhaustiveness over the
protocol's MSG_* vocabulary (LDT1003) — and, layered on the same
ProgramInfo without a second parse (``ownermodel.py``), the
ownership/lifecycle dataflow (LDT1201 leak-on-path, LDT1202
double-release, LDT1203 use-after-release over the
``[tool.ldt-check.resources]`` vocabulary) and the content-purity taint
rule (LDT1301 over ``[tool.ldt-check.content-paths]``). Two runtime
witnesses close the evidence loop: the lock-order sanitizer
(``utils/lockorder.py`` + ``ldt check --lock-witness``) and the
resource-lease sanitizer (``utils/leaktrack.py`` + ``ldt check
--leak-witness``), each corroborating or pruning its static family.
``ldt graph --dot`` renders the thread/lock topology, ``--ownership``
adds resource nodes and leak edges. Configured under ``[tool.ldt-check]``
in pyproject.toml; per-line suppression via ``# ldt: ignore[LDTxxx]``
(LDT10xx/12xx/13xx ignores require a ``-- reason``); grandfathered
findings live in a baseline file.

Programmatic surface::

    from lance_distributed_training_tpu.analysis import analyze, load_config
    findings = analyze(repo_root, load_config(repo_root))
"""

from .config import CheckConfig, load_config  # noqa: F401
from .core import (  # noqa: F401
    Finding,
    ModuleInfo,
    Rule,
    all_rules,
    analyze,
    analyze_project,
    register,
)
from .cli import check_main, graph_main  # noqa: F401
from .concmodel import ProgramInfo, build_program  # noqa: F401
from .ownermodel import OwnerModel, build_owner_model  # noqa: F401

__all__ = [
    "CheckConfig",
    "Finding",
    "ModuleInfo",
    "OwnerModel",
    "ProgramInfo",
    "Rule",
    "all_rules",
    "analyze",
    "analyze_project",
    "build_owner_model",
    "build_program",
    "check_main",
    "graph_main",
    "load_config",
    "register",
]
