#!/usr/bin/env python
"""Standalone `ldt check` runner for verify.sh / ci.sh.

The console `ldt check` imports the full training package (the top-level
__init__ eagerly imports jax/flax and the whole stack). That is fine day to
day, but a gate that dies with an ImportError from the stack it lints is
useless exactly when needed.

The analysis package itself is stdlib-only, so this runner registers a
synthetic parent package (name + __path__, no __init__ execution) and then
imports `lance_distributed_training_tpu.analysis` directly. The lint always
runs, whatever state the training stack is in.
"""

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "lance_distributed_training_tpu"

if PKG not in sys.modules:
    parent = types.ModuleType(PKG)
    parent.__path__ = [os.path.join(ROOT, PKG)]
    sys.modules[PKG] = parent
sys.path.insert(0, ROOT)

from lance_distributed_training_tpu.analysis.cli import check_main  # noqa: E402

if __name__ == "__main__":
    argv = sys.argv[1:]
    if not any(a == "--root" or a.startswith("--root=") for a in argv):
        argv += ["--root", ROOT]
    sys.exit(check_main(argv))
