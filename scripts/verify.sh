#!/usr/bin/env bash
# The ONE blessed verification entrypoint — builders and CI run this, nothing
# else. Two stages:
#   1. `ldt check` — the AST-based distributed-training lint gate (exits
#      non-zero on new findings; see README "Static analysis"). Run via the
#      standalone runner so the gate still works when the training package
#      itself fails to import.
#   2. The tier-1 command from ROADMAP.md verbatim: fast-tier tests on a
#      simulated 8-device CPU mesh, collection errors tolerated per-module,
#      pass-count echoed for the driver.
# Run from the repo root.
python "$(dirname "$0")/ldt_check.py" || exit $?
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
