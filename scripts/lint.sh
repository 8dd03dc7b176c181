#!/usr/bin/env bash
# Lint gate (reference scripts/lint.sh analog). This image ships no
# flake8/ruff (and installs are disallowed), so the gate is: every source
# byte-compiles, no syntax errors, no tabs-in-indentation, no merge
# markers, no stray breakpoints.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m compileall -q quiver_tpu quiver tests examples scripts benchmarks __graft_entry__.py setup.py

fail=0
if grep -rn --include='*.py' -P '^\t' quiver_tpu quiver tests examples scripts; then
  echo "^ tabs in indentation"; fail=1
fi
if grep -rn --include='*.py' -E '^(<<<<<<<|=======$|>>>>>>>)' quiver_tpu quiver tests examples scripts; then
  echo "^ merge markers"; fail=1
fi
if grep -rn --include='*.py' -E 'breakpoint\(\)|pdb\.set_trace' quiver_tpu quiver examples scripts; then
  echo "^ stray debugger"; fail=1
fi
exit $fail
