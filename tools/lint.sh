#!/usr/bin/env bash
# Lint gate, wired next to the tier-1 test command (ROADMAP.md):
#
#   bash tools/lint.sh
#
# Runs ruff with the minimal repo config from pyproject.toml ([tool.ruff]:
# syntax errors, comparison/f-string misuse, undefined names). The hermetic
# CI image has no egress, so when ruff isn't installed the gate degrades to
# a byte-compile pass — syntax rot is still caught, and installing ruff
# upgrades the gate with no script change.
set -euo pipefail
cd "$(dirname "$0")/.."

# metrics self-check: import and validate every Prometheus exposition
# surface without a cluster (promtool-style conformance; no egress needed),
# plus DECLARED_METRIC_FAMILIES == the rendered family set (the runtime half
# of the metric-conformance contract graftlint checks statically below)
JAX_PLATFORMS=cpu python -m dynamo_tpu.utils.prometheus --check

# graftlint: JAX/asyncio-aware static analysis gating the hot path (pure
# stdlib AST — runs on the no-egress image with a bare interpreter). First
# the detectors prove themselves against their seeded fixtures, then the
# repo scan must come back with zero unsuppressed findings.
python -m tools.graftlint --self-check
python -m tools.graftlint

if command -v ruff >/dev/null 2>&1; then
    exec ruff check dynamo_tpu tests tools
fi
if python -c "import ruff" >/dev/null 2>&1; then
    exec python -m ruff check dynamo_tpu tests tools
fi
echo "lint: ruff unavailable (no-egress image); falling back to the" \
     "compileall syntax gate" >&2
exec python -m compileall -q dynamo_tpu tests tools
