#!/usr/bin/env bash
# Pre-image quality gate: kgct-lint (empty-findings baseline, no allowlist)
# then the tier-1 test suite. docker/build.sh runs this before building, so
# an image can never ship lint-dirty or test-broken code; run it standalone
# before any push for the same signal.
#
# Usage: scripts/check.sh [--lint-only] [--changed GIT_REF]
#   --lint-only        skip the tier-1 pytest run (seconds instead of
#                      minutes; the lint gate alone still blocks every
#                      rule violation)
#   --changed GIT_REF  lint only .py files touched vs GIT_REF (kgct-lint
#                      --changed): the pre-commit fast path, same rules
#
# Artifacts: the SARIF findings document lands next to the tier-1 log
# (/tmp/_kgct_check.sarif) so CI can upload it for PR annotation.
#
# Exit codes: 0 clean; non-zero on the first failing stage (pipefail —
# a tee'd pytest failure cannot launder its exit status).
set -euo pipefail

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "${REPO_ROOT}"
LINT_ONLY=0
CHANGED_REF=""

while [[ $# -gt 0 ]]; do
  case "$1" in
    --lint-only) LINT_ONLY=1; shift ;;
    --changed) CHANGED_REF="${2:?--changed needs a git ref}"; shift 2 ;;
    *) echo "unknown arg: $1" >&2; exit 2 ;;
  esac
done

echo ">> kgct-lint (empty-baseline gate)"
rm -f /tmp/_kgct_check.sarif
LINT_ARGS=(kubernetes_gpu_cluster_tpu chip_smoke.py --sarif /tmp/_kgct_check.sarif)
if [[ -n "${CHANGED_REF}" ]]; then
  LINT_ARGS+=(--changed "${CHANGED_REF}")
fi
python -m kubernetes_gpu_cluster_tpu.analysis.cli "${LINT_ARGS[@]}"

if [[ "${LINT_ONLY}" == 1 ]]; then
  echo ">> check.sh: lint clean (tier-1 skipped via --lint-only)"
  exit 0
fi

echo ">> tier-1 tests"
rm -f /tmp/_kgct_check.log
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider \
  2>&1 | tee /tmp/_kgct_check.log
rc=${PIPESTATUS[0]}
echo ">> check.sh: lint clean, tier-1 rc=${rc}"
exit "${rc}"
