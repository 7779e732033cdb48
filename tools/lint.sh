#!/usr/bin/env bash
# tools/lint.sh — the static-analysis gate, runnable anywhere tier-1 runs.
#
#   1. syntax pass: every file under kubernetes_tpu/ must byte-compile
#      (the pyflakes-equivalent floor; ktpu-lint skips unparseable files,
#      so this pass is what turns a syntax error into a hard failure);
#   2. ktpu-lint over the package with the committed baseline, failing on
#      any NEW finding and printing a machine-readable [ktpu-lint] JSON
#      summary line (one JSON object, last) for CI wrappers to parse.
#
# Exit: 0 clean, non-zero on syntax errors or new findings.
set -o pipefail

REPO_ROOT="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO_ROOT"

PY="${PYTHON:-python}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "[lint] syntax pass (compileall) ..."
if ! "$PY" -m compileall -q kubernetes_tpu; then
    echo '[ktpu-lint] {"tool": "ktpu-lint", "ok": false, "error": "syntax"}'
    exit 1
fi

echo "[lint] ktpu-lint (fail on new findings vs committed baseline) ..."
"$PY" -m kubernetes_tpu.analysis --json "$@"
rc=$?
if [ $rc -ne 0 ]; then
    echo "[lint] FAILED: new findings above (suppress with a reasoned"
    echo "       '# ktpu-lint: disable=KTL00N -- why', fix the code, or"
    echo "       deliberately accept via --write-baseline)"
fi
exit $rc
