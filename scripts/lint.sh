#!/usr/bin/env bash
# graftlint wrapper: static analysis over the repo, CPU-pinned.
#
# The analyzers never use a JAX backend; an accidental backend init
# would take the chip from the job that owns it, so pin through the one
# shared implementation, utils.backend.pin_cpu (env var +
# jax.config.update).
# Non-zero exit iff findings (the tier-1 suite enforces the same via
# tests/test_static_analysis.py::test_repo_clean).
#
# Usage: scripts/lint.sh [--changed] [paths...]
#          (default paths: tensor2robot_tpu scripts)
#
# --changed is the CI fast path: lint only files git reports as
# modified/untracked vs HEAD, through the engine's content-hash
# incremental cache (.git/graftlint-cache.json — per-clone, never
# committed). Exits 0 immediately when nothing relevant changed. A full
# uncached lint remains the release gate (cached .gin results can go
# stale against module edits; see `lint --help`).
set -euo pipefail
cd "$(dirname "$0")/.."

changed=0
args=()
for arg in "$@"; do
  if [[ "$arg" == "--changed" ]]; then
    changed=1
  else
    args+=("$arg")
  fi
done

if [[ "$changed" == "1" ]]; then
  mapfile -t files < <(
    { git diff --name-only HEAD; git ls-files --others --exclude-standard; } \
      | sort -u | grep -E '\.(py|gin)$' || true)
  existing=()
  for f in "${files[@]}"; do
    [[ -f "$f" ]] && existing+=("$f")
  done
  if [[ "${#existing[@]}" == "0" ]]; then
    echo "graftlint: no changed .py/.gin files" >&2
    exit 0
  fi
  args+=(--cache-file .git/graftlint-cache.json --changed-only
         "${existing[@]}")
fi

exec python -c '
import sys
from tensor2robot_tpu.utils import backend
backend.pin_cpu()
from tensor2robot_tpu.analysis import lint
sys.exit(lint.main(sys.argv[1:]))
' "${args[@]}"
