#!/usr/bin/env bash
# graftscope reader wrapper: summarize a model_dir's telemetry, list run
# history, or diff two runs — CPU-pinned.
#
# The reader never uses a JAX backend; an accidental backend init would
# take the chip from the job that owns it, so pin through the one shared
# implementation, utils.backend.pin_cpu (env var + jax.config.update) —
# the same belt-and-braces recipe as scripts/lint.sh.
#
# Usage: scripts/obs_report.sh <model_dir> [--top N]
#        scripts/obs_report.sh --history <model_dir|runs.jsonl>
#        scripts/obs_report.sh --diff <runA> <runB> [--threshold m=rel]
#        scripts/obs_report.sh --trend <model_dir|runs.jsonl> [-k K]
#        scripts/obs_report.sh --postmortem <dir> [--index I] [--list]
#        scripts/obs_report.sh --timeline <dir> [--out timeline.json]
#        scripts/obs_report.sh --watch <dir> [--snapshot] [--json]
#   (run references: model_dir / runs.jsonl, optional #run_id or #index;
#    --trend evaluates drift over ONE run history — median of the last
#    K records vs the prior K, direction-aware thresholds, exit 3 on a
#    flagged trend; --postmortem renders the latest flight-recorder
#    bundle: last steps, incident timeline; --timeline merges graftrace trace-*.json shards under
#    <dir> into one clock-aligned Perfetto JSON; --watch renders the
#    graftwatch fleet dashboard from the metrics shards — exit 0
#    healthy / 1 SLO over budget / 2 no usable shards)
set -euo pipefail
cd "$(dirname "$0")/.."
case "${1:-}" in
  --diff) shift; set -- diff "$@" ;;
  --trend) shift; set -- diff --trend "$@" ;;
  --history) shift; set -- history "$@" ;;
  --postmortem) shift; set -- postmortem "$@" ;;
  --timeline) shift; set -- timeline "$@" ;;
  --watch) shift; set -- watch "$@" ;;
esac
exec python -c '
import sys
from tensor2robot_tpu.utils import backend
backend.pin_cpu()
from tensor2robot_tpu.bin import graftscope
sys.exit(graftscope.main(sys.argv[1:]))
' "$@"
