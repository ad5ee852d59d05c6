#!/usr/bin/env bash
# graftcache cold-vs-warm start bench + regression gate.
#
# Runs `bench.py --cache cold` then `bench.py --cache warm` in two
# SEPARATE processes against one cache dir (in-process executables would
# mask the disk round trip): cold evicts the smoke entries and pays
# every compile, warm must report engine_compiles == 0 /
# train_cache_hit == true with every executable deserialized. Both
# headlines (`qtopt_cold_start_ms_cpu_smoke` /
# `qtopt_warm_start_ms_cpu_smoke`, and the warm record's
# `cold_vs_warm_warmup` speedup ratio) append to runs.jsonl; the gate
# then (a) fails loudly if the warm record did not hit the cache, and
# (b) diffs the new warm record against the PREVIOUS warm record with
# `graftscope diff` so a cold-start regression (warmup_ms up-bad,
# cold_vs_warm_warmup down-bad) exits non-zero exactly like a
# throughput one. See PERFORMANCE.md "Reading a cache bench".
#
# Usage: scripts/cache_bench.sh
# (the cache lives at excache.cache_root(): $JAX_COMPILATION_CACHE_DIR
# when set, else the checkout's .graftcache)
set -euo pipefail
cd "$(dirname "$0")/.."

RUNS="${GRAFTSCOPE_RUNS:-runs.jsonl}"

JAX_PLATFORMS=cpu python bench.py --cache cold
JAX_PLATFORMS=cpu python bench.py --cache warm

# Indices of the last two WARM records + the warm-hit sanity check.
# The lookup runs OUTSIDE a process substitution so a failure
# (unreadable runs.jsonl, broken import) fails the script loudly
# instead of reading as "no baseline" and silently skipping the gate
# (same hardening as scripts/data_bench.sh).
IDX_OUT=$(JAX_PLATFORMS=cpu python - "$RUNS" <<'EOF'
import sys
from tensor2robot_tpu.obs import runlog
records = runlog.load_records(sys.argv[1])
warm = [i for i, r in enumerate(records)
        if "warm_start" in str((r.get("bench") or {}).get("metric", ""))]
if not warm:
    sys.exit("cache_bench: no warm record landed in runs.jsonl")
latest = records[warm[-1]]["bench"]
if latest.get("engine_compiles") != 0 or not latest.get("train_cache_hit"):
    sys.exit("cache_bench: warm start COMPILED "
             f"(engine_compiles={latest.get('engine_compiles')}, "
             f"train_cache_hit={latest.get('train_cache_hit')}) — the "
             "executable cache is not serving; see cache/corrupt_entries")
for i in warm[-2:]:
    print(i)
EOF
) || { echo "cache_bench: runs.jsonl warm-record check failed" >&2; exit 1; }
IDX=()
[ -n "$IDX_OUT" ] && mapfile -t IDX <<< "$IDX_OUT"

if [ "${#IDX[@]}" -lt 2 ]; then
  echo "cache_bench: first warm record in $RUNS; no diff baseline yet" >&2
  exit 0
fi

JAX_PLATFORMS=cpu python -m tensor2robot_tpu.bin.graftscope diff \
    "$RUNS#${IDX[0]}" "$RUNS#${IDX[1]}"
