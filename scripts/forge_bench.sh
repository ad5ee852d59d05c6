#!/usr/bin/env bash
# graftforge cold-vs-forged start bench + regression gate (ISSUE 15).
#
# Runs `bench.py --forge`: a COLD fleet+trainer start in a fresh
# subprocess, the forge farm (`obs.forge.run_forge` worker pool)
# populating the forge_smoke/ namespace of the cache root, then the
# FORGED start in another fresh subprocess. The gate then (a) fails
# loudly unless the forged arm performed ZERO fresh compiles
# (engine_compiles all-zero AND train_cache_hit — the executable farm
# is not serving otherwise; read warmup_provenance to see which rungs
# went cold) and met the 2.0x forged_vs_cold acceptance floor, and
# (b) diffs the new record against the PREVIOUS forge record with
# `graftscope diff` (forged_vs_cold down-bad, forged_start_ms up-bad,
# forge_compile_share up-bad at zero tolerance) so a forge regression
# exits non-zero exactly like a throughput one. See PERFORMANCE.md
# "Reading a forge bench".
#
# Usage: scripts/forge_bench.sh
# (the cache lives at excache.cache_root(): $JAX_COMPILATION_CACHE_DIR
# when set, else the checkout's .graftcache)
set -euo pipefail
cd "$(dirname "$0")/.."

RUNS="${GRAFTSCOPE_RUNS:-runs.jsonl}"

JAX_PLATFORMS=cpu python bench.py --forge

# Indices of the last two forge records + the zero-fresh-compile pin.
# Runs OUTSIDE a process substitution so a failure fails the script
# loudly instead of reading as "no baseline" (data_bench.sh hardening).
IDX_OUT=$(JAX_PLATFORMS=cpu python - "$RUNS" <<'EOF'
import sys
from tensor2robot_tpu.obs import runlog
records = runlog.load_records(sys.argv[1])
forge = [i for i, r in enumerate(records)
         if (r.get("bench") or {}).get("metric")
         == "qtopt_forged_start_ms_cpu_smoke"]
if not forge:
    sys.exit("forge_bench: no forge record landed in runs.jsonl")
latest = records[forge[-1]]["bench"]
compiles = latest.get("engine_compiles")
if compiles is None or any(compiles) or not latest.get("train_cache_hit"):
    sys.exit("forge_bench: forged start COMPILED "
             f"(engine_compiles={compiles}, "
             f"train_cache_hit={latest.get('train_cache_hit')}) — the "
             "forge farm is not serving; see warmup_provenance + "
             "cache/corrupt_entries in the record")
ratio = latest.get("forged_vs_cold")
if ratio is None or ratio < 2.0:
    sys.exit(f"forge_bench: forged_vs_cold {ratio} below the 2.0 "
             "acceptance floor (ISSUE 15)")
for i in forge[-2:]:
    print(i)
EOF
) || { echo "forge_bench: runs.jsonl forge-record check failed" >&2; exit 1; }
IDX=()
[ -n "$IDX_OUT" ] && mapfile -t IDX <<< "$IDX_OUT"

if [ "${#IDX[@]}" -lt 2 ]; then
  echo "forge_bench: first forge record in $RUNS; no diff baseline yet" >&2
  exit 0
fi

JAX_PLATFORMS=cpu python -m tensor2robot_tpu.bin.graftscope diff \
    "$RUNS#${IDX[0]}" "$RUNS#${IDX[1]}"
