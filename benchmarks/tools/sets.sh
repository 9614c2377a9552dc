#!/bin/sh
# Repeated runs of one cell, one call: sh benchmarks/tools/sets.sh <out.jsonl> <workload> <seconds> <trace> <set tag> <seed>...
# Each run's last line is appended to <out.jsonl> with its workload, set and seed; everything else a run prints goes to <out.jsonl>.log
out=$1; workload=$2; seconds=$3; trace=$4; tag=$5; shift 5
mkdir -p "$(dirname "$out")"
for seed in "$@"; do
  python3 benchmarks/run.py --workload "$workload" --seed "$seed" --seconds "$seconds" --trace "$trace" > "$out.tmp" 2>> "$out.err"
  rc=$?
  grep -v '^check: worst\|^{' "$out.tmp" | cut -c1-400 >> "$out.log"
  line=$(tail -n 1 "$out.tmp")
  case "$line" in
    "{"*) echo "{\"workload\": \"$workload\", \"set\": \"$tag\", \"seed\": $seed, \"rc\": $rc, \"line\": $line}" >> "$out" ;;
    *) echo "run of $workload seed $seed gave no result (rc $rc)" >> "$out.log" ;;
  esac
done
rm -f "$out.tmp"
