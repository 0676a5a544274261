#!/bin/sh
# One chip call's worth of measurement of one cell, from the directory the
# benchmark is in: a set of untraced runs (tools/repeat.py), then one traced run
# if the call's time allows it. Everything goes under <out>.
#
#   chiprun --timeout <s> -- sh benchmark/tools/measure_cell.sh <cell> <runs> <first seed> <time for all, s> <out dir>
#
# Run it from a copy of the committed files (git archive $(git write-tree) |
# tar -x -C _proof; cd _proof) to prove at the same time that they are enough.
cell=$1; runs=$2; seed=$3; total=$4; out=$5
start=$(date +%s)
mkdir -p "$out"
python3 benchmark/tools/repeat.py --workload "$cell" --runs "$runs" --first-seed "$seed" \
    --out "$out/$cell.set$seed.jsonl" || exit $?
left=$((total - $(date +%s) + start))
echo "measure_cell: $left s left for a traced run"
if [ "$left" -ge 170 ]; then
    timeout "$left" python3 benchmark/run.py --workload "$cell" --seed $((seed + runs)) \
        --seconds "$(python3 -c 'import json; print(json.load(open("BENCHMARK.json"))["run_seconds"])')" \
        --trace 1 > "$out/$cell.t1.log" 2>&1
    echo "measure_cell: traced run ended with $?"
    tail -c 9000 "$out/$cell.t1.log"
fi
