#!/bin/bash
# usage: extras.sh <workload> <out-tag> [seconds of the traced runs, default
# 51]: six more seeds, three traced and three untraced short ones.
w=$1; tag=$2
for s in 1 2 3 4 5 6; do
  if [ $s -le 3 ]; then t=1; secs=${3:-51}; else t=0; secs=15; fi
  python benchmark/run.py --workload $w --seed $((2147499000+s*7919)) --seconds $secs --trace $t 2>/dev/null | tail -1 > chiprun_out/_line.txt
  echo "EXTRA seed $s trace $t $(cut -c1-3200 chiprun_out/_line.txt)"
  echo "{\"set\":\"X$t\",\"seed\":$s,\"line\":$(cat chiprun_out/_line.txt)}" >> chiprun_out/extras-$w-$tag.jsonl
done
