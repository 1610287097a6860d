#!/bin/bash
# usage: sets.sh <workload> <out-tag> [runs-to-a-set, default 6] [first seed
# index, default 1] [sets, default "A B"]; two sets, the same seeds in both. The result lines go to
# chiprun_out/sets-<workload>-<tag>.jsonl, the loop's own lines (every
# step's time, the first token's statistics) to the .log beside it.
w=$1; tag=$2; n=${3:-6}; first=${4:-1}
for set in ${5:-A B}; do for s in $(seq $first $((first+n-1))); do
  python benchmark/run.py --workload $w --seed $((2147480000+s*100003)) --seconds 51 --trace 0 2>/dev/null > chiprun_out/_out.txt
  tail -1 chiprun_out/_out.txt > chiprun_out/_line.txt
  grep '^\[bench\]' chiprun_out/_out.txt | sed "s/^/$set $s /" >> chiprun_out/sets-$w-$tag.log
  echo "SET $set seed $s $(cut -c1-1400 chiprun_out/_line.txt)"
  echo "{\"set\":\"$set\",\"seed\":$s,\"line\":$(cat chiprun_out/_line.txt)}" >> chiprun_out/sets-$w-$tag.jsonl
done; done
