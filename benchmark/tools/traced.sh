#!/bin/bash
# usage: traced.sh <out-tag> <train-seed> <serve-seed> [trace 0|1, default 1]
# One run of each cell from the checkout this is called in; the result
# lines, the loops' own lines and (traced) each run's .xplane.pb and the
# program's events go to <repo>/chiprun_out/<out-tag>-<cell>.*
# The compile cache is the checkout's own, whatever the machine came with:
# its key leaves metadata out, so an executable that another version of the
# code compiled shows that version's scope names in this one's profile.
export JAX_COMPILATION_CACHE_DIR=$PWD/.compile_cache
tag=$1; trace=${4:-1}
out=${CHIPRUN_OUT:-chiprun_out}; mkdir -p $out
for spec in "train-large-steady $2" "serve-medium-chat $3"; do
  set -- $spec
  python benchmark/run.py --workload $1 --seed $2 --seconds 51 --trace $trace > $out/$tag-$1.txt 2> $out/$tag-$1.err
  echo "rc=$? $tag $1 seed $2 trace $trace"
  grep '^\[bench\]' $out/$tag-$1.txt | grep -v "^\[bench\] steps" | cut -c1-1500
  tail -1 $out/$tag-$1.txt | cut -c1-2600
  if [ "$trace" = 1 ]; then
    cp benchmark/.run/trace-$1/plugins/profile/*/*.xplane.pb $out/$tag-$1.xplane.pb
    cat benchmark/.run/obs-$1/*.jsonl > $out/$tag-$1.events.jsonl
    ls -l $out/$tag-$1.xplane.pb $out/$tag-$1.events.jsonl | awk '{print $5, $9}'
  fi
done
