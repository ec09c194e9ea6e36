#!/bin/bash
# usage: benchmark/trial.sh <tag> <workload> <seconds> <trace> <seed> [<seed> ...]
# runs the cell once per seed, each in a process of its own, and keeps
# every run's output under chiprun_out/<tag>/
tag=$1; cell=$2; secs=$3; trace=$4; shift 4
mkdir -p chiprun_out/$tag
for seed in "$@"; do
  out=chiprun_out/$tag/${cell}_s${seed}_t${trace}
  start=$(date +%s.%N)
  python3 benchmark/run.py --workload $cell --seed $seed --seconds $secs --trace $trace \
    > $out.out 2> $out.err
  rc=$?
  end=$(date +%s.%N)
  echo "RUN $cell seed=$seed trace=$trace rc=$rc wall=$(python3 -c "print($end - $start)")"
  grep -E '"step": "(first_calls|warm_cycle|window|data)"' $out.out | cut -c1-900
  tail -n 1 $out.out | cut -c1-3000
  grep -v "^check\|^correct" $out.err | tail -n 5 | cut -c1-400
done
