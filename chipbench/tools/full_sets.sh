#!/bin/bash
# the measurement a bound is set from: two sets of N runs of one cell with the
# same seeds in both, then one traced run.  Result lines go to
# chiprun_out/<cell>.set<k>.jsonl, everything else to <cell>.sets.log.
# usage: full_sets.sh <cell> <seconds> <n> <seed0> [<directory for the results>]
cell=$1; secs=$2; n=$3; seed0=$4; out=${5:-chiprun_out}
mkdir -p $out; : > $out/$cell.sets.log
for k in 1 2; do
  : > $out/$cell.set$k.jsonl
  for i in $(seq 0 $((n - 1))); do
    seed=$((seed0 + 1000003 * i))
    python3 chipbench/run.py --workload $cell --seed $seed --seconds $secs --trace 0 > $out/_run.out 2>> $out/$cell.sets.log
    echo "rc=$? seed=$seed set=$k" >> $out/$cell.sets.log
    grep CHIPBENCH $out/_run.out >> $out/$cell.sets.log
    tail -n 1 $out/_run.out >> $out/$cell.set$k.jsonl
  done
done
python3 chipbench/run.py --workload $cell --seed $((seed0 + 7)) --seconds $secs --trace 1 > $out/_run.out 2>> $out/$cell.sets.log
echo "rc=$? traced" >> $out/$cell.sets.log
grep CHIPBENCH $out/_run.out >> $out/$cell.sets.log
tail -n 1 $out/_run.out > $out/$cell.traced.json
rm -f $out/_run.out
python3 chipbench/tools/spread.py $out/$cell.set1.jsonl $out/$cell.set2.jsonl
