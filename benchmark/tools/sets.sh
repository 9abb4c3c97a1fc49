#!/bin/bash
# Two sets of runs of one cell, the same seeds in both, as the contract's
# rule for a bound asks:  chiprun -- bash benchmark/tools/sets.sh <cell> <seed>...
# Result lines go to chiprun_out/sets/<cell>.jsonl, each run's stderr beside them.
cell=$1; shift
out=chiprun_out/sets; mkdir -p $out
for set in 1 2; do
  for seed in "$@"; do
    python3 benchmark/run.py --workload $cell --seed $seed --seconds ${SECONDS_PER_RUN:-51} --trace 0 \
      > $out/run.out 2> $out/$cell.set$set.$seed.err
    rc=$?
    echo "{\"cell\": \"$cell\", \"set\": $set, \"seed\": $seed, \"rc\": $rc, \"result\": $(tail -n 1 $out/run.out | grep '^{' || echo null)}" | tee -a $out/$cell.jsonl | cut -c1-700
  done
done
