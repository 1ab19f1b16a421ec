#!/bin/bash
# One run of a cell (CELL, by default gpt2-large-serve-backlog) in a checkout under this one (say a parent
# unpacked by `git archive` into the git-ignored _chip_proof/parent), its last
# line echoed and its output kept under chiprun_out/pr/:
#   tools/chip/pairs.sh one <label> <dir> <seed> <trace 0|1>
set -u
OUT=$PWD/chiprun_out/pr
mkdir -p $OUT
CELL=${CELL:-gpt2-large-serve-backlog}
one() {  # label dir seed trace
  local label=$1 dir=$2 seed=$3 trace=$4
  ( cd $dir
    python $OLDPWD/tools/chip/dump_run.py pr/$label --workload $CELL --seed $seed --seconds 30 --trace $trace
  ) > $OUT/$label.out 2> $OUT/$label.err
  echo "== $label rc=$? $(tail -n 1 $OUT/$label.out | cut -c1-${CUT:-1800})"
  grep "^# decode counters" $OUT/$label.err
  # a traced run in the parent's directory writes its ops under the parent
  [ -d $dir/chiprun_out/pr ] && [ "$dir" != "." ] && cp $dir/chiprun_out/pr/* $OUT/ 2>/dev/null
  return 0
}
"$@"
