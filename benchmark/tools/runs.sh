#!/bin/sh
# Several runs of one cell in one chip call, each a process of its own:
#   chiprun -- sh benchmark/tools/runs.sh <workload> <seconds> <runs> <first seed> <trace 0|1> [tag]
# Prints each run's result line and what it logged; keeps both, and the
# window's per-step host clock, under chiprun_out/runs_<workload>_<tag>/.
W=$1; S=$2; N=$3; SEED=$4; T=${5:-0}; TAG=${6:-s$S}
OUT=chiprun_out/runs_${W}_$TAG; mkdir -p $OUT
i=0
while [ $i -lt $N ]; do
  seed=$((SEED + 7919 * i))
  t0=$(date +%s)
  python3 benchmark/run.py --workload $W --seed $seed --seconds $S --trace $T \
    --dump $OUT > $OUT/run$i.out 2> $OUT/run$i.err
  rc=$?
  echo "== $W seconds=$S trace=$T seed=$seed rc=$rc total=$(( $(date +%s) - t0 ))s"
  tail -n 1 $OUT/run$i.out | cut -c1-2600
  grep '^\[bench\]' $OUT/run$i.err | grep -v 'compared' | tail -n 4
  grep '^\[bench\] compared' $OUT/run$i.err | sed 's/\[bench\] compared //' | cut -c1-60 | tr '\n' ';'; echo
  [ $rc -ne 0 ] && grep -v '^\[bench\]' $OUT/run$i.err | tail -n 25
  i=$((i + 1))
done
